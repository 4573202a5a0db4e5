#!/usr/bin/env python3
"""One run of a benchmark cell that also reports how often the resolver's
overlap engaged.

    python3 scripts/bench_overlap.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

`benchmark/run.py` with the same arguments, unchanged, plus one line on
standard error each time the run reads the resolver's counters:

    overlap {"batches_resolved": ..., "batches_overlapped": ...,
             "pipeline_drains": {...}, "share": ...,
             "dispatches": ..., "hist_merges": ..., "merges_per_dispatch": ...}

(the last three where the program's engine counts the window history's
merges: how often a dispatch's paint did not fit the delta, PR 41)

summed over the cell's resolvers, cumulative since boot (the last such
line is the whole run's). `benchmark/lib/observe.py` reads a fixed tuple
of counters and only a `benchmark` PR may edit it (PERF.md, section 7:
`batches_overlapped_share`); until one does, this is how the figure is
read. Nothing of the benchmark is changed on disk: the observers'
`counters` methods are wrapped in this process.

TO DELETE with the `benchmark` PR that declares `batches_overlapped_share`
(`COUNTERS` += `batches_overlapped`): the result line then carries the
figure and nothing needs this wrapper.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _report(metrics: list) -> None:
    if not all("batches_overlapped" in m for m in metrics):
        return  # a program without the overlap: nothing to report
    drains: dict = {}
    for m in metrics:
        for cause, n in m["pipeline_drains"].items():
            drains[cause] = drains.get(cause, 0) + n
    resolved = sum(m["batches_resolved"] for m in metrics)
    overlapped = sum(m["batches_overlapped"] for m in metrics)
    out = {
        "batches_resolved": resolved, "batches_overlapped": overlapped,
        "pipeline_drains": drains,
        "share": round(overlapped / resolved, 4) if resolved else None,
    }
    if all("hist_merges" in m["engine"] for m in metrics):
        dispatches = sum(m["engine"]["dispatches"] for m in metrics)
        merges = sum(m["engine"]["hist_merges"] for m in metrics)
        out.update(dispatches=dispatches, hist_merges=merges,
                   merges_per_dispatch=round(merges / dispatches, 4)
                   if dispatches else None)
    print("overlap " + json.dumps(out), file=sys.stderr, flush=True)


def _wrap(cls) -> None:
    inner = cls.counters

    async def counters(self):
        out = await inner(self)
        eps = getattr(self, "resolver_eps", None) or [self.resolver_ep]
        _report([await ep.get_metrics() for ep in eps])
        return out

    cls.counters = counters


def main() -> int:
    from benchmark import run
    from benchmark.drivers import (
        cluster_mesh,
        resolver_replay_mako,
        resolver_replay_tpcc,
    )
    from benchmark.lib import observe, observe_nr

    for cls in (observe.Observer, observe_nr.ObserverNR,
                cluster_mesh.Observer,
                resolver_replay_mako.Observer, resolver_replay_tpcc.Observer):
        _wrap(cls)
    return run.main()


if __name__ == "__main__":
    sys.exit(main())
