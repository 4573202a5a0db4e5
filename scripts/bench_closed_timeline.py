#!/usr/bin/env python3
"""One traced run of a closed cell, with a row every five seconds of its
window: where the cell's slow regime begins, and what moved first.

    python3 scripts/bench_closed_timeline.py --workload ycsb_f_closed_4r \
        --seed <n> --seconds 51 --trace 1

`benchmark/run.py` with the same arguments, unchanged (`--trace 1`: the rows
are read from every process's `admin.obs_snapshot`, which an untraced role
does not serve), plus on standard error

    timeline {"t_s": ..., "commits": ..., "tps_limit": ..., "base_tps": ...,
              "measured_tps": ..., "grv_tps": ..., "ceiling_probes": ...,
              "limiting_reason": ..., "kc_advances_by_notify": ...,
              "kc_advances_by_push": ..., "commit_notifies_sent": ...,
              "grv_proxy_queue_p95_ms": ..., "storage_version_lag_p95_ms": ...,
              "storage_version_wait_ms": ..., ..., "busy": {"proxy": ..., ...},
              "covered_s": {"proxy": ..., ...}}

a slice: the commits the proxies acknowledged in it (and the batches and
transactions the resolvers resolved), the ratekeeper's
`get_rates()` at its end (`grv_tps`: read versions granted a second, what
the budget is spent on; `ceiling_probes`: the times `base_tps` was raised
for it, since boot), which path moved the tlogs' known-committed bound
first in the slice (`kc_advances_by_notify`: the proxy's word at the
acknowledgement; `kc_advances_by_push`: the next push's; summed over the
tlogs; `commit_notifies_sent` by the proxies), the slice's own `grv_proxy_queue` p95,
`storage_version_lag` p95, `storage_version_wait` mean and the means of the
other stages a commit and a read cross (`MEANS`; histograms of every process
merged, slice end minus slice start) and each role's busy
share (`loop_busy:<role>` over busy + idle, and under `covered_s` the busy +
idle seconds those samples account for); and, once, after the window

    read_path {<each per-layer metric PR 38 declared>: <value>}

read through the benchmark's own metric files over the window's sources,
whatever cells BENCHMARK.json lists them for (the four-resolver cell's list
is pinned by a test only a `benchmark` PR may edit: PERF.md, section 7).

Nothing of the benchmark is changed on disk: `Observer.watch_window`, the two
`window_sources` and `SocketCluster.open_client` are wrapped in this
process. The rows cost every role one `obs_snapshot` each five seconds.

With `--no-profile` the roles trace their spans but no profiler runs: the
rows then show the cell as an untraced run has it; there is no result line
(exit 3).

TO DELETE with the `benchmark` PR that puts a time series in the result line
(PERF.md, section 7: `Observer.counters` reading the ratekeeper too).
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SLICE_S = 5.0
ROLES = ("client", "proxy", "resolver", "tlog", "storage", "sequencer",
         "ratekeeper")
MEANS = ("storage_version_wait", "read_rpc", "grv_rtt", "resolve_wait",
         "rpc_inbound:resolver.resolve", "coalesce_queue", "device_dispatch",
         "tlog_durable", "rpc_inbound:tlog.push")
# a served counter (the proxies' get_metrics, the tlogs' metrics) -> its
# name in a row, where it is the slice's difference
COUNTS = {"txns_committed": "commits",
          "commit_notifies_sent": "commit_notifies_sent",
          "kc_advances_by_notify": "kc_advances_by_notify",
          "kc_advances_by_push": "kc_advances_by_push"}
READ_PATH_METRICS = (
    "grv_rtt_ms", "read_rpc_ms", "client_loop_busy_share", "grv_queue_ms",
    "grv_queue_p95_ms", "grv_sequencer_ms", "proxy_loop_busy_share",
    "resolve_inbound_ms", "resolver_loop_busy_share", "storage_inbound_ms",
    "storage_version_wait_ms", "storage_version_wait_p95_ms",
    "storage_lookup_ms", "storage_version_lag_p95_ms",
    "storage_loop_busy_share", "tlog_loop_busy_share", "tlog_inbound_ms")


def say(kind: str, doc: dict) -> None:
    print(kind + " " + json.dumps(doc), file=sys.stderr, flush=True)


def slice_row(stages: dict) -> dict:
    """What one slice's merged stage histograms say."""
    def stat(stage, fn):
        h = stages.get(stage)
        return round(fn(h), 3) if h is not None and h.count else None

    busy, covered = {}, {}
    for role in ROLES:
        b, i = (stages.get(f"loop_{k}:{role}") for k in ("busy", "idle"))
        total = (b.sum_ms if b else 0.0) + (i.sum_ms if i else 0.0)
        if total > 0:
            busy[role] = round((b.sum_ms if b else 0.0) / total, 3)
            # busy + idle seconds the role's processes accounted for: the
            # slice's length times its processes, or the rows are askew
            covered[role] = round(total / 1e3, 2)
    row = {
        "grv_proxy_queue_p95_ms": stat("grv_proxy_queue",
                                       lambda h: h.percentile(95)),
        "storage_version_lag_p95_ms": stat("storage_version_lag",
                                           lambda h: h.percentile(95)),
    }
    # means of the stages a commit and a read cross, to see which moves
    for stage in MEANS:
        row[stage.replace("rpc_inbound:", "inbound_") + "_ms"] = stat(
            stage, lambda h: h.mean())
    row.update(busy=busy, covered_s=covered)
    return row


def main() -> int:
    from benchmark import run
    from benchmark.lib import observe, observe_nr
    from benchmark.lib.hist import stages_between
    from foundationdb_tpu.loadgen.deploy import SocketCluster
    from foundationdb_tpu.server import parse_addr

    opened: dict = {}
    open_client = SocketCluster.open_client

    def remember(self):
        loop, t, db = open_client(self)
        opened.update(cluster=self, t=t)
        return loop, t, db

    SocketCluster.open_client = remember

    async def timeline(observer, t_start: float, t_stop: float, now) -> None:
        cluster, t, loop = opened["cluster"], opened["t"], observer.loop
        rk = cluster.ratekeeper_ep(t)
        proxies = [t.endpoint(parse_addr(a), "commit_proxy")
                   for a in cluster.spec["proxy"]]
        tlogs = [t.endpoint(parse_addr(a), "tlog")
                 for a in cluster.spec["tlog"]]

        async def read() -> dict:
            served = ([await p.get_metrics() for p in proxies]
                      + [await g.metrics() for g in tlogs])
            return {
                "dumps": await observer.dumps(),
                "rates": await rk.get_rates() if rk is not None else {},
                # .get: a parent without the notification reads 0
                **{k: sum(m.get(k, 0) for m in served) for k in COUNTS},
                "resolved": await observer.counters(),
            }

        await loop.sleep(max(0.0, t_start - now()))
        prev, at = await read(), t_start
        while at + SLICE_S <= t_stop + 1e-6:
            at += SLICE_S
            await loop.sleep(max(0.0, at - now()))
            cur = await read()
            rates = cur["rates"]
            say("timeline", dict(
                {"t_s": round(at - t_start, 1)},
                **{COUNTS[k]: cur[k] - prev[k] for k in COUNTS},
                # the resolvers' batches and transactions, summed
                **{k: cur["resolved"][k] - prev["resolved"][k]
                   for k in ("batches_resolved", "txns_resolved")},
                **{k: rates.get(k) for k in (
                    "tps_limit", "base_tps", "measured_tps", "grv_tps",
                    "ceiling_probes", "limiting_reason")},
                **slice_row(stages_between(prev["dumps"], cur["dumps"]))))
            prev = cur

    watch_window = observe.Observer.watch_window

    async def watched(self, t_start, t_stop, trace_s, now):
        rows = self.loop.spawn(timeline(self, t_start, t_stop, now),
                               name="bench.timeline")
        out = await watch_window(self, t_start, t_stop, trace_s, now)
        await rows
        return out

    observe.Observer.watch_window = watched

    def with_read_path(module) -> None:
        inner = module.window_sources

        def window_sources(*args, **kwargs):
            sources = inner(*args, **kwargs)
            say("read_path", dict(
                {name: run.read_metric(name, {"sources": sources})
                 for name in READ_PATH_METRICS},
                window=slice_row(sources["spans"])))
            return sources

        module.window_sources = window_sources

    with_read_path(observe)
    with_read_path(observe_nr)
    if "--no-profile" in sys.argv:
        sys.argv.remove("--no-profile")
        rows_only(observe, observe_nr, stages_between)
    return run.main()


def rows_only(observe, observe_nr, stages_between) -> None:
    """`--no-profile`: the roles trace their spans but no profiler is
    started, so the rows show the cell as an untraced run has it (every
    profiled run slows by a fifth from the profiler's window on: PERF.md
    section 5). There is then no device trace and no result line: the run
    prints its rows and `read_path`, and ends there, exit 3."""
    async def no_trace(self, seconds):
        await self.loop.sleep(seconds)

    def spans_only(watched, *_args, **_kwargs):
        spans = stages_between(watched["first"]["dumps"],
                               watched["last"]["dumps"])
        say("read_path", dict(
            {name: _read_metric(name, spans) for name in READ_PATH_METRICS},
            window=slice_row(spans)))
        print("timeline only (--no-profile): no trace, no result line",
              file=sys.stderr)
        sys.exit(3)  # through the driver: the cluster is shut down

    observe.Observer.trace = observe_nr.ObserverNR.trace = no_trace
    observe.window_sources = observe_nr.window_sources = spans_only


def _read_metric(name: str, spans: dict):
    from benchmark import run

    return run.read_metric(name, {"sources": {"spans": spans}})


if __name__ == "__main__":
    sys.exit(main())
