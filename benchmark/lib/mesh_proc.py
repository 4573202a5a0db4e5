"""The resolver launcher for ONE resolver process that holds SEVERAL chips
(a spec with `resolver_mesh`): `resolver_proc`'s `served` mode with one more
thing in the reply to `reduce`, the numbers a mesh adds to a trace.

    python -m benchmark.lib.mesh_proc --ctl DIR served <server.py's arguments>

The process's trace then holds one plane a chip (`/device:TPU:0` ...
`/device:TPU:3`), each with its own `XLA Ops` and `XLA Modules` lines: the
one SPMD program runs on all of them at once. `trace_reduce.reduce_planes`
averages the planes' busy time and SUMS their executions, so a reader that
divides the one by the other is out by the number of chips; and it keeps the
ten largest operations, among which a cross-chip reduction of a few
microseconds never is. `mesh_numbers` keeps, a plane: busy seconds,
executions of the resolve program, and the seconds of the collectives
(`all-gather`, `all-reduce`, ... on the `XLA Ops` line: their own time, the
wait for the slowest chip included). The reply's `mesh` is that; everything
else in it is `reduce_planes`' own, unchanged.
"""

from __future__ import annotations

import json
import re
import sys

from benchmark.lib.resolver_proc import ControlThread
from benchmark.lib.trace_reduce import (
    DEVICE_PLANE,
    MODULES_LINE,
    OPS_LINE,
    _op_name,
    _strip_id,
    reduce_planes,
    union_seconds,
)

COLLECTIVE = re.compile(
    r"all-gather|all-reduce|all-to-all|reduce-scatter|collective-permute"
    r"|collective-broadcast")
TOP = 6


def mesh_numbers(planes, module: str = "resolve") -> dict:
    """`planes` as `trace_reduce.reduce_planes` takes them. Per device
    plane, in the planes' order: `busy_s` (the union of the intervals in
    which an operation ran), `executions` of the programs whose name
    matches `module`, `collective_s` (the summed durations of the
    collective operations) and how many ran; and the collectives by name,
    largest first, over all planes. No device plane (the CPU backend's
    stand-in): no planes, and the readers report nothing."""
    out, by_name = [], {}
    for name, lines in planes:
        if not DEVICE_PLANE.match(name):
            continue
        intervals, coll_s, coll_n, runs = [], 0.0, 0, 0
        for ln, events in lines:
            if ln == OPS_LINE:
                for ev, start, dur in events:
                    if dur <= 0:
                        continue
                    intervals.append((start, start + dur))
                    op = _op_name(ev)
                    if COLLECTIVE.search(op):
                        coll_s += dur / 1e9
                        coll_n += 1
                        rec = by_name.setdefault(op, [0, 0.0])
                        rec[0] += 1
                        rec[1] += dur / 1e9
            elif ln == MODULES_LINE:
                runs += sum(1 for ev, _s, _d in events
                            if re.search(module, _strip_id(ev)))
        out.append({"plane": name, "busy_s": union_seconds(intervals)[0],
                    "executions": runs, "collective_s": coll_s,
                    "collectives": coll_n})
    return {"planes": out, "collective_ops": [
        [k, n, s] for k, (n, s) in sorted(
            by_name.items(), key=lambda kv: -kv[1][1])[:TOP]]}


def load_planes(path: str) -> list:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    return [(pl.name, [(ln.name, [(ev.name, ev.start_ns, ev.duration_ns)
                                  for ev in ln.events])
                       for ln in pl.lines])
            for pl in pd.planes]


class MeshControlThread(ControlThread):
    def op_reduce(self, doc) -> dict:
        from benchmark.lib.trace_reduce import dump_planes

        planes = load_planes(doc["xplane"])
        out = reduce_planes(planes, doc["window_s"])
        out["mesh"] = mesh_numbers(planes)
        if doc.get("fixture"):
            with open(doc["fixture"], "w") as f:
                json.dump(dump_planes(doc["xplane"]), f)
        return out


def main(argv: "list[str] | None" = None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) < 3 or argv[0] != "--ctl" or argv[2] != "served":
        raise SystemExit(__doc__)
    MeshControlThread(argv[1]).start()
    from foundationdb_tpu.server import main as server_main

    server_main(argv[3:])


if __name__ == "__main__":
    sys.exit(main())
