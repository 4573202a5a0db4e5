"""What a run reads from a cluster with SEVERAL resolver processes, each on
a chip of its own: `observe.Observer` with every resolver role's counters,
every launcher's control directory, and the merge of what the processes
report, so that every reader that is there keeps its meaning.

- Counters are summed over the roles; `ranges_received` and
  `txns_with_ranges` (what a resolver was SENT after the proxy's clip) are
  also kept a role, for the share of the fullest.
- `start`, `stop` and `reduce` go to all launchers at once, so the traces
  cover the same seconds of the window.
- The merged `trace` is the reduction of the BUSIEST chip, whole:
  `window_s`, `busy_s`, `modules`, `device_ops` and `idle_gaps` of one
  process, so that `busy_s <= window_s` and device time per batch divide
  like by like. `chips` lists every chip's own numbers.
- The merged `device` is one platform and kind, `count` the sum of the
  processes' device counts, `memory_peak_bytes` the fullest chip's.
"""

from __future__ import annotations

import json
import os
import time

from benchmark.lib import observe
from benchmark.lib.control import POLL_S, Control, ControlError
from benchmark.lib.hist import stages_between
from benchmark.readers.device_per_batch import executions

ROLE_COUNTERS = ("ranges_received", "txns_with_ranges")
FIXTURE_EVENTS = 150  # per line and chip, of the 400 dump_planes keeps


class _Asked:
    """`op` sent to every launcher at once; `done()` gathers the replies,
    in the launchers' order, and raises once the time is up."""

    def __init__(self, controls: list, op: str, timeout_s: float,
                 args: "list | None" = None):
        self.op, self.timeout_s = op, timeout_s
        self.paths = [c.send(op, **a) for c, a in
                      zip(controls, args or [{}] * len(controls))]
        self.replies: list = [None] * len(controls)
        self.deadline = time.monotonic() + timeout_s

    def done(self) -> bool:
        for i, path in enumerate(self.paths):
            if self.replies[i] is None:
                self.replies[i] = Control.take(path)
        if all(r is not None for r in self.replies):
            return True
        if time.monotonic() > self.deadline:
            raise ControlError(
                f"a resolver process did not answer {self.op!r} within "
                f"{self.timeout_s:.0f}s")
        return False


def call_all(controls: list, op: str, timeout_s: float = 120.0,
             args: "list | None" = None) -> list:
    """`op` to every launcher at once, then every reply, in order;
    `args[i]` are launcher i's arguments. Blocking form."""
    asked = _Asked(controls, op, timeout_s, args)
    while not asked.done():
        time.sleep(POLL_S)
    return asked.replies


async def acall_all(loop, controls: list, op: str,
                    timeout_s: float = 120.0) -> list:
    """The same from a coroutine of the program's event loop."""
    asked = _Asked(controls, op, timeout_s)
    while not asked.done():
        await loop.sleep(POLL_S)
    return asked.replies


class ObserverNR(observe.Observer):
    def __init__(self, loop, controls: list, resolver_eps: list,
                 admin_eps: list, client_sink=None):
        super().__init__(loop, None, None, admin_eps, client_sink)
        self.controls = controls
        self.resolver_eps = resolver_eps

    async def counters(self) -> dict:
        """The roles' counters summed, and under `per_role` what each
        resolver was sent."""
        ms = [await ep.get_metrics() for ep in self.resolver_eps]
        out = {k: sum(m[k] for m in ms)
               for k in observe.COUNTERS + ROLE_COUNTERS}
        out.update({k: sum(m["engine"][k] for m in ms)
                    for k in observe.ENGINE_COUNTERS})
        out["per_role"] = {k: [m[k] for m in ms] for k in ROLE_COUNTERS}
        return out

    async def trace(self, seconds: float) -> list:
        await acall_all(self.loop, self.controls, "start")
        await self.loop.sleep(seconds)
        return await acall_all(self.loop, self.controls, "stop")


def counters_between(first: dict, last: dict) -> dict:
    out = {k: last[k] - first[k] for k in last if k != "per_role"}
    out["per_role"] = {
        k: [b - a for a, b in zip(first["per_role"][k], v)]
        for k, v in last["per_role"].items()}
    return out


def share_fullest_pct(received: list) -> float:
    """The fullest resolver's share of what all were sent, in %; 100 where
    none was sent anything (no split was exercised)."""
    total = sum(received)
    return 100.0 * max(received) / total if total else 100.0


def merge_reports(reports: list) -> dict:
    kinds = {(r["platform"], r["kind"]) for r in reports}
    if len(kinds) != 1:
        raise RuntimeError(f"the resolvers sit on unlike devices: {kinds}")
    platform, kind = kinds.pop()
    return {"platform": platform, "kind": kind,
            "count": sum(r["count"] for r in reports),
            "memory_peak_bytes": max(r["memory_peak_bytes"]
                                     for r in reports)}


def merge_traces(reduced: list, reports: list,
                 module: str = "resolve") -> tuple[dict, list]:
    """(the busiest chip's reduction with every chip's planes named, every
    chip's own numbers) of the processes' `trace_reduce` reductions."""
    chips = [{"resolver": i, "busy_s": tr["busy_s"],
              "window_s": tr["window_s"],
              "executions": executions(tr, module),
              "memory_peak_bytes": rep["memory_peak_bytes"]}
             for i, (tr, rep) in enumerate(zip(reduced, reports))]
    busiest = max(range(len(reduced)), key=lambda i: reduced[i]["busy_s"])
    trace = dict(reduced[busiest], busiest=busiest, device_planes=[
        f"resolver{i}:{p}" for i, tr in enumerate(reduced)
        for p in tr["device_planes"]])
    return trace, chips


def join_fixtures(fixture: str, n: int) -> None:
    """The launchers' `dump_planes` files `<fixture>.<i>` into one: a list
    of one trace a chip, cut to FIXTURE_EVENTS events a line."""
    out = []
    for i in range(n):
        part = f"{fixture}.{i}"
        with open(part) as f:
            planes = json.load(f)
        out.append([[name, [[ln, events[:FIXTURE_EVENTS]]
                            for ln, events in lines]]
                    for name, lines in planes])
        os.remove(part)
    with open(fixture, "w") as f:
        json.dump(out, f)


def window_sources(watched: dict, controls: list, reports: list,
                   fixture: "str | None") -> dict:
    """`observe.window_sources` over several resolver processes: spans
    merged over every process, counters summed, the traces reduced by the
    processes that took them (at once, after the window) and merged."""
    first, last = watched["first"], watched["last"]
    reduced = call_all(controls, "reduce", timeout_s=300, args=[
        {"fixture": f"{fixture}.{i}" if fixture else None,
         "xplane": s["xplane"], "window_s": s["window_s"]}
        for i, s in enumerate(watched["stopped"])])
    if fixture:
        join_fixtures(fixture, len(controls))
    counters = counters_between(first["counters"], last["counters"])
    per_role = counters.pop("per_role")
    trace, chips = merge_traces(reduced, reports)
    out = {
        "spans": stages_between(first["dumps"], last["dumps"]),
        "counters": counters,
        "ranges_received": per_role["ranges_received"],
        "ranges_share_fullest_pct": share_fullest_pct(
            per_role["ranges_received"]),
        "trace": trace,
        "chips": chips,
    }
    busy = [c["busy_s"] for c in chips]
    if not trace["stand_in"] and max(busy) > 0:
        # never a CPU number under a device metric's name
        out["chip_busy_least_over_most"] = min(busy) / max(busy)
    return out
