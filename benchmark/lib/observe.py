"""What a run reads from the program besides its answers: the obs span dumps
of every process (`admin.obs_snapshot`, FDB_TPU_OBS=1), the resolver role's
counters (`get_metrics`), and, through the launcher's control directory, the
profiler trace and the device report of the process that holds the chip.

The span dumps and counters are cumulative since boot, so a window is read
twice, at its start and at its end, and reported as the difference.
"""

from __future__ import annotations

from benchmark.lib.hist import stages_between

COUNTERS = ("batches_resolved", "txns_resolved", "txns_conflicted",
            "overflow_events", "txns_rejected_fail_safe", "resolve_failures")
ENGINE_COUNTERS = ("full_repacks", "evictions", "auto_reshards")


class Observer:
    def __init__(self, loop, control, resolver_ep, admin_eps: list,
                 client_sink=None):
        self.loop = loop
        self.control = control
        self.resolver_ep = resolver_ep
        self.admin_eps = admin_eps
        self.client_sink = client_sink

    async def counters(self) -> dict:
        m = await self.resolver_ep.get_metrics()
        out = {k: m[k] for k in COUNTERS}
        out.update({k: m["engine"][k] for k in ENGINE_COUNTERS})
        return out

    async def dumps(self) -> list:
        """Every process's span dump; a process that runs untraced has
        none, which is an error of a traced run."""
        out = []
        for ep in self.admin_eps:
            snap = await ep.obs_snapshot()
            if not snap.get("enabled"):
                raise RuntimeError("a role runs without FDB_TPU_OBS=1")
            out.append(snap["dump"])
        if self.client_sink is not None:
            out.append(self.client_sink.dump())
        return out

    async def snapshot(self) -> dict:
        return {"dumps": await self.dumps(), "counters": await self.counters()}

    async def trace(self, seconds: float) -> dict:
        """Trace the chip's process for `seconds`; the reduction waits for
        `reduce`, after the window, so it does not slow the role."""
        await self.control.acall(self.loop, "start")
        await self.loop.sleep(seconds)
        return await self.control.acall(self.loop, "stop")

    async def watch_window(self, t_start: float, t_stop: float,
                           trace_s: float, now) -> dict:
        """Snapshot at the window's start, trace `trace_s` seconds in its
        middle, snapshot at its end. `now` is the host clock the window was
        set on."""
        await self.loop.sleep(max(0.0, t_start - now()))
        first = await self.snapshot()
        middle = (t_start + t_stop) / 2 - trace_s / 2
        await self.loop.sleep(max(0.0, middle - now()))
        stopped = await self.trace(trace_s)
        await self.loop.sleep(max(0.0, t_stop - now()))
        last = await self.snapshot()
        return {"first": first, "last": last, "stopped": stopped}


def window_sources(watched: dict, control, fixture: "str | None") -> dict:
    """The span histograms and counter differences of a watched window, and
    its trace, reduced now (after the window) by the process that took it."""
    first, last = watched["first"], watched["last"]
    stopped = watched["stopped"]
    return {
        "spans": stages_between(first["dumps"], last["dumps"]),
        "counters": {k: last["counters"][k] - first["counters"][k]
                     for k in last["counters"]},
        "trace": control.call("reduce", timeout_s=300, fixture=fixture,
                              xplane=stopped["xplane"],
                              window_s=stopped["window_s"]),
    }
