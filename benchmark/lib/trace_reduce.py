"""From a profiler trace (`.xplane.pb`) to the numbers the benchmark reports.

Runs inside the process that took the trace (benchmark/lib/resolver_proc.py):
only the process that holds the chip can trace it, and the harness never
loads JAX. `reduce_xplane` reads the file with `jax.profiler.ProfileData`;
`reduce_planes` does the arithmetic on plain tuples, so it is testable
without a trace.

What a trace of this program holds (looked at by hand, PR 23; a TPU v5e):
the chip is the plane `/device:TPU:0`. Its line `XLA Ops` has one event per
HLO operation that ran (named by its whole HLO text), its line `XLA Modules`
one event per execution of a jitted program (`jit__resolve_res_jit(<id>)`),
and `Async XLA Ops` the copies that overlap them, which busy time leaves out. The host is the plane `/host:CPU`, one line per thread, with
the runtime's TraceMe events. On the CPU backend (the rehearsal) there is no
device plane; the XLA client's own threads (`tf_XLA...` lines of
`/host:CPU`) stand in for it.
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
CPU_BACKEND_LINE = re.compile(r"^tf_XLA")
TOP = 10
ATTRIBUTED_GAPS = 200  # the longest; the rest are summed under one name


def union_seconds(intervals) -> tuple[float, list]:
    """(total length, merged intervals) of [(start_ns, end_ns)]."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged) / 1e9, merged


def _strip_id(name: str) -> str:
    """`jit__resolve_res_jit(123456789)` -> `jit__resolve_res_jit`."""
    return re.sub(r"\(\d+\)$", "", name)


def _op_name(name: str) -> str:
    """The chip's trace names an operation by its whole HLO text:
    `%fusion.275 = s32[16386]{0:T(1024)S(1)} fusion(...), kind=kCustom, ...`
    -> `%fusion.275 = s32[16386] fusion`: the name, the result's shape
    without its layout, the opcode."""
    m = re.match(r"^(%\S+) = (\(?[a-z0-9]+\[[0-9,]*\])[^ ]*"
                 r"(?:.*?\) | )([a-z\-]+)\(", name)
    return f"{m.group(1)} = {m.group(2)} {m.group(3)}" if m else name[:100]


def reduce_planes(planes, window_s: float) -> dict:
    """`planes`: [(plane name, [(line name, [(event name, start_ns,
    duration_ns)])])]. `window_s`: the traced window on the tracing
    process's clock. Returns busy_s averaged over the device planes (the
    union of the intervals in which an operation ran), device time by
    operation and by program, and the longest idle gaps by what the host
    was doing in them."""
    devices = [(n, ls) for n, ls in planes if DEVICE_PLANE.match(n)]
    host_lines = [ls for n, ls in planes if n == HOST_PLANE]
    host_lines = host_lines[0] if host_lines else []
    stand_in = not devices
    if stand_in:
        # CPU backend: its executor threads are the "device".
        devices = [(HOST_PLANE, [(ln, ev) for ln, ev in host_lines
                                 if CPU_BACKEND_LINE.match(ln)])]
        host_lines = [(ln, ev) for ln, ev in host_lines
                      if not CPU_BACKEND_LINE.match(ln)]

    busy, by_op, by_module, gaps_by_host = [], {}, {}, {}
    first_ns, last_ns = None, None
    host = _HostEvents(host_lines)
    for _name, lines in devices:
        op_lines = [ev for ln, ev in lines if ln == OPS_LINE] or \
                   [ev for ln, ev in lines if ln != MODULES_LINE]
        intervals = []
        for events in op_lines:
            for name, start, dur in events:
                if dur <= 0:
                    continue
                intervals.append((start, start + dur))
                rec = by_op.setdefault(_op_name(name), [0, 0.0])
                rec[0] += 1
                rec[1] += dur / 1e9
        for ln, events in lines:
            if ln != MODULES_LINE:
                continue
            for name, _start, dur in events:
                rec = by_module.setdefault(_strip_id(name), [0, 0.0])
                rec[0] += 1
                rec[1] += dur / 1e9
        seconds, merged = union_seconds(intervals)
        busy.append(seconds)
        if merged:
            first_ns = merged[0][0] if first_ns is None \
                else min(first_ns, merged[0][0])
            last_ns = merged[-1][1] if last_ns is None \
                else max(last_ns, merged[-1][1])
        gaps = sorted(((s1 - e0, (e0 + s1) / 2) for (_s0, e0), (s1, _e1)
                       in zip(merged, merged[1:])), reverse=True)
        for i, (length, middle) in enumerate(gaps):
            what = host.at(middle) if i < ATTRIBUTED_GAPS \
                else "gaps beyond the longest %d" % ATTRIBUTED_GAPS
            gaps_by_host[what] = gaps_by_host.get(what, 0.0) + length / 1e9
    if stand_in:
        # The stand-in has no `XLA Modules` line: the host's PjitFunction
        # TraceMes count program executions instead.
        for _ln, events in host_lines:
            for name, _start, dur in events:
                if name.startswith("PjitFunction("):
                    rec = by_module.setdefault(name[13:-1], [0, 0.0])
                    rec[0] += 1
                    rec[1] += dur / 1e9
    busy_s = sum(busy) / len(busy) if busy else 0.0
    # The window is the tracing process's own clock around start and stop;
    # where the device events themselves span more (clock granularity),
    # the longer of the two is the window.
    if first_ns is not None:
        window_s = max(window_s, (last_ns - first_ns) / 1e9)

    def top(d: dict, key) -> list:
        return [[k, key(v)] for k, v in
                sorted(d.items(), key=lambda kv: -key(kv[1]))[:TOP]]

    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "device_planes": [n for n, _ in devices],
        "stand_in": stand_in,
        "device_ops": top(by_op, lambda v: v[1]),
        "idle_gaps": top(gaps_by_host, lambda v: v),
        "modules": {k: v for k, v in sorted(
            by_module.items(), key=lambda kv: -kv[1][1])[:TOP]},
    }


class _HostEvents:
    """The host plane's TraceMe events, to ask what covered an instant."""

    def __init__(self, host_lines):
        import numpy as np

        rows = [(name, start, dur) for _ln, events in host_lines
                for name, start, dur in events if dur > 0]
        self.names = [r[0] for r in rows]
        self.start = np.array([r[1] for r in rows], np.float64)
        self.dur = np.array([r[2] for r in rows], np.float64)
        self._np = np

    def at(self, t_ns: float) -> str:
        """The innermost event that covers instant `t_ns`."""
        np = self._np
        hit = np.flatnonzero((self.start <= t_ns)
                             & (t_ns <= self.start + self.dur))
        if not hit.size:
            return "host: no TraceMe event"
        name = self.names[int(hit[np.argmin(self.dur[hit])])]
        return _strip_id(name)[:80]


def reduce_xplane(path: str, window_s: float) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = [(pl.name, [(ln.name, [(ev.name, ev.start_ns, ev.duration_ns)
                                    for ev in ln.events])
                         for ln in pl.lines])
              for pl in pd.planes]
    return reduce_planes(planes, window_s)


def dump_planes(path: str, max_events: int = 400) -> list:
    """A trace cut down to a fixture: per line the first `max_events`
    events. JSON-ready; `reduce_planes` takes it as it is."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    return [[pl.name, [[ln.name, [[ev.name, ev.start_ns, ev.duration_ns]
                                  for ev in list(ln.events)[:max_events]]]
                       for ln in pl.lines]]
            for pl in pd.planes]
