"""Device time by kernel phase and idle time by program stage, from the same
profiler trace `trace_reduce.py` reads: the part of the reduction that needs
the names PR 24 put into the program (`jax.named_scope` on the kernel's
phases, `fdb:<stage>` TraceAnnotations around the engine's host stages).

`reduce_planes` here takes the plane list `trace_reduce.reduce_planes` takes,
with one more entry per device plane, which that function passes over:
`["op_paths /device:TPU:0", [[event name, op_path], ...]]`, where `op_path`
is the operation's `jax` name path (`jit(_resolve_res_jit)/jit(main)/
dict_insert/cond/...`; see SCOPE_STAT for where a trace keeps it). Without
the entry (the older fixtures) every operation is `(unscoped)`. It returns

- `device_scopes`: device SELF seconds by scope. An event's self time is its
  duration minus the events nested in it on the same `XLA Ops` line (a
  `conditional` holds its branch's `while`, which holds its body's fusions),
  so the scopes partition the busy time: they sum to `busy_s` wherever the
  line's events nest properly. `(unscoped)`: operations outside every scope.
- `gap_spans`: idle seconds (the gaps between busy intervals, every one),
  each gap cut up among the `fdb:` annotations of the host's plane that
  overlap it, innermost first; `(no span)` for what none covers. (A gap
  between two executions spans decode, pack, rank and enqueue: naming it
  by its midpoint alone moved whole gaps from stage to stage with the
  tracer's own cost.)
- `device_ops`, `idle_gaps`: the ten largest as `trace_reduce` names them,
  each with its scope, or the span at its midpoint, in front: `dict_insert
  / %while.119 = s32[131073] while`, `dict_rank / conflict_set.py:49
  _rows_to_u64`.

Nothing here changes what `trace_reduce.reduce_planes` computes; the two are
read side by side.
"""

from __future__ import annotations

from benchmark.lib.trace_reduce import (
    ATTRIBUTED_GAPS,
    DEVICE_PLANE,
    HOST_PLANE,
    MODULES_LINE,
    OPS_LINE,
    TOP,
    _HostEvents,
    _op_name,
    union_seconds,
)

#: The kernel's vocabulary (foundationdb_tpu/models/conflict_kernel.py).
SCOPES = ("dict_insert", "dict_evict", "dict_remap", "hist_merge",
          "history_probe", "endpoint_ranks", "accept", "paint_compact",
          "verdicts")
UNSCOPED = "(unscoped)"
PATHS_PLANE = "op_paths "  # + the device plane's name
NO_SPAN = "(no span)"
SPAN_PREFIX = "fdb:"
#: Where the chip's trace keeps an operation's name path (looked at by hand,
#: PR 24): not in the event's name (the HLO text, without its metadata) nor in
#: the event's own stats (`device_offset_ps`, `device_duration_ps`), but in
#: the stat `tf_op` of the event's METADATA record on the device's plane
#: (`jit(_resolve_res_jit)/jit(main)/dict_insert/cond/...:`), which
#: `jax.profiler.ProfileData` does not expose: `op_paths` reads it from the
#: file's protobuf directly.
SCOPE_STAT = "tf_op"


def _varint(buf: memoryview, i: int) -> tuple:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf: memoryview):
    """(field number, wire type, value) of one protobuf message; a
    length-delimited value is a view, never a copy."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield field, wire, value


def op_paths(path: str) -> dict:
    """{plane name: {event name: `tf_op`}} for the device planes of an
    `.xplane.pb`. The schema (tsl/profiler/protobuf/xplane.proto): XSpace
    {1: planes}; XPlane {2: name, 4: event_metadata map, 5: stat_metadata
    map}; a map entry {1: key, 2: value}; XEventMetadata {2: name, 5: stats};
    XStat {1: metadata_id, 5: str_value, 7: ref_value (a stat_metadata id
    whose name is the string)}; XStatMetadata {2: name}. Lines and events,
    most of the file, are skipped by their length."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict = {}
    for field, _w, plane in _fields(space):
        if field != 1:
            continue
        name, event_md, stat_names = "", [], {}
        for pf, _pw, value in _fields(plane):
            if pf == 2:
                name = bytes(value).decode()
            elif pf == 4:
                event_md.append(value)
            elif pf == 5:
                entry = {k: v for k, _x, v in _fields(value)}
                stat_names[entry[1]] = next(
                    (bytes(v).decode() for k, _x, v in _fields(entry[2])
                     if k == 2), "")
        if not DEVICE_PLANE.match(name):
            continue
        want = {i for i, n in stat_names.items() if n == SCOPE_STAT}
        paths = out.setdefault(name, {})
        for entry in event_md:
            md = next(v for k, _x, v in _fields(entry) if k == 2)
            ev_name, op = "", ""
            for k, _x, v in _fields(md):
                if k == 2:
                    ev_name = bytes(v).decode()
                elif k == 5:
                    stat = {sk: sv for sk, _y, sv in _fields(v)}
                    if stat.get(1) in want:
                        op = (bytes(stat[5]).decode() if 5 in stat
                              else stat_names.get(stat.get(7), ""))
            if op:
                paths[ev_name] = op
    return out


def scope_of(op_path: str) -> str:
    """The outermost component of `op_path` that is one of SCOPES."""
    for part in op_path.split("/"):
        if part in SCOPES:
            return part
    return UNSCOPED


def self_seconds(events, paths: dict) -> list:
    """[(event, self seconds, scope)] for the events of ONE line. Self: the
    event's duration minus its direct children's (events that start and end
    inside it; events that only partly overlap count as siblings). Scope:
    `scope_of` its path in `paths` ({event name: path}); a control-flow
    operation, which the chip's trace
    gives no path (`conditional`, `while`), takes the scope most of its
    children's time lies in, so `%while.119` reads `dict_insert` like the
    fusions of its body."""
    rows = sorted((e for e in events if e[2] > 0),
                  key=lambda e: (e[1], -e[2]))
    selfs = [float(e[2]) for e in rows]
    parent = [-1] * len(rows)
    stack: list = []  # indices of the open events, outermost first
    for i, e in enumerate(rows):
        start, end = e[1], e[1] + e[2]
        while stack and rows[stack[-1]][1] + rows[stack[-1]][2] <= start:
            stack.pop()
        if stack and end <= rows[stack[-1]][1] + rows[stack[-1]][2]:
            parent[i] = stack[-1]
            selfs[stack[-1]] -= e[2]
        stack.append(i)
    scopes = [scope_of(paths.get(e[0], "")) for e in rows]
    by_child: dict = {}
    for i in range(len(rows) - 1, -1, -1):  # children before their parents
        if scopes[i] == UNSCOPED and i in by_child:
            scopes[i] = max(by_child[i].items(), key=lambda kv: kv[1])[0]
        if parent[i] >= 0 and scopes[i] != UNSCOPED:
            held = by_child.setdefault(parent[i], {})
            held[scopes[i]] = held.get(scopes[i], 0.0) + rows[i][2]
    return [(e, s / 1e9, sc) for e, s, sc in zip(rows, selfs, scopes)]


class _Spans:
    """The host plane's `fdb:` annotations, flattened to the stretches in
    which one of them is the INNERMOST: annotations of one thread nest (the
    `device_dispatch` umbrella holds the engine's stages), so a sweep with a
    stack cuts them into disjoint pieces."""

    def __init__(self, host_lines):
        import numpy as np

        starts, ends, names = [], [], []
        for _ln, events in host_lines:
            rows = sorted(((e[1], e[1] + e[2], e[0][len(SPAN_PREFIX):])
                           for e in events
                           if e[0].startswith(SPAN_PREFIX) and e[2] > 0),
                          key=lambda r: (r[0], -r[1]))
            stack: list = []  # (end, name) of the open annotations
            cursor = None

            def emit(until):
                if stack and cursor is not None and until > cursor:
                    starts.append(cursor)
                    ends.append(until)
                    names.append(stack[-1][1])

            for start, end, name in rows:
                while stack and stack[-1][0] <= start:
                    emit(stack[-1][0])
                    cursor = stack.pop()[0]
                emit(start)
                cursor = start
                stack.append((end, name))
            while stack:
                emit(stack[-1][0])
                cursor = stack.pop()[0]
        order = np.argsort(np.asarray(starts, np.float64), kind="stable")
        self.start = np.asarray(starts, np.float64)[order]
        self.end = np.asarray(ends, np.float64)[order]
        self.names = [names[i] for i in order]
        self._np = np

    def at(self, t_ns: float) -> str:
        """The innermost annotation that covers instant `t_ns`."""
        np = self._np
        hit = np.flatnonzero((self.start <= t_ns) & (t_ns < self.end))
        return self.names[int(hit[0])] if hit.size else NO_SPAN

    def split(self, t0: float, t1: float, into: dict) -> None:
        """Add the seconds of [t0, t1) to `into` by innermost annotation,
        the uncovered rest under NO_SPAN."""
        np = self._np
        covered = 0.0
        for i in np.flatnonzero((self.start < t1) & (self.end > t0)):
            part = float(min(t1, self.end[i]) - max(t0, self.start[i]))
            into[self.names[i]] = into.get(self.names[i], 0.0) + part / 1e9
            covered += part
        if t1 - t0 - covered > 0:
            into[NO_SPAN] = into.get(NO_SPAN, 0.0) + (t1 - t0 - covered) / 1e9


def reduce_planes(planes) -> dict:
    devices = [(n, ls) for n, ls in planes if DEVICE_PLANE.match(n)]
    host_lines = [ls for n, ls in planes if n == HOST_PLANE]
    host_lines = host_lines[0] if host_lines else []
    if not devices:
        return {}  # the CPU backend's stand-in has no operation paths
    spans, host = _Spans(host_lines), _HostEvents(host_lines)
    paths = {n[len(PATHS_PLANE):]: dict(rows) for n, rows in planes
             if n.startswith(PATHS_PLANE)}
    scopes: dict = {}
    by_op: dict = {}
    gap_spans: dict = {}
    gaps_by_host: dict = {}
    for name, lines in devices:
        intervals = []
        for ln, events in lines:
            if ln != OPS_LINE:
                continue
            for e, self_s, scope in self_seconds(events,
                                                 paths.get(name, {})):
                scopes[scope] = scopes.get(scope, 0.0) + self_s
                intervals.append((e[1], e[1] + e[2]))
                key = f"{scope} / {_op_name(e[0])}"
                by_op[key] = by_op.get(key, 0.0) + e[2] / 1e9
        _busy, merged = union_seconds(intervals)
        gaps = sorted(((s1 - e0, e0, s1) for (_s0, e0), (s1, _e1)
                       in zip(merged, merged[1:])), reverse=True)
        for i, (length, g0, g1) in enumerate(gaps):
            spans.split(g0, g1, gap_spans)
            middle = (g0 + g1) / 2
            what = f"{spans.at(middle)} / {host.at(middle)}" \
                if i < ATTRIBUTED_GAPS \
                else "gaps beyond the longest %d" % ATTRIBUTED_GAPS
            gaps_by_host[what] = gaps_by_host.get(what, 0.0) + length / 1e9
    n = len(devices)  # averaged over the device planes, like busy_s

    def top(d: dict) -> list:
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {
        "device_scopes": {k: v / n for k, v in sorted(scopes.items())},
        "gap_spans": {k: v / n for k, v in sorted(gap_spans.items())},
        "device_ops": top(by_op),
        "idle_gaps": top(gaps_by_host),
    }


def _read_planes(path: str) -> list:
    """The whole trace as plain lists, with the paths' entries."""
    from jax.profiler import ProfileData

    planes = [[pl.name, [[ln.name, [[ev.name, ev.start_ns, ev.duration_ns]
                                    for ev in ln.events]]
                         for ln in pl.lines]]
              for pl in ProfileData.from_file(path).planes]
    return planes + [[PATHS_PLANE + name, sorted(of.items())]
                     for name, of in op_paths(path).items()]


def reduce_xplane(path: str) -> dict:
    return reduce_planes(_read_planes(path))


def dump_planes(path: str, executions: int = 2) -> list:
    """A trace cut down to a fixture that `reduce_planes`, here and in
    `trace_reduce`, takes as it is: the first `executions` program runs of
    each device plane with every operation inside them, and of the host's
    plane the `fdb:` annotations of that stretch plus, for every idle gap in
    it, the events that cover the gap's midpoint (all that the reductions
    ask of a host line). To keep it small an operation is named as the
    breakdown names it (`_op_name`, which leaves such a name as it is) and
    its path is cut after the scope."""
    import numpy as np

    planes = _read_planes(path)
    t_end = None
    out, middles = [], []
    for name, lines in planes:
        if name.startswith(PATHS_PLANE):
            out.append([name, sorted({
                (_op_name(n), "/".join(p.split("/")[:3]))
                for n, p in lines})])
        if not DEVICE_PLANE.match(name):
            continue
        mods = sorted((e for ln, evs in lines if ln == MODULES_LINE
                       for e in evs), key=lambda e: e[1])[:executions]
        if not mods:
            continue
        t0, t1 = mods[0][1], mods[-1][1] + mods[-1][2]
        t_end = t1 if t_end is None else max(t_end, t1)
        ops = [[_op_name(e[0]), e[1], e[2]] for ln, evs in lines
               if ln == OPS_LINE for e in evs
               if t0 <= e[1] and e[1] + e[2] <= t1]
        out.append([name, [[OPS_LINE, ops], [MODULES_LINE, mods]]])
        _busy, merged = union_seconds(
            [(e[1], e[1] + e[2]) for e in ops if e[2] > 0])
        middles += [(e0 + s1) / 2 for (_s0, e0), (s1, _e1)
                    in zip(merged, merged[1:])]
    middles = np.sort(np.asarray(middles, np.float64))

    def covers_a_gap(e) -> bool:
        i = int(np.searchsorted(middles, e[1], "left"))
        return i < len(middles) and middles[i] <= e[1] + e[2]

    for name, lines in planes:
        if name != HOST_PLANE:
            continue
        kept = []
        for ln, evs in lines:
            keep = [e for e in evs if e[2] > 0 and (
                (e[0].startswith(SPAN_PREFIX) and t_end is not None
                 and e[1] <= t_end) or covers_a_gap(e))]
            if keep:
                kept.append([ln, keep])
        out.append([name, kept])
    return out
