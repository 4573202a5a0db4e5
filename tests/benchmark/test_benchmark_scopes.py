"""The reductions and readers PR 24 adds beside the ones that were there:
device self time by kernel scope and idle time by `fdb:` span
(benchmark/lib/trace_scopes.py) on hand-made planes and on the traces
recorded on the chip, the three new readers, the new per-layer entries of
BENCHMARK.json — and the proof that nothing the benchmark already read has
moved: the existing trace-derived metrics read the recorded PR 23 fixtures
to the same values as on the parent commit."""

import json
import os

import numpy as np
import pytest

from benchmark.lib import contract, trace_reduce, trace_scopes
from benchmark.lib.hist import LatencyHistogram
from benchmark.readers import (
    counter_ratio,
    device_per_batch,
    device_scope_per_batch,
    span_or_zero,
)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURES = os.path.join(REPO, "benchmark", "fixtures")
BM = contract.load_benchmark(REPO)


def fixture(name: str):
    with open(os.path.join(FIXTURES, name)) as f:
        return json.load(f)


def path(scope: str) -> str:
    return f"jit(_resolve_res_jit)/jit(main)/{scope}/while/body/add"


# One execution, 0..1000 ns: a conditional (100..700) holding a while
# (200..600) holding two dict_insert fusions (250..350, 400..500) — the two
# control-flow operations without a path of their own, as the chip gives
# them; then an accept fusion (700..800) and an operation of no scope
# (900..1000). Idle: 0..100 never counts (before the first op), 800..900
# does.
OPS = [
    ["%cond.1 = (s32[8]{0}) conditional(s32[] %p)", 100, 600],
    ["%while.2 = (s32[8]{0}) while(s32[8] %q)", 200, 400],
    ["%fusion.3 = s32[8]{0} fusion(s32[8] %a), kind=kLoop", 250, 100],
    ["%fusion.4 = s32[8]{0} fusion(s32[8] %a), kind=kLoop", 400, 100],
    ["%fusion.5 = s32[8]{0} fusion(s32[8] %b), kind=kLoop", 700, 100],
    ["%copy.6 = s32[8]{0} copy(s32[8] %c)", 900, 100],
]
PATHS = {OPS[2][0]: path("dict_insert"), OPS[3][0]: path("dict_insert"),
         OPS[4][0]: path("accept"), OPS[5][0]: "jit(f)/jit(main)/mul"}
HOST = [["python", [
    ["fdb:device_dispatch", 0, 2000],
    ["fdb:verdict_wait", 820, 60],       # covers the gap's midpoint, 850
    ["conflict_set.py:99 _decode", 840, 20],
    ["fdb:dict_rank", 10, 50],
]]]
PLANES = [["/device:TPU:0", [["XLA Ops", OPS], ["XLA Modules", [
    ["jit__resolve_res_jit(123)", 100, 900]]]]], ["/host:CPU", HOST],
    [trace_scopes.PATHS_PLANE + "/device:TPU:0", sorted(PATHS.items())]]


class TestHandMadePlanes:
    def test_self_time_takes_nested_events_out(self):
        rows = trace_scopes.self_seconds(OPS, PATHS)
        assert {e[0].split(" ")[0]: s * 1e9 for e, s, _sc in rows} == \
            pytest.approx({
                "%cond.1": 200.0, "%while.2": 200.0, "%fusion.3": 100.0,
                "%fusion.4": 100.0, "%fusion.5": 100.0, "%copy.6": 100.0})

    def test_control_flow_takes_the_scope_of_what_it_holds(self):
        scopes = {e[0].split(" ")[0]: sc
                  for e, _s, sc in trace_scopes.self_seconds(OPS, PATHS)}
        assert scopes == {
            "%cond.1": "dict_insert", "%while.2": "dict_insert",
            "%fusion.3": "dict_insert", "%fusion.4": "dict_insert",
            "%fusion.5": "accept", "%copy.6": trace_scopes.UNSCOPED}

    def test_scopes_partition_the_busy_time(self):
        out = trace_scopes.reduce_planes(PLANES)
        busy = trace_reduce.reduce_planes(PLANES, window_s=1e-6)["busy_s"]
        assert out["device_scopes"] == pytest.approx({
            "dict_insert": 600e-9, "accept": 100e-9,
            trace_scopes.UNSCOPED: 100e-9})
        assert sum(out["device_scopes"].values()) == pytest.approx(busy)

    def test_an_idle_gap_is_cut_up_among_the_innermost_spans(self):
        """The gap 800..900: `verdict_wait` covers 820..880 of it, the
        umbrella the rest; its midpoint names the breakdown's row."""
        out = trace_scopes.reduce_planes(PLANES)
        assert out["gap_spans"] == pytest.approx(
            {"verdict_wait": 60e-9, "device_dispatch": 40e-9})
        assert sum(out["gap_spans"].values()) == pytest.approx(100e-9)
        assert out["idle_gaps"] == [[
            "verdict_wait / conflict_set.py:99 _decode",
            pytest.approx(100e-9)]]

    def test_a_gap_no_span_covers_says_so(self):
        planes = [PLANES[0], PLANES[2], ["/host:CPU", [["python", [
            ["wire.py:123 unpack_obj", 800, 100]]]]]]
        out = trace_scopes.reduce_planes(planes)
        assert out["gap_spans"] == pytest.approx(
            {trace_scopes.NO_SPAN: 100e-9})
        assert out["idle_gaps"][0][0] == "(no span) / wire.py:123 unpack_obj"

    def test_operations_carry_their_scope_in_front(self):
        out = trace_scopes.reduce_planes(PLANES)
        names = [row[0] for row in out["device_ops"]]
        assert names[0] == "dict_insert / %cond.1 = (s32[8] conditional"
        assert "accept / %fusion.5 = s32[8] fusion" in names
        assert "(unscoped) / %copy.6 = s32[8] copy" in names

    def test_a_path_names_its_outermost_scope(self):
        assert trace_scopes.scope_of(path("paint_compact")) == "paint_compact"
        assert trace_scopes.scope_of(
            "jit(f)/hist_merge/cond/branch_1_fun/history_probe/add:"
        ) == "hist_merge"
        assert trace_scopes.scope_of("jit(f)/mul") == trace_scopes.UNSCOPED
        assert trace_scopes.scope_of("") == trace_scopes.UNSCOPED

    def test_the_protobuf_reader_finds_the_paths_in_the_metadata(
            self, tmp_path):
        """`op_paths` on a hand-encoded XSpace: one device plane whose
        event metadata carries `tf_op` once as a string and once as a
        reference to a stat-metadata name; a host plane is passed over."""
        def varint(n):
            out = bytearray()
            while True:
                out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
                n >>= 7
                if not n:
                    return bytes(out)

        def field(num, payload):
            if isinstance(payload, int):
                return varint(num << 3) + varint(payload)
            if isinstance(payload, str):
                payload = payload.encode()
            return varint(num << 3 | 2) + varint(len(payload)) + payload

        def entry(key, value):
            return field(1, key) + field(2, value)

        stat_md = (field(5, entry(26, field(1, 26) + field(2, "tf_op")))
                   + field(5, entry(90, field(1, 90) + field(2, path("accept")))))
        ev1 = field(1, 7) + field(2, "%fusion.7 = s32[] fusion()") + field(
            5, field(1, 26) + field(5, path("dict_insert")))
        ev2 = field(1, 8) + field(2, "%fusion.8 = s32[] fusion()") + field(
            5, field(1, 26) + field(7, 90))
        ev3 = field(1, 9) + field(2, "%copy.9 = s32[] copy()")
        line = field(3, field(2, "XLA Ops") + field(4, field(1, 7)))
        device = (field(2, "/device:TPU:0") + line + stat_md
                  + field(4, entry(7, ev1)) + field(4, entry(8, ev2))
                  + field(4, entry(9, ev3)))
        host = field(2, "/host:CPU") + field(4, entry(7, ev1))
        f = tmp_path / "t.xplane.pb"
        f.write_bytes(field(1, device) + field(1, host))
        assert trace_scopes.op_paths(str(f)) == {"/device:TPU:0": {
            "%fusion.7 = s32[] fusion()": path("dict_insert"),
            "%fusion.8 = s32[] fusion()": path("accept")}}

    def test_a_trace_without_a_device_plane_gives_nothing(self):
        assert trace_scopes.reduce_planes([["/host:CPU", HOST]]) == {}


# -- the readers --------------------------------------------------------------


def traced_result(**trace):
    base = {"stand_in": False, "modules": {"jit__resolve_res_jit": [4, 1.0]}}
    return {"sources": {"trace": dict(base, **trace)}}


class TestDeviceScopeReader:
    def test_ms_per_execution_of_the_scopes_named(self):
        result = traced_result(device_scopes={
            "paint_compact": 0.010, "hist_merge": 0.030, "accept": 0.002})
        assert device_scope_per_batch.read(
            {"scopes": ["paint_compact", "hist_merge"]},
            result) == pytest.approx(10.0)
        assert device_scope_per_batch.read(
            {"scopes": ["dict_insert"]}, result) == 0.0

    def test_nothing_where_there_is_nothing_to_read(self):
        params = {"scopes": ["accept"]}
        assert device_scope_per_batch.read(params, {}) is None
        assert device_scope_per_batch.read(params, traced_result()) is None
        assert device_scope_per_batch.read(params, traced_result(
            stand_in=True, device_scopes={"accept": 1.0})) is None
        assert device_scope_per_batch.read(params, traced_result(
            modules={}, device_scopes={"accept": 1.0})) is None


class TestCounterRatioReader:
    def test_a_ratio_of_two_window_differences(self):
        result = {"sources": {"counters": {"delta_new_keys": 900,
                                           "dispatches": 300}}}
        assert counter_ratio.read(
            {"num": "delta_new_keys", "den": "dispatches"}, result) == 3.0

    def test_zero_over_zero_is_zero_and_a_missing_counter_nothing(self):
        params = {"num": "delta_new_keys", "den": "dispatches"}
        assert counter_ratio.read(params, {"sources": {"counters": {
            "delta_new_keys": 0, "dispatches": 0}}}) == 0.0
        assert counter_ratio.read(params, {"sources": {"counters": {
            "dispatches": 3}}}) is None
        assert counter_ratio.read(params, {}) is None


def hist_of(*ms):
    h = LatencyHistogram()
    for x in ms:
        h.counts[int(np.searchsorted(h._EDGES, x))] += 1
        h.sum_ms += x
        h.max_ms = max(h.max_ms, x)
    return h


NEW_SPAN_METRICS = [m for m in BM["per_layer"] if m["name"].split(".")[0] in (
    "dict_rank_ms", "engine_enqueue_ms", "verdict_wait_ms", "rpc_decode_ms",
    "resolve_post_ms")]


def test_the_new_span_metrics_are_the_nine_the_program_can_feed():
    assert sorted(m["name"] for m in NEW_SPAN_METRICS) == sorted(
        [n + s for n in ("dict_rank_ms", "engine_enqueue_ms",
                         "verdict_wait_ms", "resolve_post_ms")
         for s in ("", ".share")] + ["rpc_decode_ms.share"])
    contract.validate_benchmark(BM, REPO)


@pytest.mark.parametrize("metric", [m["name"] for m in NEW_SPAN_METRICS])
def test_a_new_span_metric_reads_its_stage_or_zero(metric):
    """Its stage's mean where the program recorded it; 0.0, and no error,
    over a program that lacks the stage (the parent commit); nothing in an
    untraced run."""
    from foundationdb_tpu.obs.span import SUB_STAGES

    with open(os.path.join(REPO, "benchmark", "metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "span_or_zero"
    stage = spec["params"]["stage"]
    assert stage in SUB_STAGES
    entry = contract.find(BM["per_layer"], metric, "metric")
    cell = "resolver_share_f" if metric.endswith(".share") else "ycsb_f_closed"
    assert entry["workloads"] == [cell] and entry["better"] == "lower"
    got = span_or_zero.read(spec["params"], {"sources": {"spans": {
        stage: hist_of(2.0, 4.0), "device_dispatch": hist_of(80.0)}}})
    assert got == pytest.approx(3.0)
    assert span_or_zero.read(spec["params"], {"sources": {"spans": {
        "device_dispatch": hist_of(80.0)}}}) == 0.0
    assert span_or_zero.read(spec["params"], {"sources": {}}) is None


# -- what was there has not moved --------------------------------------------

# (busy_s, executions of jit__resolve*, device_ms_per_batch, the first
# operation and its seconds) as the parent commit reads its own fixtures.
PARENT_READS = {
    "trace_ycsb_f_closed.json": (
        0.010604613, 45, 0.23565806666666667,
        ["%while.47 = (s32[] while", 0.001527632]),
    "trace_resolver_share_f.json": (
        0.114845506, 14, 8.20325042857143,
        ["%cond.31 = (s32[131072,1] conditional", 0.074529402]),
}


@pytest.mark.parametrize("name", sorted(PARENT_READS))
def test_the_existing_trace_metrics_read_the_pr23_fixtures_as_before(name):
    busy, n, per_batch, first_op = PARENT_READS[name]
    out = trace_reduce.reduce_planes(fixture(name), 3.0)
    assert out["busy_s"] == pytest.approx(busy, rel=1e-12)
    assert out["window_s"] == 3.0
    assert device_per_batch.executions(out, "resolve") == n
    assert device_per_batch.read(
        {"module": "resolve"},
        {"sources": {"trace": out}}) == pytest.approx(per_batch, rel=1e-12)
    assert out["device_ops"][0] == [first_op[0],
                                    pytest.approx(first_op[1], rel=1e-12)]


@pytest.mark.parametrize("name", sorted(PARENT_READS))
def test_an_unscoped_fixture_is_all_unscoped_and_still_partitions(name):
    """The PR 23 fixtures were recorded before the kernel named its
    phases and carry no paths: everything is `(unscoped)`, every gap
    `(no span)`, and the self times still sum to the busy time."""
    planes = fixture(name)
    out = trace_scopes.reduce_planes(planes)
    busy = trace_reduce.reduce_planes(planes, 3.0)["busy_s"]
    assert set(out["device_scopes"]) == {trace_scopes.UNSCOPED}
    assert out["device_scopes"][trace_scopes.UNSCOPED] == pytest.approx(
        busy, rel=0.02)
    assert set(out["gap_spans"]) == {trace_scopes.NO_SPAN}


# -- the traces recorded on the chip with the names in ------------------------

SCOPED = {"ycsb_f_closed": "trace_scopes_ycsb_f_closed.json",
          "resolver_share_f": "trace_scopes_resolver_share_f.json"}
# The share of a cut's idle seconds under an `fdb:` span. The closed cut
# holds ONE gap between its two executions, 10.5 ms, of which the role's
# loop spends 2.8 ms outside every span (reply, select, frame read): 73 %
# named, where that run's whole 3 s trace read 88.6 % (PERF.md section 5).
NAMED_FLOOR = {"ycsb_f_closed": 0.7, "resolver_share_f": 0.9}


@pytest.mark.parametrize("cell", sorted(SCOPED))
class TestTheScopedTraceRecordedOnTheChip:
    """Two executions of `jit__resolve_res_jit` a cell, compiled afresh so
    that the programs carry PR 24's scopes, cut down by
    `trace_scopes.dump_planes` (my chip runs, PR 24)."""

    def test_both_reductions_take_it_as_it_is(self, cell):
        planes = fixture(SCOPED[cell])
        plain = trace_reduce.reduce_planes(planes, 0.1)
        assert plain["stand_in"] is False
        assert device_per_batch.executions(plain, "resolve") == 2
        assert trace_scopes.reduce_planes(planes)["device_scopes"]

    def test_the_scopes_partition_the_busy_time(self, cell):
        planes = fixture(SCOPED[cell])
        scopes = trace_scopes.reduce_planes(planes)["device_scopes"]
        busy = trace_reduce.reduce_planes(planes, 0.1)["busy_s"]
        assert sum(scopes.values()) == pytest.approx(busy, rel=0.02)
        assert scopes[trace_scopes.UNSCOPED] < 0.05 * busy
        assert set(scopes) - {trace_scopes.UNSCOPED} <= set(
            trace_scopes.SCOPES)

    def test_the_dictionary_merge_is_most_of_it(self, cell):
        """The finding the names were added for: the conflict check proper
        (history probe, accept, paint) is a few ms of a batch."""
        planes = fixture(SCOPED[cell])
        scopes = trace_scopes.reduce_planes(planes)["device_scopes"]
        busy = sum(scopes.values())
        assert scopes["dict_insert"] > 0.7 * busy
        check = sum(scopes.get(s, 0.0) for s in (
            "history_probe", "accept", "paint_compact", "verdicts"))
        # ~5.9 ms an execution in either cell; the closed cut's two
        # executions merge a small delta (35 ms each, 65 over the trace).
        assert 0.0 < check < 0.2 * busy
        ops = trace_scopes.reduce_planes(planes)["device_ops"]
        assert ops[0][0].startswith("dict_insert / %cond")

    def test_the_idle_gaps_fall_under_named_spans(self, cell):
        planes = fixture(SCOPED[cell])
        out = trace_scopes.reduce_planes(planes)
        plain = trace_reduce.reduce_planes(planes, 0.1)
        idle = sum(out["gap_spans"].values())
        # the gaps are what the union of the operations leaves out
        ops = [e for n, ls in planes if n == "/device:TPU:0"
               for ln, evs in ls if ln == "XLA Ops" for e in evs]
        spread = (max(e[1] + e[2] for e in ops)
                  - min(e[1] for e in ops)) / 1e9
        assert idle == pytest.approx(spread - plain["busy_s"], rel=1e-6)
        named = idle - out["gap_spans"].get(trace_scopes.NO_SPAN, 0.0)
        assert named > NAMED_FLOOR[cell] * idle
        assert all(row[0].split(" / ")[0] in set(out["gap_spans"])
                   | {trace_scopes.NO_SPAN}
                   for row in out["idle_gaps"]
                   if not row[0].startswith("gaps beyond"))
