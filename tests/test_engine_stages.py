"""The resolver's engine bracket, stage by stage (obs/span.py ENGINE_STAGES):
the per-batch identity on a wall-time loop, every stage sampled, nothing
recorded with no sink, the counters that say why the dictionary repacked or
the process compiled, the kernel's named scopes in the lowered program, and
the rule that obs/span.py never loads JAX by itself.
"""

import re
import subprocess
import sys

import numpy as np
import pytest

from foundationdb_tpu.core.types import KeyRange, TxnConflictInfo
from foundationdb_tpu.models import conflict_kernel as ck
from foundationdb_tpu.models import conflict_set as cset
from foundationdb_tpu.models.conflict_set import TPUConflictSet
from foundationdb_tpu.obs.span import (
    ENGINE_STAGES,
    SUB_STAGES,
    SpanSink,
    stage_timer,
)
from foundationdb_tpu.runtime.flow import Loop
from foundationdb_tpu.runtime.net import NetTransport, RealLoop, rpc
from foundationdb_tpu.runtime.resolver import Resolver

INTERIOR = ENGINE_STAGES[1:] + ("engine_unattributed",)
BATCH = 128
STEP = 1000  # versions a batch
ENGINE_COUNTERS = (
    "repacks_delta_overflow", "repacks_dict_full", "repacks_frag_due",
    "repack_s", "delta_new_keys", "dispatches", "delta_empty_dispatches",
    "compiles", "compile_s")


def point_txns(keys, read_version):
    return [TxnConflictInfo(read_version=read_version,
                            read_ranges=[KeyRange(k, k + b"\x00")],
                            write_ranges=[KeyRange(k, k + b"\x00")])
            for k in keys]


def small_engine(**kw):
    # A dictionary of 2,048 keys takes 256 new endpoint keys a batch: it
    # fills, and repacks (cause: dict_full), within the 16 batches.
    args = dict(capacity=1 << 13, dict_capacity=1 << 11, batch_size=BATCH)
    args.update(kw)
    return TPUConflictSet(**args)


def drive(loop, resolver, n_batches, seed=7, history_batches=3):
    """`n_batches` of BATCH never-seen keys through `resolver`, the MVCC
    floor `history_batches` behind: old keys die, so a repack finds room."""
    rng = np.random.default_rng(seed)

    async def main():
        prev = 0
        for b in range(n_batches):
            v = (b + 1) * STEP
            keys = [b"k%09d" % x for x in rng.integers(0, 1 << 30, BATCH)]
            await resolver.resolve(prev, v, point_txns(keys, max(0, v - STEP)),
                                   max(0, v - history_batches * STEP))
            prev = v
        return await resolver.get_metrics()

    return loop.run(main(), timeout=300)


def spans_by_version(sink):
    out: dict = {}
    for s in sink.spans:
        if s.get("version") is not None:
            stage = out.setdefault(s["version"], {})
            stage[s["name"]] = stage.get(s["name"], 0.0) + s["dur"]
    return out


@pytest.fixture(scope="module")
def traced():
    """16 batches through a Resolver on the resident engine, on a
    wall-time loop with a sink that records every tick."""
    loop = RealLoop()
    sink = SpanSink(loop, sample_every=1)
    cs = small_engine()
    cs.warm_up()
    resolver = Resolver(loop, cs)
    metrics = drive(loop, resolver, 16)
    return sink, metrics, cs


class TestEngineIdentity:
    def test_identity_holds_per_batch(self, traced):
        sink, _m, _cs = traced
        batches = spans_by_version(sink)
        assert len(batches) == 16
        for version, d in batches.items():
            bracket = d["host_pack"] + d["device_dispatch"]
            parts = sum(d.get(s, 0.0) for s in ENGINE_STAGES) \
                + d["engine_unattributed"]
            assert abs(bracket - parts) < 1e-6, (version, d)

    def test_unattributed_is_small(self, traced):
        """Under 5 % of the bracket in the median batch: on a loaded
        runner one preempted batch can hold more than every other
        batch's residue together, so the sum would test the scheduler."""
        sink, _m, _cs = traced
        shares = sorted(
            d["engine_unattributed"] / (d["host_pack"] + d["device_dispatch"])
            for d in spans_by_version(sink).values())
        assert 0.0 <= shares[0] and shares[len(shares) // 2] < 0.05, shares

    @pytest.mark.parametrize("stage", INTERIOR)
    def test_every_stage_has_a_sample(self, traced, stage):
        sink, _m, _cs = traced
        assert stage in SUB_STAGES
        assert stage in sink.stage_hists, sorted(sink.stage_hists)
        assert sink.stage_hists[stage].count > 0

    def test_repack_seconds_are_the_dict_repack_stage(self, traced):
        sink, metrics, _cs = traced
        eng = metrics["engine"]
        assert eng["full_repacks"] >= 1
        by_stage = sum(d.get("dict_repack", 0.0)
                       for d in spans_by_version(sink).values())
        assert eng["repack_s"] == pytest.approx(by_stage, rel=1e-3, abs=1e-6)

    @pytest.mark.parametrize("name", ENGINE_COUNTERS)
    def test_get_metrics_carries_the_counter(self, traced, name):
        _sink, metrics, _cs = traced
        assert name in metrics["engine"]
        assert metrics["engine"][name] >= 0
        if name in ("dispatches", "delta_new_keys", "compiles"):
            assert metrics["engine"][name] > 0

    def test_nothing_recorded_with_no_sink(self):
        loop = RealLoop()
        cs = small_engine()
        resolver = Resolver(loop, cs)
        drive(loop, resolver, 3)
        assert not hasattr(loop, "span_sink")
        assert resolver._inflight is None
        # The engine's own always-on record holds engine stages only.
        assert "headroom_sync" not in cs.last_stage_s
        assert cs.last_stage_s["engine_enqueue"] > 0.0

    def test_sim_loop_keeps_the_interior_out(self):
        """Synchronous work is 0 virtual seconds: a sim loop records the
        umbrella (and host_pack), never wall-clock interior stages, so
        span records stay seed-deterministic."""
        loop = Loop(seed=3)
        sink = SpanSink(loop, sample_every=1)
        resolver = Resolver(loop, small_engine())
        drive(loop, resolver, 3)
        assert "device_dispatch" in sink.stage_hists
        assert not set(INTERIOR) & set(sink.stage_hists)
        assert all(s.get("version") is None for s in sink.spans)


# -- one batch in flight while the next is packed ----------------------------


def drive_in_flight(loop, resolver, n_batches, depth, seed=7,
                    history_batches=3):
    """`drive`, with `depth` batches sent before their replies are
    awaited: every batch but a burst's first is dispatched while its
    predecessor is on the device."""
    rng = np.random.default_rng(seed)

    async def main():
        prev, tasks = 0, []
        for b in range(n_batches):
            v = (b + 1) * STEP
            keys = [b"k%09d" % x for x in rng.integers(0, 1 << 30, BATCH)]
            tasks.append(loop.spawn(resolver.resolve(
                prev, v, point_txns(keys, max(0, v - STEP)),
                max(0, v - history_batches * STEP))))
            prev = v
            if len(tasks) == depth:
                for t in tasks:
                    await t
                tasks = []
        return await resolver.get_metrics()

    return loop.run(main(), timeout=300)


@pytest.fixture(scope="module")
def traced_overlapped():
    """16 batches, four in flight at a time, every tick recorded; and,
    beside the sink, the role's two brackets a batch timed from outside
    (_dispatch_entry, _finish) on the same clock."""
    import time

    loop = RealLoop()
    sink = SpanSink(loop, sample_every=1)
    cs = small_engine()
    cs.warm_up()
    resolver = Resolver(loop, cs)
    outer: dict = {}

    dispatch, finish = resolver._dispatch_entry, resolver._finish
    # A dispatch finishes its predecessor inside itself: time the finish
    # alone, and take it out of the dispatch that held it.
    inner_finish: dict = {}

    def finish_timed(fl):
        t0 = time.perf_counter()
        try:
            return finish(fl)
        finally:
            dt = time.perf_counter() - t0
            outer[fl.entry.version] = outer.get(fl.entry.version, 0.0) + dt
            inner_finish["s"] = inner_finish.get("s", 0.0) + dt

    def dispatch_timed(entry, *rest):
        inner_finish["s"] = 0.0
        t0 = time.perf_counter()
        try:
            return dispatch(entry, *rest)
        finally:
            outer[entry.version] = outer.get(entry.version, 0.0) \
                + time.perf_counter() - t0 - inner_finish["s"]
            inner_finish["s"] = 0.0

    resolver._finish = finish_timed
    resolver._dispatch_entry = dispatch_timed
    t0 = time.perf_counter()
    metrics = drive_in_flight(loop, resolver, 16, depth=4)
    return sink, metrics, outer, time.perf_counter() - t0


class TestEngineIdentityWithOverlap:
    def test_the_overlap_engaged(self, traced_overlapped):
        _sink, metrics, _outer, _wall = traced_overlapped
        drains = metrics["pipeline_drains"]
        assert metrics["batches_overlapped"] + drains["repack"] == 12
        assert drains["no_successor"] == 4 and drains["headroom"] == 0

    def test_identity_holds_per_batch_under_its_own_version(
            self, traced_overlapped):
        sink, _m, _outer, _wall = traced_overlapped
        batches = spans_by_version(sink)
        assert sorted(batches) == [(b + 1) * STEP for b in range(16)]
        for version, d in batches.items():
            # Every stage of the batch is there under ITS version: the
            # dispatch half's and, collected one batch later, the
            # collect half's.
            for stage in ("host_pack", "dict_rank", "engine_enqueue",
                          "verdict_wait", "headroom_sync", "resolve_post"):
                assert stage in d, (version, stage)
            bracket = d["host_pack"] + d["device_dispatch"]
            parts = sum(d.get(s, 0.0) for s in ENGINE_STAGES) \
                + d["engine_unattributed"]
            assert abs(bracket - parts) < 1e-6, (version, d)

    def test_device_dispatch_is_the_batchs_two_brackets(
            self, traced_overlapped):
        """host_pack + device_dispatch is the dispatch half plus the
        collect half of THAT batch: inside the two calls timed from
        outside, close to them, and never another batch's pack (the
        brackets of all batches fit in the wall time together)."""
        sink, _m, outer, wall = traced_overlapped
        batches = spans_by_version(sink)
        slack = []
        for version, d in batches.items():
            bracket = d["host_pack"] + d["device_dispatch"]
            assert bracket <= outer[version] + 1e-6, (version, d)
            slack.append(outer[version] - bracket)
        assert sorted(slack)[len(slack) // 2] < 2e-3, slack
        assert sum(d["host_pack"] + d["device_dispatch"]
                   for d in batches.values()) <= wall

    def test_overlap_is_a_sub_stage_outside_the_identity(
            self, traced_overlapped):
        sink, _m, _outer, _wall = traced_overlapped
        assert "overlap" in SUB_STAGES and "overlap" not in ENGINE_STAGES
        batches = spans_by_version(sink)
        assert all("overlap" in d for d in batches.values())
        # A batch with a successor waited a whole host half for its
        # collect; a burst's last one a loop turn at most.
        waited = sorted(d["overlap"] for d in batches.values())
        assert waited[0] >= 0.0 and waited[-1] > waited[0]

    def test_sim_loop_records_no_overlap_span(self):
        loop = Loop(seed=3)
        sink = SpanSink(loop, sample_every=1)
        resolver = Resolver(loop, small_engine())
        m = drive_in_flight(loop, resolver, 4, depth=4)
        assert m["batches_overlapped"] + m["pipeline_drains"]["repack"] == 3
        assert "overlap" not in sink.stage_hists
        assert not set(INTERIOR) & set(sink.stage_hists)
        assert all(s.get("version") is None for s in sink.spans)


# -- why the dictionary repacked ---------------------------------------------


def resolve_new_keys(cs, version, n_keys, oldest=0, tag=b"a"):
    keys = [tag + b"%08d.%06d" % (version, i) for i in range(n_keys)]
    cs.resolve(point_txns(keys, max(0, version - 1)), version, oldest)


def repack_causes(cs):
    st = cs.dict_stats
    return {k: st[k] for k in ("repacks_delta_overflow", "repacks_dict_full",
                               "repacks_frag_due", "full_repacks")}


class TestRepackCauses:
    def test_delta_overflow(self):
        # 64 point transactions bring 128 new endpoint keys: over the 64
        # delta slots, far under the dictionary's capacity.
        cs = TPUConflictSet(capacity=1 << 12, dict_capacity=1 << 12,
                            dict_delta_slots=64, batch_size=64)
        resolve_new_keys(cs, 1000, 64)
        assert repack_causes(cs) == {
            "repacks_delta_overflow": 1, "repacks_dict_full": 0,
            "repacks_frag_due": 0, "full_repacks": 1}

    def test_dict_full(self):
        cs = TPUConflictSet(capacity=1 << 12, dict_capacity=256,
                            dict_delta_slots=128, batch_size=32)
        for b in range(1, 4):  # 64 new keys a batch; live history: 1 batch
            resolve_new_keys(cs, b * 1000, 32, oldest=(b - 1) * 1000)
        assert repack_causes(cs) == {"repacks_delta_overflow": 0,
                                     "repacks_dict_full": 0,
                                     "repacks_frag_due": 0, "full_repacks": 0}
        resolve_new_keys(cs, 4000, 32, oldest=3000)  # 193 + 64 > 256
        assert repack_causes(cs) == {
            "repacks_delta_overflow": 0, "repacks_dict_full": 1,
            "repacks_frag_due": 0, "full_repacks": 1}

    def test_frag_due(self):
        cs = TPUConflictSet(capacity=1 << 12, dict_capacity=256,
                            dict_delta_slots=128, batch_size=32)
        for b in range(1, 4):  # 193 keys: over half of 256, all in use
            resolve_new_keys(cs, b * 1000, 32, oldest=0)
        assert cs.dict_stats["full_repacks"] == 0
        # The floor passes every key's last use: mostly full AND mostly
        # stale, with room for this batch's 2 new keys.
        resolve_new_keys(cs, 9000, 1, oldest=8000)
        assert repack_causes(cs) == {
            "repacks_delta_overflow": 0, "repacks_dict_full": 0,
            "repacks_frag_due": 1, "full_repacks": 1}
        # A second batch after a frag_due repack repacks nothing: the
        # dictionary, as stale as before, has grown by 2 keys since.
        resolve_new_keys(cs, 10000, 1, oldest=8000)
        assert repack_causes(cs) == {
            "repacks_delta_overflow": 0, "repacks_dict_full": 0,
            "repacks_frag_due": 1, "full_repacks": 1}


def test_delta_empty_dispatches_counts_the_skipped_merges():
    """A dispatch whose device delta is empty (every key resident, or the
    new keys rode in with a full repack) skips dict_insert; one that ships
    a new key pays for it."""
    cs = TPUConflictSet(capacity=1 << 12, dict_capacity=1 << 12,
                        dict_delta_slots=64, batch_size=64)

    def counts():
        st = cs.dict_stats
        return (st["dispatches"], st["delta_empty_dispatches"],
                st["full_repacks"])

    keys = [b"k%04d" % i for i in range(8)]
    cs.resolve(point_txns(keys, 999), 1000, 0)  # 16 new endpoints: a merge
    assert counts() == (1, 0, 0)
    cs.resolve(point_txns(keys, 1999), 2000, 0)  # the same keys: none new
    assert counts() == (2, 1, 0)
    resolve_new_keys(cs, 3000, 64)  # 128 new keys over 64 slots: a repack
    assert counts() == (3, 2, 1)
    resolve_new_keys(cs, 4000, 1)
    assert counts() == (4, 2, 1)


def test_compiles_rise_on_a_first_call_and_not_on_the_next():
    import jax
    import jax.numpy as jnp

    cset.count_compiles()
    fn = jax.jit(lambda x: x * 3 + 1)
    x = jnp.arange(37, dtype=jnp.int32)  # a shape nothing else uses
    before = dict(cset._COMPILE_STATS)
    fn(x).block_until_ready()
    first = dict(cset._COMPILE_STATS)
    fn(x).block_until_ready()
    second = dict(cset._COMPILE_STATS)
    assert first["compiles"] == before["compiles"] + 1
    assert first["compile_s"] > before["compile_s"]
    assert second == first


# -- the kernel's phases by name ---------------------------------------------


def lowered_scopes(jitted, *args) -> set:
    text = jitted.lower(*args).as_text(debug_info=True)
    return {part for path in re.findall(r'loc\("(jit\([^"]*)"', text)
            for part in path.split("/")}


def test_the_programs_the_benchmark_reads_are_the_engines_own():
    """What benchmark/lib/trace_reduce.py counts by name
    (`jit__resolve_res_jit`, `jit__capacity_reading_jit`: `jit_` and the
    jitted function's name) and tests/benchmark/
    test_benchmark_rehearsal_mesh.py's fixtures spell for the mesh
    (`jit__sharded_resolve_res`), read off live engines, not fixtures."""
    from foundationdb_tpu.parallel.sharded_resolver import ShardedConflictSet

    kw = dict(capacity=256, batch_size=8, max_key_bytes=8)
    cs = TPUConflictSet(**kw)
    assert not (cs.wave_commit or cs.spec or cs.tiered)
    assert cs._resolve_fn is ck._resolve_res_jit
    assert cs._resolve_report_fn is ck._resolve_report_res_jit
    assert cs._repack_fn is ck._repack_res_jit
    assert cs._resolve_fn.__name__ == "_resolve_res_jit"
    assert ck._capacity_reading_jit.__name__ == "_capacity_reading_jit"
    mesh = ShardedConflictSet(n_shards=2, **kw)
    assert mesh._resolve_fn.__name__ == "_sharded_resolve_res"
    assert mesh._repack_fn is ck._repack_res_jit


@pytest.fixture(scope="module")
def resident_args():
    from foundationdb_tpu.core.keypack import INT32_MAX

    cs = TPUConflictSet(capacity=1 << 10, dict_capacity=1 << 10,
                        batch_size=16)
    bt = cs._empty_batch()
    flat, dims = cs._flat_endpoints(bt)
    empty = cs._ranks_to_batch(
        bt, np.full(len(flat), INT32_MAX, np.int32), dims)
    return cs, empty


@pytest.mark.parametrize("scope", [
    "dict_insert", "hist_merge", "history_probe", "endpoint_ranks",
    "accept", "paint_compact", "verdicts"])
def test_resolve_program_names_its_phases(resident_args, scope):
    cs, empty = resident_args
    zero = np.int32(0)
    assert scope in lowered_scopes(ck._resolve_res_jit, cs.state, empty,
                                   zero, zero)


@pytest.mark.parametrize("scope,entry", [("dict_evict", "_evict_res_jit"),
                                         ("dict_remap", "_repack_res_jit")])
def test_dictionary_upkeep_programs_name_their_phase(resident_args, scope,
                                                     entry):
    cs, _empty = resident_args
    mir = cs._mirror
    if entry == "_evict_res_jit":
        args = (cs.state, np.full(8, np.iinfo(np.int32).max, np.int32))
    else:
        args = (cs.state,
                np.zeros((mir.capacity + 1, mir.rows.shape[1]), np.int32),
                np.int32(mir.n), np.arange(mir.capacity + 1, dtype=np.int32))
    assert scope in lowered_scopes(getattr(ck, entry), *args)


def test_dict_insert_searches_and_gathers_by_the_delta_not_the_dictionary(
        resident_args):
    """The structural rule of the delta merge, read off the lowered
    program: nothing indexes the dictionary row by row. 1,025 rows is the
    dictionary's alone in this program (history 1,024, delta 512), so any
    gather that takes 1,025 indices, and any loop that carries more than
    the searched column at that length (a binary search's bounds), is a
    per-dictionary-row search or row gather, whatever scope it sits in."""
    cs, empty = resident_args
    zero = np.int32(0)
    d1 = cs.state.dict_keys.shape[0]
    assert d1 not in (cs.capacity, empty.delta_keys.shape[0])
    text = ck._resolve_res_jit.lower(cs.state, empty, zero, zero).as_text()
    assert f"tensor<{d1}x" in text  # the dictionary is in the program
    gathers = re.findall(
        r'"stablehlo\.gather"[^\n]* : \(tensor<[^>]*>, tensor<(\d+)[x>]', text)
    assert gathers and str(d1) not in gathers
    loops = re.findall(r"stablehlo\.while\([^\n]*\) : ([^\n]*)", text)
    assert loops
    assert max(carry.count(f"tensor<{d1}xi32>") for carry in loops) <= 1


# -- the helper, and the processes that must never load JAX ------------------


def test_stage_timer_accumulates_and_carves_out_nested_stages():
    rec: dict = {}
    with stage_timer(rec, "outer", 7):
        with stage_timer(rec, "inner", 7, inside="outer") as t:
            pass
    with stage_timer(rec, "outer", 7) as again:
        pass
    assert rec["inner"] == t.seconds
    assert rec["outer"] >= again.seconds
    assert rec["outer"] >= 0.0
    with stage_timer(None, "timed_only") as t2:  # no record: a plain timer
        pass
    assert t2.seconds >= 0.0


def test_client_and_span_module_do_not_load_jax():
    code = (
        "import sys\n"
        "import foundationdb_tpu.client\n"
        "from foundationdb_tpu.obs.span import stage_timer\n"
        "rec = {}\n"
        "with stage_timer(rec, 'dict_rank', 5):\n"
        "    pass\n"
        "assert rec['dict_rank'] >= 0.0\n"
        "assert 'jax' not in sys.modules, 'jax was loaded'\n"
        "print('clean')\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "clean"


class Echo:
    @rpc
    async def echo(self, x):
        return x


@pytest.mark.parametrize("with_sink", [True, False])
def test_rpc_decode_is_ticked_per_request_frame_only_with_a_sink(with_sink):
    loop = RealLoop()
    sink = SpanSink(loop, sample_every=1) if with_sink else None
    server, client = NetTransport(loop), NetTransport(loop)
    server.serve("echo", Echo())
    ep = client.endpoint(server.addr, "echo")

    async def main():
        for i in range(5):
            assert await ep.echo([i, b"x" * 64]) == [i, b"x" * 64]

    try:
        loop.run(main(), timeout=30)
    finally:
        server.close()
        client.close()
    if with_sink:
        # Five request frames; the five replies are not requests.
        assert sink.stage_hists["rpc_decode"].count == 5
    else:
        assert not hasattr(loop, "span_sink")
