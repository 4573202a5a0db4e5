"""Global wave commit across sharded resolvers (ISSUE 13).

Coverage: the core/wavemesh exchange algebra (pack/OR/level parity with
the oracle rule), the two-phase engine protocol on the oracle AND the
device engine (clipped shards ≡ single engine ≡ oracle, verdicts AND
byte-identical schedules), the mesh ShardedConflictSet's in-jit exchange
(3-way parity + exchange stats + auto-reshard-mid-stream schedule
parity), the runtime protocol end-to-end through SimCluster (per-shard
counters byte-identical, wave_batches/wave_exchanges metrics, obs
wave_exchange/wave_level sub-stages), the capability refusals that
replaced the blanket n_resolvers>1 ban, and the pinned regression that
the OLD clipped-graph AND path can never emit a wave schedule."""

import numpy as np
import pytest

from foundationdb_tpu.core.types import (
    KeyRange,
    TxnConflictInfo,
    Verdict,
    validate_wave_commit,
)
from foundationdb_tpu.core.wavemesh import (
    WaveEdges,
    WaveGraph,
    clip_txns,
    combine_edges,
    level_wave_graph,
    pack_pred_rows,
    schedule_graph,
    unpack_pred_rows,
)
from foundationdb_tpu.models.conflict_set import TPUConflictSet
from foundationdb_tpu.parallel.sharded_resolver import ShardedConflictSet
from foundationdb_tpu.sim.oracle import OracleConflictSet, ReplayCheckedOracle
from tests.test_conflict_oracle import rand_txn


BOUNDS_3 = [(b"", b"\x0e"), (b"\x0e", b"\x1c"), (b"\x1c", b"\xff\xff")]


def eng_kw(**kw):
    kw.setdefault("capacity", 512)
    kw.setdefault("batch_size", 16)
    kw.setdefault("max_read_ranges", 4)
    kw.setdefault("max_write_ranges", 4)
    kw.setdefault("max_key_bytes", 8)
    return kw


# ---------------------------------------------------------------------------
# core/wavemesh algebra
# ---------------------------------------------------------------------------


class TestWavemeshAlgebra:
    def test_pack_unpack_roundtrip(self):
        rng = np.random.default_rng(1)
        n = 37
        pred = {
            j: {int(i) for i in rng.integers(0, n, size=rng.integers(0, 5))
                if int(i) != j}
            for j in range(n)
        }
        pred = {j: s for j, s in pred.items() if s}
        m = pack_pred_rows(pred, n)
        assert m.shape == (64, 2)
        assert unpack_pred_rows(m, n) == pred

    def test_or_of_clipped_matrices_is_global(self):
        """Shards partition the edge set: OR of per-shard clipped pred
        matrices equals the unclipped matrix."""
        rng = np.random.default_rng(2)
        oracle = OracleConflictSet(wave_commit=True)
        for _ in range(5):
            txns = [rand_txn(rng, read_version=0) for _ in range(12)]
            full = oracle._gate_and_pred(txns)[3]
            acc = np.zeros_like(pack_pred_rows(full, len(txns)))
            for lo, hi in BOUNDS_3:
                sh = OracleConflictSet(wave_commit=True)
                part = sh._gate_and_pred(clip_txns(txns, lo, hi))[3]
                acc |= pack_pred_rows(part, len(txns))
            assert unpack_pred_rows(acc, len(txns)) == {
                j: s for j, s in full.items() if s
            }

    def test_level_wave_graph_matches_oracle_resolve(self):
        """The shared leveler IS the oracle's wave rule (refactor pin)."""
        rng = np.random.default_rng(3)
        oracle = OracleConflictSet(wave_commit=True)
        cv = 10
        for _ in range(6):
            cv += 5
            txns = [rand_txn(rng, read_version=cv - 3) for _ in range(14)]
            verdicts = oracle.resolve(txns, cv)
            lv = oracle.last_wave
            for i, v in enumerate(verdicts):
                assert (v == Verdict.COMMITTED) == (lv[i] >= 0)

    def test_combine_edges_rejects_mismatched_chunking(self):
        a = WaveEdges(count=3, too_old=np.zeros(3, bool),
                      hist_conflict=np.zeros(3, bool),
                      chunks=[(3, np.zeros((32, 1), np.uint32))])
        b = WaveEdges(count=3, too_old=np.zeros(3, bool),
                      hist_conflict=np.zeros(3, bool), chunks=[])
        with pytest.raises(ValueError, match="chunking"):
            combine_edges([a, b])

    def test_wire_roundtrip(self):
        e = WaveEdges(
            count=2, too_old=np.array([True, False]),
            hist_conflict=np.array([False, True]),
            chunks=[(2, np.arange(32, dtype=np.uint32).reshape(32, 1))],
        )
        r = WaveEdges.from_wire(e.to_wire())
        assert r.count == 2 and list(r.too_old) == [True, False]
        assert np.array_equal(r.chunks[0][1], e.chunks[0][1])
        g = WaveGraph(count=2, too_old=r.too_old, cand=~r.too_old,
                      chunks=r.chunks)
        r2 = WaveGraph.from_wire(g.to_wire())
        assert list(r2.cand) == [False, True]

    def test_schedule_graph_chunk_offsets(self):
        """Chunk i+1's wave 0 serializes after all of chunk i's waves."""
        p = pack_pred_rows({1: {0}}, 2)  # 0 before 1 in each chunk
        g = WaveGraph(count=4, too_old=np.zeros(4, bool),
                      cand=np.ones(4, bool), chunks=[(2, p), (2, p)])
        levels, reordered = schedule_graph(g)
        assert levels == [0, 1, 2, 3]
        assert reordered == 2  # raw level > 0 per chunk, offsets excluded


# ---------------------------------------------------------------------------
# two-phase protocol at engine level: shards ≡ single ≡ oracle
# ---------------------------------------------------------------------------


def _run_two_phase(shards, bounds, txns, cv, oldest):
    edges = [
        WaveEdges.from_wire(
            sh.resolve_edges(clip_txns(txns, lo, hi), cv, oldest).to_wire()
        )
        for (lo, hi), sh in zip(bounds, shards)
    ]
    graph = WaveGraph.from_wire(combine_edges(edges).to_wire())
    return [sh.resolve_apply(graph) for sh in shards]


class TestTwoPhaseOracle:
    def test_sharded_matches_single_schedules_and_reports(self):
        rng = np.random.default_rng(7)
        single = OracleConflictSet(wave_commit=True)
        shards = [ReplayCheckedOracle(wave_commit=True) for _ in BOUNDS_3]
        cv = 100
        for step in range(12):
            cv += int(rng.integers(2, 20))
            txns = [
                rand_txn(rng, read_version=int(
                    rng.integers(max(0, cv - 60), cv)))
                for _ in range(int(rng.integers(2, 20)))
            ]
            for t in txns[::3]:
                object.__setattr__(t, "report_conflicting_keys", True)
            oldest = cv - 50
            want = single.resolve(txns, cv, oldest)
            got = _run_two_phase(shards, BOUNDS_3, txns, cv, oldest)
            for g in got:
                assert g == want, step
            for sh in shards:
                assert sh.last_wave == single.last_wave, step
                assert sh.last_reordered == single.last_reordered
            # Conflicting-keys report: the union over shards covers every
            # single-engine range (each shard reports its clipped slice).
            union: dict = {}
            for sh in shards:
                for i, ranges in sh.last_conflicting.items():
                    union.setdefault(i, []).extend(ranges)
            for i, ranges in single.last_conflicting.items():
                assert i in union, step
                for r in ranges:
                    assert any(
                        k.begin <= r.begin and r.end <= k.end
                        or (k.begin <= r.begin < k.end)
                        for k in union[i]
                    ), (step, i, r, union[i])

    def test_phase_ordering_errors(self):
        o = OracleConflictSet(wave_commit=True)
        g = WaveGraph(count=0, too_old=np.zeros(0, bool),
                      cand=np.zeros(0, bool), chunks=[])
        with pytest.raises(ValueError, match="without a pending"):
            o.resolve_apply(g)
        o.resolve_edges([], 10)
        with pytest.raises(ValueError, match="apply outstanding"):
            o.resolve_edges([], 11)
        o.resolve_abandon()
        o.resolve_edges([], 12)  # abandoned: a new window may open

    def test_requires_wave_commit(self):
        o = OracleConflictSet(wave_commit=False)
        assert not o.wave_global_capable
        with pytest.raises(ValueError, match="wave-commit"):
            o.resolve_edges([], 10)


class TestTwoPhaseDevice:
    @pytest.mark.parametrize("seed", [11, 12])
    def test_sharded_matches_single_and_oracle(self, seed):
        rng = np.random.default_rng(seed)
        kw = eng_kw(wave_commit=True)
        single = TPUConflictSet(**kw)
        shards = [TPUConflictSet(**kw) for _ in range(2)]
        oracle = OracleConflictSet(wave_commit=True)
        bounds = [(b"", b"\x14"), (b"\x14", b"\xff\xff")]
        cv = 1000
        for step in range(8):
            cv += int(rng.integers(2, 30))
            txns = [
                rand_txn(rng, read_version=int(
                    rng.integers(max(0, cv - 150), cv)))
                for _ in range(int(rng.integers(2, 17)))
            ]
            oldest = cv - 120
            want = single.resolve(txns, cv, oldest)
            oracle.oldest_version = max(oracle.oldest_version, oldest)
            assert want == oracle.resolve(txns, cv), step
            assert single.last_wave == oracle.last_wave, step
            got = _run_two_phase(shards, bounds, txns, cv, oldest)
            for g in got:
                assert g == want, step
            for sh in shards:
                assert sh.last_wave == single.last_wave, step
                assert sh.last_reordered == single.last_reordered

    def test_window_capped_at_one_chunk(self):
        cs = TPUConflictSet(**eng_kw(wave_commit=True))
        txns = [rand_txn(np.random.default_rng(1), read_version=5)
                for _ in range(17)]
        with pytest.raises(ValueError, match="one schedule domain"):
            cs.resolve_edges(txns, 10)

    def test_capability_surface(self):
        assert TPUConflictSet(**eng_kw(wave_commit=True)).wave_global_capable
        assert not TPUConflictSet(**eng_kw(wave_commit=False)) \
            .wave_global_capable
        # The mesh engine shards internally (exchange in-jit) and is a
        # single resolver from the role's perspective.
        mesh = ShardedConflictSet(n_shards=2, auto_reshard=False,
                                  **eng_kw(wave_commit=True))
        assert not mesh.wave_global_capable


# ---------------------------------------------------------------------------
# mesh engine: in-jit exchange (3-way parity, stats, auto-reshard)
# ---------------------------------------------------------------------------


class TestMeshWave:
    @pytest.mark.parametrize("n_shards", [2, 4])
    def test_three_way_parity_with_levels(self, n_shards):
        rng = np.random.default_rng(n_shards)
        kw = eng_kw(wave_commit=True)
        mesh = ShardedConflictSet(n_shards=n_shards, auto_reshard=False,
                                  **kw)
        single = TPUConflictSet(**kw)
        oracle = OracleConflictSet(wave_commit=True)
        cv = 1000
        for step in range(8):
            cv += int(rng.integers(2, 30))
            txns = [
                rand_txn(rng, read_version=int(
                    rng.integers(max(0, cv - 150), cv)), alphabet=256,
                    max_len=5)
                for _ in range(int(rng.integers(2, 17)))
            ]
            oldest = cv - 120
            got = mesh.resolve(txns, cv, oldest)
            want = single.resolve(txns, cv, oldest)
            oracle.oldest_version = max(oracle.oldest_version, oldest)
            assert got == want == oracle.resolve(txns, cv), step
            assert mesh.last_wave == single.last_wave == oracle.last_wave
        stats = mesh.exchange_stats()
        assert stats["wave_batches"] == 8
        assert 0 < stats["tiles_occupied"] <= stats["tiles_total"]
        assert stats["exchange_bytes_per_batch_scoped"] <= \
            stats["exchange_bytes_per_batch_dense"]

    def test_auto_reshard_mid_stream_schedule_parity(self):
        """The acceptance satellite: a reshard between dispatch windows
        must not perturb the global schedule (bounds move, graph does
        not)."""
        rng = np.random.default_rng(9)
        kw = eng_kw(wave_commit=True)
        mesh = ShardedConflictSet(n_shards=2, auto_reshard=True,
                                  reshard_interval=2, reshard_skew=1.0,
                                  **kw)
        single = TPUConflictSet(**kw)
        oracle = OracleConflictSet(wave_commit=True)
        cv = 1000
        for step in range(10):
            cv += int(rng.integers(2, 30))
            txns = [
                rand_txn(rng, read_version=int(
                    rng.integers(max(0, cv - 150), cv)), alphabet=256,
                    max_len=5)
                for _ in range(int(rng.integers(2, 17)))
            ]
            oldest = cv - 120
            got = mesh.resolve(txns, cv, oldest)
            want = single.resolve(txns, cv, oldest)
            oracle.oldest_version = max(oracle.oldest_version, oldest)
            assert got == want == oracle.resolve(txns, cv), step
            assert mesh.last_wave == single.last_wave, step


# ---------------------------------------------------------------------------
# runtime protocol end-to-end (SimCluster)
# ---------------------------------------------------------------------------


def run_wave_cluster(seed=5, n_resolvers=2, obs=False, n_txns=48):
    from foundationdb_tpu.client.ryw import open_database
    from foundationdb_tpu.sim.cluster import SimCluster
    from foundationdb_tpu.sim.workloads import (
        ZipfRepairWorkload,
        run_workload,
    )

    c = SimCluster(seed=seed, n_resolvers=n_resolvers,
                   engine="oracle-replay", wave_commit=True, obs=obs)
    db = open_database(c)
    w = ZipfRepairWorkload(seed=seed, n_keys=8, n_txns=n_txns, n_clients=8,
                           reads_per_txn=3, repair=True,
                           target_pick="coldest")
    m = c.loop.run(run_workload(c, db, w), timeout=1500)
    return c, m


class TestRuntimeProtocol:
    def test_sharded_cluster_commits_with_identical_shard_counters(self):
        c, m = run_wave_cluster()
        assert m.ops == 48
        shards = [
            (r.wave_batches, r.txns_reordered, r.txns_cycle_aborted,
             r.txns_conflicted)
            for r in c.resolvers
        ]
        assert len(shards) == 2
        assert shards[0] == shards[1], shards  # byte-identical schedules
        assert shards[0][0] > 0  # windows actually exchanged
        assert sum(p.wave_exchanges for p in c.commit_proxies) > 0

    def test_metrics_surface(self):
        c, _m = run_wave_cluster(seed=6)
        metrics = c.loop.run(c.resolver_eps[0].get_metrics(), timeout=60)
        assert metrics["wave_batches"] > 0
        pm = c.loop.run(c.commit_proxy_eps[0].get_metrics(), timeout=60)
        assert pm["wave_exchanges"] > 0

    def test_obs_wave_substages_recorded(self):
        from foundationdb_tpu.obs.span import SUB_STAGES

        assert "wave_exchange" in SUB_STAGES and "wave_level" in SUB_STAGES
        c, _m = run_wave_cluster(seed=7, obs=True)
        hists = c.loop.span_sink.stage_hists
        for stage in ("wave_exchange", "wave_level", "device_dispatch"):
            assert stage in hists and hists[stage].count > 0, stage

    def test_chrome_trace_export_carries_wave_substages(self):
        """The export SHAPE, not just the flat tallies: sampled
        wave_exchange/wave_level ticks must appear as complete ("X")
        Chrome-trace events on the emitting RESOLVER's track, stamped
        with the batch's commit version — that is what makes the mesh
        protocol's comms/level cost visible on a Perfetto timeline."""
        c, _m = run_wave_cluster(seed=9, obs=True)
        doc = c.loop.span_sink.to_chrome_trace()
        by_name: dict = {}
        for e in doc["traceEvents"]:
            by_name.setdefault(e["name"], []).append(e)
        processes = doc["metadata"]["processes"]
        for stage in ("wave_exchange", "wave_level"):
            evs = by_name.get(stage)
            assert evs, f"{stage} missing from the chrome export"
            for e in evs:
                assert e["ph"] == "X"
                assert e["ts"] >= 0 and e["dur"] >= 0
                # Batch-level record: no txn id, the commit version
                # identifies the window instead.
                assert e["args"].get("tid") is None
                assert e["args"]["version"] > 0
                assert "resolver" in processes[str(e["pid"])]
        # (Txn-level span export shape is pinned in test_obs.py — at the
        # default 1-in-64 sampling this short run samples no full txn,
        # which is exactly why the batch-level records must self-identify
        # by commit version.)

    def test_empty_window_fast_path(self):
        """Idle heartbeat batches advance the chain in ONE round trip."""
        from foundationdb_tpu.runtime.flow import Loop
        from foundationdb_tpu.runtime.resolver import Resolver

        loop = Loop(seed=0)
        r = Resolver(loop, OracleConflictSet(wave_commit=True))

        async def drive():
            reply = await r.resolve_edges(0, 5, [])
            assert reply == ("empty",)
            assert r.version == 5  # chain advanced without phase 2
            # A later full window still parks/advances correctly.
            p = await r.resolve_edges(5, 9, [])
            assert p == ("empty",) and r.version == 9

        loop.run(drive(), timeout=60)

    def test_apply_retransmit_mid_flight_shares_pending_reply(self):
        """Review pin: a resolve_apply retried while the first apply is
        still executing (lost reply, proxy retry) must share the pending
        reply, never error 'without a matching resolve_edges'."""
        from foundationdb_tpu.core.types import KeyRange, TxnConflictInfo
        from foundationdb_tpu.runtime.flow import Loop, all_of
        from foundationdb_tpu.runtime.resolver import Resolver

        loop = Loop(seed=0)
        # dispatch_cost_s > 0 parks the first apply mid-execution, opening
        # the retransmit window.
        r = Resolver(loop, OracleConflictSet(wave_commit=True),
                     dispatch_cost_s=0.05)
        txns = [TxnConflictInfo(
            read_version=0,
            read_ranges=[KeyRange(b"a", b"b")],
            write_ranges=[KeyRange(b"a", b"b")],
        )]

        async def drive():
            wire = await r.resolve_edges(0, 5, txns)
            graph = combine_edges([WaveEdges.from_wire(wire)]).to_wire()

            async def first():
                return await r.resolve_apply(5, graph)

            async def retry():
                await loop.sleep(0.01)  # lands mid-dispatch_cost sleep
                return await r.resolve_apply(5, graph)

            a, b = await all_of([loop.spawn(first(), name="apply1"),
                                 loop.spawn(retry(), name="apply2")])
            assert a == b and a[0] == [Verdict.COMMITTED]
            assert r.version == 5

        loop.run(drive(), timeout=60)

    def test_repair_goodput_harness_mesh_path(self):
        from foundationdb_tpu.repair.bench import run_repair_goodput

        rec = run_repair_goodput(n_txns=48, n_clients=8, n_keys=8, seed=4,
                                 wave_commit=True, n_resolvers=2,
                                 target_pick="coldest")
        assert rec["n_resolvers"] == 2
        assert rec["repair"]["wave_schedule_identical"] is True
        shards = rec["repair"]["per_shard"]
        assert len(shards) == 2 and shards[0] == shards[1]
        assert rec["repair"]["serializable"]


# ---------------------------------------------------------------------------
# refusals + the pinned clipped-graph regression
# ---------------------------------------------------------------------------


class TestCapabilityAndRegression:
    def test_validate_wave_commit_capability_rules(self):
        validate_wave_commit(n_resolvers=4, wave_global_capable=True)
        with pytest.raises(ValueError, match="global edge-exchange"):
            validate_wave_commit(n_resolvers=2, wave_global_capable=False)
        with pytest.raises(ValueError, match="skiplist"):
            validate_wave_commit(n_resolvers=1, skiplist_engine="cpp")

    def test_sim_cluster_capability_check(self):
        from foundationdb_tpu.sim.cluster import SimCluster

        with pytest.raises(ValueError, match="skiplist"):
            SimCluster(engine="cpp", wave_commit=True)
        # Capable engines at n_resolvers > 1 construct fine.
        SimCluster(engine="oracle", wave_commit=True, n_resolvers=2,
                   timekeeper=False, ratekeeper=False)

    def test_sequential_and_path_never_emits_wave(self):
        """PINNED: even a rogue multi-resolver reply carrying a schedule
        must be ignored by the sequential AND-combine path — a
        clipped-graph schedule is not serializable."""
        from foundationdb_tpu.runtime.commit_proxy import CommitProxy
        from foundationdb_tpu.runtime.flow import Loop
        from foundationdb_tpu.runtime.shardmap import KeyShardMap

        loop = Loop(seed=0)

        class RogueResolver:
            async def resolve(self, prev_version, version, txns):
                # Claims a wave schedule from its clipped view.
                return ([Verdict.COMMITTED] * len(txns), {}, False,
                        [0] * len(txns))

        resolvers = [RogueResolver(), RogueResolver()]
        proxy = CommitProxy(
            loop, None, resolvers, KeyShardMap.uniform(2), [],
            KeyShardMap.uniform(1), wave_commit=False,
        )
        req_txns = [
            (
                type("R", (), {
                    "read_version": 1,
                    "read_ranges": [KeyRange(b"a", b"b")],
                    "write_ranges": [KeyRange(b"a", b"b")],
                    "report_conflicting_keys": False,
                })(),
                None,
            )
        ]

        async def drive():
            verdicts, _conf, _fs, wave = await proxy._resolve(
                req_txns, 0, 1
            )
            assert verdicts == [Verdict.COMMITTED]
            assert wave is None  # the schedule was DISCARDED

        loop.run(drive(), timeout=60)

    def test_wave_schedule_divergence_refused(self):
        """Shards reporting different schedules must fail the batch, not
        commit on either order."""
        from foundationdb_tpu.runtime.commit_proxy import CommitProxy
        from foundationdb_tpu.runtime.flow import Loop
        from foundationdb_tpu.runtime.shardmap import KeyShardMap

        loop = Loop(seed=0)

        class Shard:
            def __init__(self, wave):
                self._wave = wave

            async def resolve_edges(self, prev_version, version, txns):
                e = WaveEdges(
                    count=len(txns),
                    too_old=np.zeros(len(txns), bool),
                    hist_conflict=np.zeros(len(txns), bool),
                    chunks=[(len(txns),
                             pack_pred_rows({}, len(txns)))],
                )
                return e.to_wire()

            async def resolve_apply(self, version, graph_wire):
                n = WaveGraph.from_wire(graph_wire).count
                return ([Verdict.COMMITTED] * n, {}, False,
                        [x + self._wave for x in range(n)])

        proxy = CommitProxy(
            loop, None, [Shard(0), Shard(1)], KeyShardMap.uniform(2), [],
            KeyShardMap.uniform(1), wave_commit=True,
        )
        txn = type("R", (), {
            "read_version": 1,
            "read_ranges": [KeyRange(b"a", b"b")],
            "write_ranges": [KeyRange(b"a", b"b")],
            "report_conflicting_keys": False,
        })()

        async def drive():
            with pytest.raises(RuntimeError, match="divergence"):
                await proxy._resolve([(txn, None)], 0, 1)

        loop.run(drive(), timeout=60)
