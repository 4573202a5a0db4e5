"""Wave commit (reorder-don't-abort) — ISSUE 7's tentpole + satellites.

Coverage the ISSUE demands:
- engine/oracle parity of verdicts AND wave levels (randomized, plus the
  full packed/history design matrix via wave_commit=... engine args);
- deep-chain adversarial windows: conflict chain depth ≈ the batch size
  (wave round count ≈ G), all committing in dependency order;
- pure-cycle windows: RMW cliques and dependency rings, with EXACT
  cycle-only aborts (every intra-window CONFLICT proven to lie on a true
  cycle by replay_wave_schedule, and committed counts exact);
- sequential replay: the realized (wave, index) order re-executed
  sequentially agrees byte-for-byte (replay_wave_schedule + the
  ReplayCheckedOracle engine);
- the mesh engine: wave levels surviving the packed all_gather;
- runtime plumbing: Resolver wave pass-through + attribution counters,
  commit-proxy same-version mutation ordering, SimCluster wiring and the
  multi-resolver refusal;
- env-flag validation satellite: unknown FDB_TPU_* values raise at
  import with the accepted list (subprocess), including the new
  FDB_TPU_WAVE_COMMIT.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from foundationdb_tpu.core.types import (
    WAVE_LEVEL_CYCLE,
    WAVE_LEVEL_NONE,
    KeyRange,
    TxnConflictInfo,
    Verdict,
)
from foundationdb_tpu.models.conflict_set import TPUConflictSet
from foundationdb_tpu.sim.oracle import (
    OracleConflictSet,
    ReplayCheckedOracle,
    replay_wave_schedule,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _k(i: int) -> bytes:
    return b"w%04d" % i  # 5 bytes: point ranges stay under max_key_bytes=8


def _txn(reads, writes, rv=0, report=False) -> TxnConflictInfo:
    def rng(x):
        return KeyRange(_k(x), _k(x) + b"\x00") if isinstance(x, int) else x

    return TxnConflictInfo(
        read_ranges=[rng(r) for r in reads],
        write_ranges=[rng(w) for w in writes],
        read_version=rv,
        report_conflicting_keys=report,
    )


def chain(n: int, rv: int = 0) -> list[TxnConflictInfo]:
    """Txn i reads key i and writes key i+1: the only constraint edges are
    i+1 → i (the reader of key i+1 must precede its writer), a single
    dependency chain of depth n — sequential BATCH order commits only the
    prefix-free subset, a wave schedule commits ALL of it."""
    return [_txn([i], [i + 1], rv=rv) for i in range(n)]


def rmw_clique(n: int, key: int = 0, rv: int = 0) -> list[TxnConflictInfo]:
    """n read-modify-writes of one key: every pair is mutually entangled
    (each reads what the other writes) — a pure-cycle window where any
    schedule commits EXACTLY ONE member."""
    return [_txn([key], [key], rv=rv, report=True) for _ in range(n)]


def ring(n: int, rv: int = 0) -> list[TxnConflictInfo]:
    """Txn i reads key i and writes key (i+1) % n: one n-cycle — breaking
    a single victim leaves a chain that all commits."""
    return [_txn([i], [(i + 1) % n], rv=rv, report=True) for i in range(n)]


def wave_cs(batch_size=64, **kw) -> TPUConflictSet:
    # One shape family across the file (keys fit 8 bytes, 4 ranges): every
    # (entry point, batch_size) pair compiles once and every test after
    # the first reuses the program.
    kw.setdefault("capacity", 1 << 12)
    kw.setdefault("max_read_ranges", 4)
    kw.setdefault("max_write_ranges", 4)
    kw.setdefault("max_key_bytes", 8)
    return TPUConflictSet(batch_size=batch_size, wave_commit=True, **kw)


def assert_schedule_parity(cs, orc, txns, cv, oldest=0):
    hist_before = list(orc.history)
    floor_before = max(orc.oldest_version, oldest)
    got = cs.resolve(txns, cv, oldest_version=oldest)
    want = orc.resolve(txns, cv, oldest_version=oldest)
    assert got == want
    assert cs.last_wave == orc.last_wave
    replay_wave_schedule(txns, want, orc.last_wave, hist_before, floor_before)
    return got


# ---------------------------------------------------------------------------
# Kernel ↔ oracle parity (verdicts + levels + sequential replay)
# ---------------------------------------------------------------------------


class TestWaveParity:
    @pytest.mark.parametrize("seed", [3, 17, 29])
    def test_randomized_parity_with_replay(self, seed):
        from tests.test_conflict_oracle import rand_txn

        rng = np.random.default_rng(seed)
        cs = wave_cs()
        orc = OracleConflictSet(wave_commit=True)
        cv = 1000
        for _ in range(8):
            cv += int(rng.integers(1, 50))
            txns = [
                rand_txn(rng,
                         read_version=int(rng.integers(max(0, cv - 300), cv)))
                for _ in range(int(rng.integers(1, 48)))
            ]
            oldest = cv - 200  # tight window: TOO_OLD + history GC ride along
            assert_schedule_parity(cs, orc, txns, cv, oldest=oldest)

    def test_wave_commits_more_than_seq_on_contention(self):
        """The tentpole's point, in one window: a sequential-order engine
        aborts most of a dependency chain, the wave engine commits it."""
        txns = chain(32, rv=9) + rmw_clique(4, key=200, rv=9)
        seq = TPUConflictSet(capacity=1 << 12, batch_size=64,
                             max_read_ranges=4, max_write_ranges=4,
                             max_key_bytes=8, wave_commit=False)
        wav = wave_cs()
        sv = seq.resolve(list(txns), 10, oldest_version=0)
        wv = wav.resolve(list(txns), 10, oldest_version=0)
        n_seq = sum(v == Verdict.COMMITTED for v in sv)
        n_wav = sum(v == Verdict.COMMITTED for v in wv)
        # chain(32) fully commits under wave; the clique contributes
        # exactly one commit under either schedule.
        assert n_wav == 33
        assert n_wav > n_seq

    def test_conflicting_key_reports_cover_oracle(self):
        rng = np.random.default_rng(41)
        from tests.test_conflict_oracle import rand_txn

        cs = wave_cs()
        orc = OracleConflictSet(wave_commit=True)
        cv = 500
        for _ in range(4):
            cv += int(rng.integers(5, 40))
            txns = [
                rand_txn(rng,
                         read_version=int(rng.integers(max(0, cv - 150), cv)))
                for _ in range(24)
            ]
            for t in txns[::2]:
                object.__setattr__(t, "report_conflicting_keys", True)
            cs.resolve(txns, cv, oldest_version=cv - 120)
            orc.resolve(txns, cv, oldest_version=cv - 120)
            assert cs.last_conflicting.keys() == orc.last_conflicting.keys()
            for i, ranges in orc.last_conflicting.items():
                got = cs.last_conflicting[i]
                for r in ranges:
                    assert any(g.begin <= r.begin and r.end <= g.end
                               for g in got)


# ---------------------------------------------------------------------------
# Adversarial graphs: deep chains and pure cycles
# ---------------------------------------------------------------------------


class TestDeepChain:
    def test_chain_depth_equals_window(self):
        """Chain depth == batch size: the wave loop's round count reaches
        its bound (one txn determined per round) and every link commits
        in dependency order — levels are exactly the chain positions,
        deepest-reader first."""
        n = 64
        cs = wave_cs(batch_size=n)
        orc = OracleConflictSet(wave_commit=True)
        txns = chain(n, rv=0)
        got = assert_schedule_parity(cs, orc, txns, 10)
        assert got == [Verdict.COMMITTED] * n
        # txn n-1 (reads key n-1, written by txn n-2) has no predecessor…
        # edge j+1 → j throughout, so levels DESCEND from the chain tail.
        assert cs.last_wave == list(range(n - 1, -1, -1))

    def test_deep_chain_interleaved_with_independents(self):
        n = 32  # 2n txns fit the shared batch_size=64 program
        links = chain(n, rv=0)
        txns = []
        for i in range(n):
            txns.append(links[i])
            txns.append(_txn([1000 + i], [2000 + i], rv=0))
        cs = wave_cs()
        orc = OracleConflictSet(wave_commit=True)
        got = assert_schedule_parity(cs, orc, txns, 10)
        assert got == [Verdict.COMMITTED] * (2 * n)

    def test_seq_and_wave_commit_agree_on_conflict_free_windows(self):
        """On windows with NO intra-batch read/write overlap the two
        modes must be byte-identical (same verdicts, levels all 0/NONE):
        reordering only ever widens acceptance where conflicts exist."""
        rng = np.random.default_rng(7)
        seq = TPUConflictSet(capacity=1 << 12, batch_size=64,
                             max_read_ranges=4, max_write_ranges=4,
                             max_key_bytes=8, wave_commit=False)
        wav = wave_cs()
        cv = 100
        for _ in range(3):
            ks = rng.permutation(400)
            txns = [_txn([int(ks[2 * i])], [int(ks[2 * i + 1])], rv=cv - 1)
                    for i in range(24)]
            sv = seq.resolve(list(txns), cv, oldest_version=0)
            wv = wav.resolve(list(txns), cv, oldest_version=0)
            assert sv == wv
            assert all(
                lv == (0 if v == Verdict.COMMITTED else WAVE_LEVEL_NONE)
                for lv, v in zip(wav.last_wave, wv)
            )
            cv += 10


class TestPureCycles:
    @pytest.mark.parametrize("n", [2, 5, 16])
    def test_rmw_clique_commits_exactly_one(self, n):
        cs = wave_cs()
        orc = OracleConflictSet(wave_commit=True)
        txns = rmw_clique(n, rv=0)
        got = assert_schedule_parity(cs, orc, txns, 10)
        assert sum(v == Verdict.COMMITTED for v in got) == 1
        assert sum(lv == WAVE_LEVEL_CYCLE for lv in cs.last_wave) == n - 1

    @pytest.mark.parametrize("n", [3, 8, 31])
    def test_ring_aborts_one_victim(self, n):
        """An n-cycle loses exactly its deterministic victim; the broken
        ring is a chain and commits whole."""
        cs = wave_cs()
        orc = OracleConflictSet(wave_commit=True)
        txns = ring(n, rv=0)
        got = assert_schedule_parity(cs, orc, txns, 10)
        assert sum(v == Verdict.COMMITTED for v in got) == n - 1
        assert cs.last_wave.count(WAVE_LEVEL_CYCLE) == 1

    def test_downstream_of_cycle_still_commits(self):
        """Txns merely DOWNSTREAM of a cycle are re-examined after the
        victim aborts and must commit — abort is cycle-membership-exact,
        not reachability-wide."""
        txns = rmw_clique(2, key=0, rv=0)
        # reads key 5, writes key 0: must serialize BEFORE both clique
        # members (they read key 0) — upstream, unaffected.
        txns.append(_txn([5], [0], rv=0, report=True))
        # reads key 0 (written by the clique), writes key 9: downstream.
        txns.append(_txn([0], [9], rv=0, report=True))
        cs = wave_cs()
        orc = OracleConflictSet(wave_commit=True)
        got = assert_schedule_parity(cs, orc, txns, 10)
        assert got[2] == Verdict.COMMITTED
        assert got[3] == Verdict.COMMITTED
        assert sum(v == Verdict.COMMITTED for v in got) == 3
        assert cs.last_wave.count(WAVE_LEVEL_CYCLE) == 1

    def test_many_disjoint_cycles(self):
        """One victim per cycle, nothing else: 10 disjoint 2-cliques plus
        independents."""
        txns = []
        for c in range(10):
            txns += rmw_clique(2, key=c, rv=0)
        txns += [_txn([100 + i], [200 + i], rv=0) for i in range(8)]
        cs = wave_cs()
        orc = OracleConflictSet(wave_commit=True)
        got = assert_schedule_parity(cs, orc, txns, 10)
        assert sum(v == Verdict.COMMITTED for v in got) == 10 + 8
        assert cs.last_wave.count(WAVE_LEVEL_CYCLE) == 10


# ---------------------------------------------------------------------------
# Chunking, the window path, and the mesh engine
# ---------------------------------------------------------------------------


class TestWaveSurfaces:
    def test_chunked_resolve_matches_chunk_fed_oracle(self):
        """Chunks serialize in submission order (earlier chunks' writes
        paint before later chunks resolve), so the engine's coherent
        last_wave equals the oracle fed the same chunk boundaries with
        the same wave offsets."""
        from tests.test_conflict_oracle import rand_txn

        rng = np.random.default_rng(13)
        B = 16
        cs = wave_cs(batch_size=B, max_key_bytes=8)
        orc = OracleConflictSet(wave_commit=True)
        cv = 100
        for _ in range(3):
            txns = [rand_txn(rng, read_version=cv - 1) for _ in range(40)]
            got = cs.resolve(txns, cv, oldest_version=0)
            want, waves, off = [], [], 0
            for s in range(0, len(txns), B):
                want += orc.resolve(txns[s:s + B], cv, oldest_version=0)
                lv = orc.last_wave
                waves += [x + off if x >= 0 else x for x in lv]
                off += max((x for x in lv if x >= 0), default=-1) + 1
            assert got == want
            assert cs.last_wave == waves
            cv += 10

    def test_chunked_reordered_count_ignores_chunk_offsets(self):
        """40 pairwise-independent txns over batch_size=16: the published
        schedule carries cross-chunk offsets (chunks serialize), but
        NOTHING was reordered — the exact attribution count must be 0."""
        from foundationdb_tpu.runtime.flow import Loop
        from foundationdb_tpu.runtime.resolver import Resolver

        cs = wave_cs(batch_size=16)
        txns = [_txn([2 * i], [2 * i + 1], rv=0) for i in range(40)]
        got = cs.resolve(txns, 10, oldest_version=0)
        assert got == [Verdict.COMMITTED] * 40
        assert max(cs.last_wave) > 0      # offsets present in the schedule
        assert cs.last_reordered == 0     # …but nothing actually reordered
        loop = Loop(seed=1)
        res = Resolver(loop, wave_cs(batch_size=16))
        loop.run(res.resolve(0, 10, txns, oldest_version=0))
        assert res.txns_reordered == 0
        assert res.txns_cycle_aborted == 0

    def test_window_path_publishes_per_batch_waves(self):
        from foundationdb_tpu.models.conflict_set import encode_resolve_batch

        B = 16
        cs = wave_cs(batch_size=B)
        orc = OracleConflictSet(wave_commit=True)
        batches = [
            chain(B, rv=0),
            rmw_clique(B, rv=1),
            [_txn([300 + i], [400 + i], rv=2) for i in range(B)],
        ]
        wire = b"".join(encode_resolve_batch(t) for t in batches)
        cvs = [10, 20, 30]
        got = cs.resolve_wire_window(wire, cvs, B)
        assert got.shape == (3, B)
        assert cs.last_wave_window is not None
        assert cs.last_wave_window.shape == (3, B)
        for i, (cv, txns) in enumerate(zip(cvs, batches)):
            want = orc.resolve(txns, cv, oldest_version=0)
            assert [int(v) for v in got[i]] == [int(v) for v in want]
            assert cs.last_wave_window[i].tolist() == orc.last_wave

    def test_sharded_engine_wave_parity(self):
        """Mesh engine: the schedule must survive the packed all_gather —
        every device computes the same waves from the replicated batch."""
        from foundationdb_tpu.parallel.sharded_resolver import (
            ShardedConflictSet,
        )

        cs = ShardedConflictSet(
            n_shards=4, capacity=1 << 10, batch_size=64, max_read_ranges=4,
            max_write_ranges=4, max_key_bytes=8, wave_commit=True,
        )
        orc = OracleConflictSet(wave_commit=True)
        for cv, txns in [
            (10, chain(32, rv=0) + rmw_clique(3, key=500, rv=0)),
            (20, ring(9, rv=9)),
        ]:
            assert_schedule_parity(cs, orc, txns, cv)

    def test_replay_checked_oracle_raises_on_forged_schedule(self):
        """The replay checker must actually have teeth."""
        txns = rmw_clique(2, rv=0)
        with pytest.raises(AssertionError):
            # Forged: both clique members claim to commit at waves 0,1 —
            # replay sees txn 1 read txn 0's write.
            replay_wave_schedule(txns, [Verdict.COMMITTED] * 2, [0, 1], [], 0)
        orc = ReplayCheckedOracle(wave_commit=True)
        got = orc.resolve(txns, 10, oldest_version=0)  # must NOT raise
        assert sorted(v.name for v in got) == ["COMMITTED", "CONFLICT"]


# ---------------------------------------------------------------------------
# Runtime plumbing: resolver, commit proxy, sim cluster
# ---------------------------------------------------------------------------


class TestRuntimePlumbing:
    def test_resolver_wave_passthrough_and_counters(self):
        from foundationdb_tpu.runtime.flow import Loop
        from foundationdb_tpu.runtime.resolver import Resolver

        loop = Loop(seed=1)
        res = Resolver(loop, OracleConflictSet(wave_commit=True))
        txns = chain(6, rv=0) + rmw_clique(3, key=700, rv=0)
        verdicts, _conf, fail_safe, wave = loop.run(
            res.resolve(0, 10, txns, oldest_version=0)
        )
        assert not fail_safe
        assert wave is not None and len(wave) == len(txns)
        # chain members at waves 1..5, plus the clique's survivor — its
        # cycle breaks only after the chain's waves drain, so it commits
        # at wave 6, reordered behind everything.
        assert res.txns_reordered == 6
        assert res.txns_cycle_aborted == 2  # clique loses 2 of 3
        assert res.txns_conflicted == 2
        m = loop.run(res.get_metrics())
        assert m["txns_reordered"] == 6
        assert m["txns_cycle_aborted"] == 2
        assert m["txns_conflicted"] == 2

    def test_seq_resolver_reports_no_wave(self):
        from foundationdb_tpu.runtime.flow import Loop
        from foundationdb_tpu.runtime.resolver import Resolver

        loop = Loop(seed=1)
        res = Resolver(loop, OracleConflictSet())
        verdicts, _conf, _fs, wave = loop.run(
            res.resolve(0, 10, chain(4, rv=0), oldest_version=0)
        )
        assert wave is None
        assert res.txns_reordered == 0 and res.txns_cycle_aborted == 0

    def test_commit_proxy_orders_same_version_mutations_by_wave(self):
        """Two committed txns both write key X; batch order says A last,
        wave order says B last — the tagged mutation list must land B's
        write after A's (tlogs/storages apply in list order)."""
        from foundationdb_tpu.core.mutations import Mutation, MutationType
        from foundationdb_tpu.runtime.commit_proxy import (
            CommitProxy,
            CommitRequest,
        )
        from foundationdb_tpu.runtime.shardmap import KeyShardMap

        proxy = object.__new__(CommitProxy)  # _assemble needs only these:
        proxy.storage_map = KeyShardMap.uniform(1)
        proxy.backup_enabled = False
        reqs = [
            CommitRequest(mutations=[
                Mutation(MutationType.SET_VALUE, b"x", b"A")], read_version=0),
            CommitRequest(mutations=[
                Mutation(MutationType.SET_VALUE, b"x", b"B")], read_version=0),
        ]
        batch = [(r, None) for r in reqs]
        verdicts = [Verdict.COMMITTED, Verdict.COMMITTED]
        by_arrival = proxy._assemble(batch, verdicts, 7)
        assert [m.param2 for m in by_arrival[0]] == [b"A", b"B"]
        reordered = proxy._assemble(batch, verdicts, 7, wave=[1, 0])
        assert [m.param2 for m in reordered[0]] == [b"B", b"A"]

    def test_sim_cluster_wave_plumbing_and_capability_check(self):
        """ISSUE 13: the blanket n_resolvers>1 refusal became a
        CAPABILITY check — engines implementing the global edge-exchange
        protocol (oracle, tpu) deploy sharded; the cpp skiplist (no
        conflict graph, no protocol) still refuses outright."""
        from foundationdb_tpu.sim.cluster import SimCluster

        c = SimCluster(seed=3, engine="oracle", wave_commit=True)
        assert all(r.cs.wave_commit for r in c.resolvers)
        c2 = SimCluster(seed=3, engine="oracle", n_resolvers=2,
                        wave_commit=True)
        assert all(r.cs.wave_global_capable for r in c2.resolvers)
        assert all(p.wave_commit for p in c2.commit_proxies)
        with pytest.raises(ValueError, match="cpp"):
            SimCluster(seed=3, engine="cpp", wave_commit=True)

    def test_deployed_factory_wave_capability_check(self, monkeypatch):
        from foundationdb_tpu.server import make_conflict_set

        monkeypatch.setenv("FDB_TPU_WAVE_COMMIT", "1")
        # Capable engines construct at any resolver count (the global
        # protocol); the cpu skiplist still refuses.
        cs = make_conflict_set("oracle", n_resolvers=2)
        assert cs.wave_commit and cs.wave_global_capable
        assert make_conflict_set("oracle", n_resolvers=1).wave_commit
        with pytest.raises(ValueError, match="cpu skiplist"):
            make_conflict_set("cpu", n_resolvers=1)
        with pytest.raises(ValueError, match="cpu skiplist"):
            make_conflict_set("cpu", n_resolvers=2)
        monkeypatch.setenv("FDB_TPU_WAVE_COMMIT", "0")
        assert make_conflict_set("oracle", n_resolvers=2).wave_commit is False

    def test_wave_rmw_workload_end_to_end_serializable(self):
        """Full stack under wave commit: Zipf RMW through proxies on a
        replay-checked oracle cluster — the RMW-sum invariant plus the
        inline sequential replay both gate, and the attribution counters
        surface reorders."""
        from foundationdb_tpu.client.ryw import open_database
        from foundationdb_tpu.sim.workloads import (
            ZipfRepairWorkload,
            run_workload,
        )
        from foundationdb_tpu.sim.cluster import SimCluster

        c = SimCluster(seed=23, engine="oracle-replay", wave_commit=True)
        db = open_database(c)
        w = ZipfRepairWorkload(seed=23, n_keys=8, n_txns=64, n_clients=16,
                               reads_per_txn=3, repair=True,
                               target_pick="coldest")
        metrics = c.loop.run(run_workload(c, db, w), timeout=1500)
        assert metrics.ops == 64  # check() raised on any lost increment
        assert sum(r.txns_reordered for r in c.resolvers) > 0
        assert sum(r.txns_cycle_aborted for r in c.resolvers) >= 0
        from foundationdb_tpu.runtime.status import fetch_status

        doc = c.loop.run(fetch_status(c), timeout=300)
        res = doc["workload"]["resolver"]
        assert res["reordered"] == sum(r.txns_reordered for r in c.resolvers)
        assert res["aborted_cycles"] == sum(
            r.txns_cycle_aborted for r in c.resolvers)
        assert res["conflicts"] == sum(
            r.txns_conflicted for r in c.resolvers)


# ---------------------------------------------------------------------------
# Env-flag validation satellite (import-once flags, subprocess)
# ---------------------------------------------------------------------------


_FLAG_PROBE = r"""
import importlib
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import foundationdb_tpu.models.conflict_kernel as ck  # defaults import fine

# The flags are read at import, so each case re-executes the module via
# importlib.reload — one subprocess covers the whole rejection matrix
# (spawning a fresh interpreter per bogus value would pay the jax import
# again for the same assertion).
for flag, bogus, accepted in [
    ("FDB_TPU_WAVE_COMMIT", "yes", "0, 1"),
    ("FDB_TPU_SPEC_RESOLVE", "On", "0, 1"),
]:
    os.environ[flag] = bogus
    try:
        importlib.reload(ck)
    except ValueError as e:
        msg = str(e)
        assert flag in msg and bogus in msg and accepted in msg, (flag, msg)
    else:
        raise SystemExit(f"{flag}={bogus} was silently accepted")
    finally:
        del os.environ[flag]
# Valid non-default values import clean and land in the snapshot.
os.environ["FDB_TPU_WAVE_COMMIT"] = "1"
os.environ["FDB_TPU_SPEC_RESOLVE"] = "1"
importlib.reload(ck)
assert ck._WAVE_COMMIT is True and ck._SPEC_RESOLVE is True
print("FLAGS-OK")
"""


class TestEnvFlagValidation:
    def test_unknown_values_raise_with_accepted_list(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        for k in ("FDB_TPU_WAVE_COMMIT", "FDB_TPU_SPEC_RESOLVE"):
            env.pop(k, None)
        r = subprocess.run(
            [sys.executable, "-c", _FLAG_PROBE], env=env,
            capture_output=True, text=True, timeout=300, cwd=_REPO,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        assert r.stdout.strip().splitlines()[-1] == "FLAGS-OK"

    def test_cluster_default_validates_without_jax(self, monkeypatch):
        from foundationdb_tpu.sim.cluster import _wave_commit_default

        monkeypatch.setenv("FDB_TPU_WAVE_COMMIT", "on")
        with pytest.raises(ValueError, match="accepted values: 0, 1"):
            _wave_commit_default()
        monkeypatch.setenv("FDB_TPU_WAVE_COMMIT", "1")
        assert _wave_commit_default() is True


# ---------------------------------------------------------------------------
# Env-default parity: the wave default taken from the environment
# ---------------------------------------------------------------------------


_WAVE_CHILD = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
from foundationdb_tpu.models import conflict_kernel as ck
assert ck._WAVE_COMMIT is True
from foundationdb_tpu.core.types import KeyRange, TxnConflictInfo, Verdict
from foundationdb_tpu.models.conflict_set import TPUConflictSet
from foundationdb_tpu.sim.oracle import OracleConflictSet, replay_wave_schedule


def k(i):
    return b"wk%04d" % i


def txn(reads, writes, rv=0):
    return TxnConflictInfo(
        read_ranges=[KeyRange(k(r), k(r) + b"\x00") for r in reads],
        write_ranges=[KeyRange(k(w), k(w) + b"\x00") for w in writes],
        read_version=rv)


cs = TPUConflictSet(capacity=1 << 11, batch_size=64, max_key_bytes=12)
assert cs.wave_commit  # env default selected the wave engine
orc = OracleConflictSet(wave_commit=True)
cv = 10
for txns in (
    [txn([i], [i + 1], rv=cv - 1) for i in range(40)],        # deep chain
    [txn([0], [0], rv=cv - 1) for _ in range(6)],             # pure clique
    [txn([i], [(i + 1) % 11], rv=cv - 1) for i in range(11)],  # ring
):
    hist = list(orc.history)
    got = cs.resolve(txns, cv, oldest_version=0)
    want = orc.resolve(txns, cv, oldest_version=0)
    assert got == want
    assert cs.last_wave == orc.last_wave
    replay_wave_schedule(txns, want, orc.last_wave, hist, 0)
    cv += 10
print("WAVE-MATRIX-OK")
"""


@pytest.mark.slow  # fresh-jax-import + engine compile per child (~15 s
# each); the fast battery proves the same parity in-process (chain/clique/
# ring above) and the env→engine default via the oracle path
# (test_deployed_factory_refuses_wave_multi_resolver), so these children
# only add the ENV path on the DEVICE engine.
def test_wave_env_default_parity():
    env = dict(os.environ, JAX_PLATFORMS="cpu", FDB_TPU_WAVE_COMMIT="1")
    r = subprocess.run(
        [sys.executable, "-c", _WAVE_CHILD], env=env, capture_output=True,
        text=True, timeout=600, cwd=_REPO,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1] == "WAVE-MATRIX-OK"
