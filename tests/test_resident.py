"""Device-resident dictionary & rank-space history.

The engine against the brute-force oracle on the same stream. The
eviction / overflow / full-repack / reshard paths are forced with tiny
dictionary capacities — randomized parity must hold across all of them,
including keys that are evicted and then reappear.
"""

import threading

import numpy as np
import pytest

from foundationdb_tpu.core.types import KeyRange, TxnConflictInfo, Verdict
from foundationdb_tpu.models import conflict_kernel as ck
from foundationdb_tpu.models.conflict_set import (
    TPUConflictSet,
    encode_resolve_batch,
)
from foundationdb_tpu.sim.oracle import OracleConflictSet
from tests.test_conflict_oracle import rand_txn

KW = dict(capacity=512, batch_size=32, max_read_ranges=4,
          max_write_ranges=4, max_key_bytes=8)

def pt(k: bytes) -> KeyRange:
    return KeyRange(k, k + b"\x00")


def drive_parity(rng, cs_res, n_batches=10, n_txns=(1, 40),
                 report_some=False):
    """Same stream through the engine and the oracle; assert parity.
    Returns the oracle (for follow-on assertions)."""
    oracle = OracleConflictSet()
    cv = 1000
    for batch_i in range(n_batches):
        cv += int(rng.integers(1, 50))
        txns = [
            rand_txn(rng, read_version=int(rng.integers(max(0, cv - 300), cv)))
            for _ in range(int(rng.integers(*n_txns)))
        ]
        if report_some:
            for t in txns[::3]:
                object.__setattr__(t, "report_conflicting_keys", True)
        oldest = cv - 200
        got_r = cs_res.resolve(txns, cv, oldest_version=oldest)
        oracle.oldest_version = max(oracle.oldest_version, oldest)
        want = oracle.resolve(txns, cv)
        assert got_r == want, f"resident vs oracle, batch {batch_i}"
        if report_some:
            for i, ranges in oracle.last_conflicting.items():
                kernel = cs_res.last_conflicting.get(i)
                assert kernel is not None, f"batch {batch_i} txn {i}"
                for r in ranges:
                    assert any(
                        k.begin <= r.begin and r.end <= k.end for k in kernel
                    ), f"batch {batch_i} txn {i}: {r} not covered"
    return oracle


@pytest.mark.parametrize("seed", [1, 2])
def test_parity_vs_oracle_and_packed(seed):
    rng = np.random.default_rng(seed)
    cs_res = TPUConflictSet(**KW)
    assert isinstance(cs_res.state, ck.ResState)
    drive_parity(rng, cs_res, report_some=(seed == 1))
    assert not cs_res.overflowed
    stats = cs_res.dict_stats
    assert stats["dispatches"] > 0 and stats["resident_keys"] > 1


def test_duplicate_keys_straddling_dispatches_hit_the_mirror():
    cs = TPUConflictSet(**KW)
    keys = [f"k{i}".encode() for i in range(24)]
    txns = [TxnConflictInfo(99, [pt(k)], [pt(k)]) for k in keys]
    cs.resolve(txns, 100)
    before = dict(cs.dict_stats)
    cs.resolve([TxnConflictInfo(100, [pt(k)], [pt(k)]) for k in keys], 101)
    after = cs.dict_stats
    # Second dispatch re-uses every endpoint: no new keys, 100% hits.
    assert after["delta_new_keys"] == before["delta_new_keys"]
    assert after["endpoint_hits"] - before["endpoint_hits"] > 0
    assert after["delta_hit_rate"] > before["delta_hit_rate"]


def test_eviction_then_reappearance_stays_exact():
    """Tiny dictionary: churning fresh keys forces repacks that evict the
    oldest-used keys; a key that was evicted and then REAPPEARS must
    re-enter the dictionary and still resolve exactly (the history that
    referenced it was remapped, never corrupted)."""
    kw = dict(KW, window_versions=120)
    cs = TPUConflictSet(dict_capacity=96, dict_delta_slots=48, **kw)
    oracle = OracleConflictSet()
    hot = b"evict-me"
    cv = 1000
    for i in range(14):
        cv += 10
        txns = [TxnConflictInfo(cv - 5, [pt(hot)], [pt(hot)])] if i % 7 == 0 \
            else []
        txns += [
            TxnConflictInfo(cv - 5, [], [pt(f"churn{i}_{j}".encode())])
            for j in range(8)
        ]
        got = cs.resolve(txns, cv, oldest_version=cv - 100)
        oracle.oldest_version = max(oracle.oldest_version, cv - 100)
        want = oracle.resolve(txns, cv)
        assert got == want, f"round {i}"
    stats = cs.dict_stats
    assert stats["full_repacks"] > 0, stats
    assert stats["evictions"] > 0, stats
    assert not cs.overflowed


def test_overflow_fallback_tiny_delta_forces_full_repack():
    rng = np.random.default_rng(9)
    cs = TPUConflictSet(dict_delta_slots=4, **KW)
    drive_parity(rng, cs, n_batches=6, n_txns=(8, 24))
    stats = cs.dict_stats
    # >4 new keys per dispatch: every early dispatch takes the fallback.
    assert stats["full_repacks"] >= 2, stats


def test_dict_capacity_too_small_raises_actionable_error():
    cs = TPUConflictSet(dict_capacity=8, dict_delta_slots=4,
                        **KW)
    txns = [TxnConflictInfo(99, [], [pt(f"k{i}".encode())]) for i in range(32)]
    with pytest.raises(ValueError, match="dict_capacity"):
        cs.resolve(txns, 100)


def test_wave_levels_parity_resident():
    """Wave commit in rank space: verdicts AND wave levels match the wave
    oracle on RMW chains + cycles."""
    rng = np.random.default_rng(21)
    kw = dict(KW, batch_size=64)
    cs_r = TPUConflictSet(wave_commit=True, **kw)
    cs_b = OracleConflictSet(wave_commit=True)
    cv = 500
    for i in range(6):
        cv += 10
        txns = []
        for j in range(int(rng.integers(8, 32))):
            a = f"w{rng.integers(0, 6)}".encode()
            b = f"w{rng.integers(0, 6)}".encode()
            txns.append(TxnConflictInfo(cv - 1, [pt(a)], [pt(b)]))
        got_r = cs_r.resolve(txns, cv)
        got_b = cs_b.resolve(txns, cv)
        assert got_r == got_b, f"round {i}"
        assert cs_r.last_wave == cs_b.last_wave, f"round {i} levels"
        assert cs_r.last_reordered == cs_b.last_reordered


def test_window_path_parity_and_deferred_repack_threaded():
    """The pipelined window path with a DEFERRED repack: a tiny delta
    budget makes the pack worker emit _RepackPlans; the mirror gate must
    serialize the worker against dispatch-side repacks and verdicts must
    equal the oracle's byte-for-byte."""
    from foundationdb_tpu.sched.packing import PipelinedWindowRunner

    rng = np.random.default_rng(13)
    kw = dict(KW, batch_size=16)
    cs_r = TPUConflictSet(dict_delta_slots=8, **kw)
    cs_b = OracleConflictSet()
    runner = PipelinedWindowRunner(cs_r, threaded=True)
    k, count = 2, 16
    outs_b = []
    n_windows = 5
    cv = 1
    wires = []
    for w in range(n_windows):
        txns = [
            rand_txn(rng, read_version=max(0, cv - 1))
            for _ in range(k * count)
        ]
        wire = encode_resolve_batch(txns)
        cvs = list(range(cv, cv + k))
        wires.append((wire, cvs))
        outs_b.append([
            [int(v) for v in cs_b.resolve(
                txns[b * count:(b + 1) * count], cvs[b])]
            for b in range(k)])
        cv += k
    for wire, cvs in wires:
        runner.submit(wire, cvs, count)
        runner.dispatch_ready()
    got = [runner.collect_next() for _ in range(n_windows)]
    runner.close()
    for w, (g, b) in enumerate(zip(got, outs_b)):
        assert np.array_equal(g, b), f"window {w}"
    stats = cs_r.dict_stats
    assert stats["repack_stalls"] >= 1, stats
    assert stats["full_repacks"] >= 1, stats


def test_gc_and_headroom_recover_under_resident():
    """advance()/headroom/clear_overflow drive the ResState wrapper: the
    fail-safe contract (headroom recovers as the window slides) must hold
    with the rank-space history."""
    cs = TPUConflictSet(capacity=256, batch_size=16,
                        max_key_bytes=8, window_versions=100)
    cv = 1000
    for i in range(30):
        cv += 10
        txns = [
            TxnConflictInfo(cv - 5, [], [pt(f"g{i}_{j}".encode())])
            for j in range(8)
        ]
        assert all(
            v == Verdict.COMMITTED for v in cs.resolve(txns, cv)
        )
    h0 = cs.headroom()
    cv += 1000  # slide the whole window past every write
    cs.advance(cv)
    assert cs.headroom() > h0
    assert not cs.overflowed
    cs.clear_overflow()  # exercises the ResState rewrap path


class TestResidentMesh:
    def _mk(self, **over):
        from foundationdb_tpu.parallel.sharded_resolver import (
            ShardedConflictSet,
        )

        kw = dict(KW, batch_size=32, auto_reshard=False, n_shards=2)
        kw.update(over)
        return ShardedConflictSet(**kw)

    def test_mesh_parity_vs_oracle(self):
        rng = np.random.default_rng(31)
        cs = self._mk()
        assert isinstance(cs.state, ck.ResState)
        drive_parity(rng, cs, n_batches=8)

    def test_reshard_scoped_repack_preserves_verdicts(self):
        """Explicit reshard mid-stream: per-shard rank histories are
        redistributed at the new bound ranks (moved shards only — the
        scoped counter proves the economy), bound keys are pinned, and
        verdicts stay oracle-exact across the move."""
        rng = np.random.default_rng(33)
        cs = self._mk(n_shards=4)
        oracle = OracleConflictSet()
        cv = 1000
        keys_seen = []
        for batch_i in range(10):
            cv += 20
            ks = [bytes([97 + int(rng.integers(0, 26))]) + b"x"
                  for _ in range(16)]
            keys_seen += ks
            txns = [TxnConflictInfo(cv - 10, [pt(k)], [pt(k)]) for k in ks]
            got = cs.resolve(txns, cv, oldest_version=cv - 500)
            oracle.oldest_version = max(oracle.oldest_version, cv - 500)
            want = oracle.resolve(txns, cv)
            assert got == want, f"batch {batch_i}"
            if batch_i == 4:
                from foundationdb_tpu.parallel.sharded_resolver import (
                    density_splits,
                )

                before = cs.reshard_moved_shards
                cs.reshard(density_splits(4, keys_seen))
                assert cs.reshard_moved_shards > before
                # New bound keys are pinned in the mirror.
                assert int(cs._mirror.pinned.sum()) >= 4
        occ = cs.shard_occupancy()
        assert len(occ) == 4 and all(o >= 1 for o in occ)

    def test_auto_reshard_default_resident(self):
        """The runtime-default auto reshard splits at live boundary keys
        (already resident → no dictionary insert) and keeps verdicts
        oracle-exact."""
        rng = np.random.default_rng(35)
        cs = self._mk(n_shards=2, auto_reshard=True,
                      reshard_interval=3, reshard_skew=1.5)
        oracle = OracleConflictSet()
        cv = 1000
        for batch_i in range(9):
            cv += 20
            # Zipf-ish: everything lands low in the keyspace so uniform
            # splits skew and the auto policy fires.
            ks = [b"\x00" + bytes([int(rng.integers(0, 200))])
                  for _ in range(16)]
            txns = [TxnConflictInfo(cv - 10, [pt(k)], [pt(k)]) for k in ks]
            got = cs.resolve(txns, cv, oldest_version=cv - 500)
            oracle.oldest_version = max(oracle.oldest_version, cv - 500)
            want = oracle.resolve(txns, cv)
            assert got == want, f"batch {batch_i}"
        assert cs.auto_reshards >= 1


def test_mirror_gate_serializes_concurrent_pack():
    """The deferred-repack gate: while a plan is pending, a concurrent
    pack blocks until the dispatch thread executes the repack."""
    cs = TPUConflictSet(dict_delta_slots=4, **KW)
    mir = cs._mirror
    mir.gate.clear()
    seen = []

    def packer():
        mir.gate.wait(timeout=5)
        seen.append("unblocked")

    t = threading.Thread(target=packer)
    t.start()
    assert not seen
    mir.gate.set()
    t.join(timeout=5)
    assert seen == ["unblocked"]
