"""Two-tier HBM/host dictionary (ISSUE 18): rank-stable spill parity.

The tiered engine (FDB_TPU_DICT_HOT_CAPACITY / dict_hot_capacity=) keeps
a bounded HBM hot tier and demotes cold keys to the host mirror's id
space instead of full-repacking at the capacity cliff. Every test here
is a parity test first — the tier must be INVISIBLE in verdicts — and an
economics assertion second (demotions happen, promotions happen on
reappearance, and the hot path never full-repacks in the intended
regime).

Workload shape matters: demotion victims must leave the MVCC window
(last_used < oldest_version) and the device-live history before they are
safely evictable, so these tests drive a SHIFTING hotspot (keys go cold
on a schedule) rather than the stationary Zipf most suites use. The
stationary/uniform stream is kept too — it is the thrash regime where
demotion cannot free room and the engine must fall back to the honest
full repack rather than evict a live rank.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from foundationdb_tpu.core.types import KeyRange, TxnConflictInfo
from foundationdb_tpu.models.conflict_set import (
    TPUConflictSet,
    encode_resolve_batch,
)
from foundationdb_tpu.sim.oracle import OracleConflictSet
from tests.test_conflict_oracle import rand_txn

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KW = dict(capacity=512, batch_size=32, max_read_ranges=4,
          max_write_ranges=4, max_key_bytes=8)
TIER = dict(dict_hot_capacity=384, dict_delta_slots=128)


def _key(i: int) -> bytes:
    return b"k%05d" % i


def _hot_txn(rng, center: int, rv: int, spread: int = 40) -> TxnConflictInfo:
    ks = [_key(center + int(rng.integers(0, spread))) for _ in range(3)]
    return TxnConflictInfo(
        read_version=rv,
        read_ranges=[KeyRange(k, k + b"\x00") for k in ks[:2]],
        write_ranges=[KeyRange(ks[2], ks[2] + b"\x00")],
    )


def _hotspot_steps(n_steps: int = 42, revisit_at: int = 32, seed: int = 17):
    """(txns, cv, oldest) per step: the hotspot walks 150 keys every 5
    steps, then returns to the FIRST hotspot — whose keys are long-cold
    by then — so eviction-then-reappearance is exercised, not assumed."""
    rng = np.random.default_rng(seed)
    cv = 1000
    for step in range(n_steps):
        cv += 10
        center = 0 if step >= revisit_at else (step // 5) * 150
        txns = [_hot_txn(rng, center, max(0, cv - 60)) for _ in range(12)]
        yield txns, cv, cv - 100


def test_shifting_hotspot_parity_no_repack():
    """3-way parity (tiered x untiered x CPU oracle) on the tier's
    intended regime, with the headline economics: keys demote as the
    hotspot moves on, promote when it returns, and the hot path never
    pays a full repack."""
    cs_t = TPUConflictSet(**TIER, **KW)
    cs_u = TPUConflictSet(**KW)
    oracle = OracleConflictSet()
    assert cs_t.tiered and not cs_u.tiered
    for i, (txns, cv, oldest) in enumerate(_hotspot_steps()):
        got = cs_t.resolve(txns, cv, oldest_version=oldest)
        want_u = cs_u.resolve(txns, cv, oldest_version=oldest)
        oracle.oldest_version = max(oracle.oldest_version, oldest)
        want = oracle.resolve(txns, cv)
        assert got == want_u == want, f"step {i}: {got} {want_u} {want}"
    st = cs_t.dict_stats
    assert st["tiered"] and st["full_repacks"] == 0, st
    assert st["demotions"] > 0, st
    assert st["promotions"] > 0, st  # reappearance re-entered via delta
    assert st["cold_tier_keys"] > 0, st
    # The cold tier is exactly the net spill (nothing forgotten).
    assert st["cold_tier_keys"] == st["demotions"] - st["promotions"], st
    # Hot tier stayed bounded while the touched keyspace exceeded it.
    assert st["resident_keys"] <= 384 < st["resident_keys"] \
        + st["cold_tier_keys"]
    assert not cs_t.overflowed


def test_uniform_thrash_regime_parity():
    """Stationary random stream where most hot ranks stay device-live:
    demotion cannot free room, so the engine must escalate to the honest
    full repack — and verdicts must STILL match the untiered engine and
    the oracle byte for byte."""
    rng = np.random.default_rng(29)
    cs_t = TPUConflictSet(dict_hot_capacity=320, dict_delta_slots=192, **KW)
    cs_u = TPUConflictSet(**KW)
    oracle = OracleConflictSet()
    cv = 1000
    for batch_i in range(12):
        cv += int(rng.integers(1, 40))
        txns = [
            rand_txn(rng, read_version=int(rng.integers(max(0, cv - 200), cv)))
            for _ in range(int(rng.integers(8, 32)))
        ]
        oldest = cv - 150
        got = cs_t.resolve(txns, cv, oldest_version=oldest)
        want_u = cs_u.resolve(txns, cv, oldest_version=oldest)
        oracle.oldest_version = max(oracle.oldest_version, oldest)
        want = oracle.resolve(txns, cv)
        assert got == want_u == want, f"batch {batch_i}"
    assert not cs_t.overflowed


@pytest.mark.slow  # ~10s: threaded runner + its own jit shapes
def test_deferred_demotion_through_runner():
    """Demotion arriving while windows are in flight must DEFER like a
    _RepackPlan — gate held, executed on the dispatch thread once
    liveness is exact — and the threaded pipelined runner's verdicts
    must match the serial untiered path exactly."""
    from foundationdb_tpu.sched.packing import PipelinedWindowRunner

    rng = np.random.default_rng(5)
    batch = 16
    kw = dict(capacity=1 << 10, batch_size=batch, max_read_ranges=2,
              max_write_ranges=2, max_key_bytes=12, window_versions=100)

    def txn(center, rv):
        ks = [b"w%06d" % (center + int(rng.integers(0, 40)))
              for _ in range(3)]
        return TxnConflictInfo(
            read_version=rv,
            read_ranges=[KeyRange(k, k + b"\x00") for k in ks[:2]],
            write_ranges=[KeyRange(ks[2], ks[2] + b"\x00")],
        )

    wires, cvs_all, cv, bidx = [], [], 0, 0
    for _ in range(24):
        wire, cvs = b"", []
        for _ in range(2):
            cv += 10
            txns = [txn((bidx // 10) * 300, max(0, cv - 60))
                    for _ in range(batch)]
            wire += encode_resolve_batch(txns)
            cvs.append(cv)
            bidx += 1
        wires.append(wire)
        cvs_all.append(cvs)

    cs_t = TPUConflictSet(dict_hot_capacity=384, dict_delta_slots=128, **kw)
    runner = PipelinedWindowRunner(cs_t, threaded=True)
    cs_u = TPUConflictSet(**kw)
    got_u = []
    for wire, cvs in zip(wires, cvs_all):
        runner.submit(wire, cvs, batch)
        got_u.append(np.asarray(cs_u.resolve_wire_window_async(
            wire, cvs, batch)()))
    got_t = [np.asarray(runner.collect_next()) for _ in wires]
    runner.close()
    assert np.array_equal(
        np.concatenate([g.reshape(-1) for g in got_t]),
        np.concatenate([g.reshape(-1) for g in got_u]),
    )
    st = cs_t.dict_stats
    assert st["demotion_stalls"] > 0, st  # the deferral actually happened
    assert st["demotions"] > 0 and st["full_repacks"] == 0, st


def test_demote_excludes_pinned_and_live_window():
    """_demote_now's victim policy, unit-level: pinned keys and keys
    still inside the MVCC window never demote; long-cold unpinned keys
    do."""
    cs = TPUConflictSet(**TIER, **KW)
    rng = np.random.default_rng(11)
    cv = 1000
    for step in range(4):
        cv += 10
        txns = [_hot_txn(rng, step * 200, cv - 5) for _ in range(12)]
        cs.resolve(txns, cv, oldest_version=cv - 100)
    mir = cs._mirror
    # Everything is inside the MVCC window: nothing is safely evictable.
    assert cs._demote_now(0) == 0

    # Age every key out of the window and past the device-live history,
    # then pin two: only the pinned pair may survive a full sweep.
    cs.advance(cv + 500, oldest_version=cv + 400)
    mir.pinned[:2] = True
    pinned_ids = mir.id_at[:2].copy()
    n0 = mir.n
    demoted = cs._demote_now(0)
    assert demoted > 0
    assert mir.n == n0 - demoted
    # Pinned keys stayed hot; their ranks moved but ids are stable.
    assert mir.hot_by_id[pinned_ids].all()
    assert int(mir.pinned[:mir.n].sum()) == 2
    assert cs.dict_stats["cold_tier_keys"] >= demoted


@pytest.mark.slow  # ~11s: wire-window + spec-ring jit shapes; the
# TIERED,SPEC_RESOLVE design-matrix row gates this combination too
def test_spec_engine_tiered_parity():
    """Speculative resolve over the tiered engine: _DemotePlan forces
    reconcile-then-demote (snapshots hold pre-evict ranks), and verdicts
    match the serial untiered engine."""
    batch = 16
    kw = dict(capacity=1 << 10, batch_size=batch, max_read_ranges=2,
              max_write_ranges=2, max_key_bytes=12, window_versions=100)
    rng = np.random.default_rng(7)

    def txn(center, rv):
        ks = [b"s%06d" % (center + int(rng.integers(0, 40)))
              for _ in range(3)]
        return TxnConflictInfo(
            read_version=rv,
            read_ranges=[KeyRange(k, k + b"\x00") for k in ks[:2]],
            write_ranges=[KeyRange(ks[2], ks[2] + b"\x00")],
        )

    wires, cvs_all, cv, bidx = [], [], 0, 0
    for _ in range(20):
        wire, cvs = b"", []
        for _ in range(2):
            cv += 10
            wire += encode_resolve_batch(
                [txn((bidx // 10) * 300, max(0, cv - 60))
                 for _ in range(batch)])
            cvs.append(cv)
            bidx += 1
        wires.append(wire)
        cvs_all.append(cvs)

    cs_s = TPUConflictSet(dict_hot_capacity=384, dict_delta_slots=128,
                          spec_resolve=True, spec_depth=2, **kw)
    cs_u = TPUConflictSet(**kw)
    got_s, got_u = [], []
    for wire, cvs in zip(wires, cvs_all):
        got_s.append(np.asarray(cs_s.resolve_wire_window_async(
            wire, cvs, batch)()))
        got_u.append(np.asarray(cs_u.resolve_wire_window_async(
            wire, cvs, batch)()))
    assert np.array_equal(
        np.concatenate([g.reshape(-1) for g in got_s]),
        np.concatenate([g.reshape(-1) for g in got_u]),
    )
    st = cs_s.dict_stats
    assert st["demotions"] > 0 and st["full_repacks"] == 0, st


_MESH_TIERED_CHILD = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4"
)
from foundationdb_tpu.utils import enable_compilation_cache
enable_compilation_cache()
import numpy as np
from foundationdb_tpu.core.types import KeyRange, TxnConflictInfo
from foundationdb_tpu.models.conflict_set import TPUConflictSet
from foundationdb_tpu.parallel.sharded_resolver import (
    ShardedConflictSet, density_splits,
)

KW = dict(capacity=512, batch_size=32, max_read_ranges=4,
          max_write_ranges=4, max_key_bytes=8)
rng = np.random.default_rng(17)


def key(i):
    return b"k%05d" % i


def txn(center, rv):
    ks = [key(center + int(rng.integers(0, 40))) for _ in range(3)]
    return TxnConflictInfo(
        read_version=rv,
        read_ranges=[KeyRange(k, k + b"\x00") for k in ks[:2]],
        write_ranges=[KeyRange(ks[2], ks[2] + b"\x00")],
    )


mesh = ShardedConflictSet(n_shards=2, auto_reshard=False,
                          dict_hot_capacity=384, dict_delta_slots=128, **KW)
single = TPUConflictSet(**KW)
assert mesh.tiered and not single.tiered
cv, touched = 1000, []
for step in range(55):
    cv += 10
    center = 0 if step >= 40 else (step // 5) * 150
    txns = [txn(center, max(0, cv - 60)) for _ in range(12)]
    touched.extend(r.begin for t in txns for r in t.write_ranges)
    oldest = cv - 100
    if step == 24:
        # Scoped reshard mid-stream: the tiered reset must preserve cold
        # ids (demote-don't-forget) while the bounds move.
        mesh.reshard(density_splits(2, touched[-256:]))
    got = mesh.resolve(txns, cv, oldest_version=oldest)
    want = single.resolve(txns, cv, oldest_version=oldest)
    assert got == want, f"step {step}: {got} != {want}"
st = mesh.dict_stats
assert st["tiered"] and st["demotions"] > 0, st
assert st["full_repacks"] == 0, st
assert st["cold_tier_keys"] > 0, st
assert not mesh.overflowed
print("MESH-TIERED-OK")
"""


@pytest.mark.slow  # ~10s subprocess: fresh JAX import + mesh compile
def test_mesh_demotion_replication_and_reshard():
    """Sharded engine: the demotion delta replicates to every device
    (shift derives from the replicated dictionary), and a scoped reshard
    mid-stream preserves cold-tier ids — verdict parity with the
    single-chip untiered engine throughout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for k in ["FDB_TPU_DICT_HOT_CAPACITY", "FDB_TPU_WAVE_COMMIT",
              "FDB_TPU_SPEC_RESOLVE"]:
        env.pop(k, None)
    r = subprocess.run(
        [sys.executable, "-c", _MESH_TIERED_CHILD], env=env,
        capture_output=True, text=True, timeout=600, cwd=_REPO,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1] == "MESH-TIERED-OK"


# -- metrics plane -------------------------------------------------------------


def test_tier_counters_in_resolver_metrics_registry():
    from foundationdb_tpu.obs.registry import DOCUMENTED_COUNTERS

    for k in ["resolver.engine.demotions", "resolver.engine.promotions",
              "resolver.engine.cold_tier_keys",
              "resolver.engine.dict_hot_occupancy",
              "resolver.engine.demotion_bytes_per_dispatch"]:
        assert k in DOCUMENTED_COUNTERS, k


def _thrash_ring(promote: bool):
    records, dem, pro = [], 0, 0
    for t in range(20):
        dem += 40
        pro += 36 if promote else 1
        records.append({"kind": "snapshot", "t": float(t), "seq": t,
                        "metrics": {
                            "resolver.resolver0.demotions": dem,
                            "resolver.resolver0.promotions": pro,
                        }})
    return records


def test_doctor_dict_thrash_detector():
    from foundationdb_tpu.obs.doctor import dict_thrash

    hot = dict_thrash(_thrash_ring(promote=True), 0.0, 19.0)
    assert hot is not None and hot["thrash"], hot
    assert hot["promotion_rate"] > 0.8
    cold = dict_thrash(_thrash_ring(promote=False), 0.0, 19.0)
    assert cold is not None and not cold["thrash"], cold


def test_doctor_dict_thrash_honest_none_when_untiered():
    from foundationdb_tpu.obs.doctor import dict_thrash

    ring = [{**r, "metrics": {}} for r in _thrash_ring(True)]
    assert dict_thrash(ring, 0.0, 19.0) is None
