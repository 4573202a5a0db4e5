"""Oracle parity across the kernel-design env-flag matrix.

The four knobs (FDB_TPU_RMQ, FDB_TPU_HISTORY, FDB_TPU_ACCEPT,
FDB_TPU_PACKED) are read ONCE at import (flipping mid-process would split
jit caches), so every combination must be exercised in a fresh
subprocess. Each child runs the randomized multi-batch oracle-parity
workload PLUS the loser-range report check, asserting inside the child.

Tier-1 runs the defaults in-process (the rest of the suite) plus each
non-default flag flipped alone and the all-flipped corner here; the full
2x2x2x2 product is @slow.
"""

import itertools
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
from foundationdb_tpu.utils import enable_compilation_cache
enable_compilation_cache()
import numpy as np
from foundationdb_tpu.core.types import KeyRange, Verdict
from foundationdb_tpu.models import conflict_kernel as ck
from foundationdb_tpu.models.conflict_set import TPUConflictSet
from foundationdb_tpu.sim.oracle import OracleConflictSet
from tests.test_conflict_oracle import rand_txn

# The import-once snapshot must reflect the env this child was spawned
# with — a false pass here would mean the matrix never left the defaults.
assert ck._RMQ_DESIGN == os.environ.get("FDB_TPU_RMQ", "sparse")
assert ck._HIST_DESIGN == os.environ.get("FDB_TPU_HISTORY", "window")
assert ck._ACCEPT_DESIGN == os.environ.get("FDB_TPU_ACCEPT", "wave")
assert ck._PACKED == (os.environ.get("FDB_TPU_PACKED", "1") != "0")
# Resident is inert without the packed kernel (rank space needs it).
assert ck._RESIDENT == (
    os.environ.get("FDB_TPU_RESIDENT", "1") != "0" and ck._PACKED
)
wave = os.environ.get("FDB_TPU_WAVE_COMMIT", "0") == "1"

rng = np.random.default_rng(29)
cs = TPUConflictSet(capacity=512, batch_size=32, max_read_ranges=4,
                    max_write_ranges=4, max_key_bytes=8)
oracle = OracleConflictSet(wave_commit=wave)
cv = 1000
for batch_i in range(6):
    cv += int(rng.integers(1, 40))
    # Every fourth transaction has up to nine ranges of a kind on four
    # slots (continuation rows): each design judges it exactly. Wave
    # engines level one dispatch at a time, the wave oracle the whole
    # list, so there the list is cut to what one dispatch holds.
    txns = [
        rand_txn(rng, read_version=int(rng.integers(max(0, cv - 200), cv)),
                 n_ranges=9 if i % 4 == 3 else 4)
        for i in range(int(rng.integers(8, 32)))
    ]
    if wave:
        txns = txns[: cs._chunks(txns)[0][1]]
    if not wave:
        for t in txns[::3]:  # loser-range report path rides along
            object.__setattr__(t, "report_conflicting_keys", True)
    oldest = cv - 150
    got = cs.resolve(txns, cv, oldest_version=oldest)
    oracle.oldest_version = max(oracle.oldest_version, oldest)
    want = oracle.resolve(txns, cv)
    assert got == want, f"batch {batch_i}: {got} != {want}"
    if wave:
        assert cs.last_wave == oracle.last_wave, f"batch {batch_i} levels"
        continue
    # Loser-range completeness: every oracle conflicting range must be
    # covered by the kernel's report.
    for i, ranges in oracle.last_conflicting.items():
        kernel = cs.last_conflicting.get(i)
        assert kernel is not None, f"batch {batch_i} txn {i}: no report"
        for r in ranges:
            assert any(k.begin <= r.begin and r.end <= k.end for k in kernel), \
                f"batch {batch_i} txn {i}: {r} not covered by {kernel}"
assert not cs.overflowed
print("MATRIX-OK")
"""

_MESH_CHILD = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4"
)
from foundationdb_tpu.utils import enable_compilation_cache
enable_compilation_cache()
import numpy as np
from foundationdb_tpu.models import conflict_kernel as ck
from foundationdb_tpu.models.conflict_set import TPUConflictSet
from foundationdb_tpu.parallel.sharded_resolver import ShardedConflictSet
from foundationdb_tpu.sim.oracle import OracleConflictSet
from tests.test_conflict_oracle import rand_txn

assert ck._PACKED == (os.environ.get("FDB_TPU_PACKED", "1") != "0")
assert ck._RESIDENT == (
    os.environ.get("FDB_TPU_RESIDENT", "1") != "0" and ck._PACKED
)
assert ck._WAVE_COMMIT == (
    os.environ.get("FDB_TPU_WAVE_COMMIT", "0") == "1"
)
n_shards = int(os.environ["MESH_SHARDS"])
reshard = os.environ.get("MESH_RESHARD") == "1"

rng = np.random.default_rng(31 + n_shards)
kw = dict(capacity=512, batch_size=16, max_read_ranges=4,
          max_write_ranges=4, max_key_bytes=8)
mesh = ShardedConflictSet(
    n_shards=n_shards, auto_reshard=reshard,
    **({"reshard_interval": 2, "reshard_skew": 1.0} if reshard else {}),
    **kw)
single = TPUConflictSet(**kw)
oracle = OracleConflictSet(wave_commit=ck._WAVE_COMMIT)
cv = 1000
for batch_i in range(8):
    cv += int(rng.integers(1, 40))
    txns = [
        rand_txn(rng, read_version=int(rng.integers(max(0, cv - 200), cv)),
                 alphabet=256, max_len=5)
        for _ in range(int(rng.integers(2, 17)))
    ]
    oldest = cv - 150
    got = mesh.resolve(txns, cv, oldest_version=oldest)
    want = single.resolve(txns, cv, oldest_version=oldest)
    oracle.oldest_version = max(oracle.oldest_version, oldest)
    worac = oracle.resolve(txns, cv)
    assert got == want == worac, f"batch {batch_i}: {got} {want} {worac}"
    if ck._WAVE_COMMIT:
        assert mesh.last_wave == single.last_wave == oracle.last_wave, (
            f"batch {batch_i} wave levels"
        )
if ck._WAVE_COMMIT:
    st = mesh.exchange_stats()
    assert st["wave_batches"] == 8 and st["tiles_occupied"] > 0, st
assert not mesh.overflowed
print("MESH-MATRIX-OK")
"""


_SPEC_CHILD = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
from foundationdb_tpu.utils import enable_compilation_cache
enable_compilation_cache()
import numpy as np
from foundationdb_tpu.models import conflict_kernel as ck
from foundationdb_tpu.models.conflict_set import (
    TPUConflictSet, encode_resolve_batch,
)
from foundationdb_tpu.sim.oracle import OracleConflictSet
from tests.test_conflict_oracle import rand_txn

# Inert gating: speculation rides the packed kernel exactly like RESIDENT
# (the reconcile ring snapshots/paints rank-space batches).
assert ck._SPEC_RESOLVE == (
    os.environ.get("FDB_TPU_SPEC_RESOLVE", "0") == "1" and ck._PACKED
)
wave = os.environ.get("FDB_TPU_WAVE_COMMIT", "0") == "1"
K, COUNT, NWIN = 2, 16, 8


def gen_windows():
    rng = np.random.default_rng(37)
    wins, cv = [], 1000
    for _ in range(NWIN):
        cvs, wtx = [], []
        for _ in range(K):
            cv += 7
            cvs.append(cv)
            wtx.extend(
                rand_txn(rng,
                         read_version=int(rng.integers(max(0, cv - 60), cv)))
                for _ in range(COUNT)
            )
        wins.append((encode_resolve_batch(wtx), cvs, wtx))
    return wins


def run_engine(spec, depth=2, hook=None):
    cs = TPUConflictSet(capacity=1 << 12, batch_size=COUNT,
                        max_read_ranges=4, max_write_ranges=4,
                        max_key_bytes=8, wave_commit=wave,
                        spec_resolve=spec, spec_depth=depth)
    if hook is not None:
        cs.spec_confirm_hook = hook
    colls = []
    for wire, cvs, _ in gen_windows():
        p = cs.pack_wire_window(np.frombuffer(wire, np.uint8), cvs, COUNT)
        colls.append(cs.dispatch_window(p))
    return np.stack([c() for c in colls]), cs


if not ck._SPEC_RESOLVE:
    # PACKED=0 row: the knob must be INERT — engine stays serial and the
    # object-path speculation seam declines the batch.
    cs = TPUConflictSet(capacity=256, batch_size=8, max_read_ranges=4,
                        max_write_ranges=4, max_key_bytes=8)
    assert not cs.spec
    rng = np.random.default_rng(5)
    assert cs.spec_resolve_async([rand_txn(rng, read_version=90)], 100) is None
    print("SPEC-MATRIX-OK")
    raise SystemExit(0)

# 3-way verdict parity: speculative (confirm-all) x serial x oracle.
serial, _ = run_engine(False)
specv, cs = run_engine(True)
m = cs.spec_metrics()
assert np.array_equal(serial, specv), "speculative != serial"
assert m["spec_dispatched"] == NWIN and m["spec_repaired"] == 0, m
oracle = OracleConflictSet(wave_commit=wave)
for w, (wire, cvs, txns) in enumerate(gen_windows()):
    for b in range(K):
        want = oracle.resolve(txns[b * COUNT:(b + 1) * COUNT], cvs[b])
        got = [int(v) for v in specv[w][b][:COUNT]]
        assert got == [int(x) for x in want], f"window {w} batch {b}"

# Adversarial: every window mis-speculates (the hook revokes the first
# accepted txn). Depth 1 reconciles each window before the next
# dispatches — a revocation-aware serial baseline the pipelined depth
# must match exactly: mis-speculated txns resolve exclusively through
# the rollback/repair path, no spurious aborts.
def adversary(seq, verdicts):
    conf = np.ones_like(verdicts, dtype=bool)
    acc = np.argwhere(verdicts == 0)
    if len(acc):
        conf[tuple(acc[0])] = False
    return conf

g, _ = run_engine(True, depth=1, hook=adversary)
s, cs2 = run_engine(True, depth=3, hook=adversary)
m2 = cs2.spec_metrics()
assert np.array_equal(g, s), "pipelined repair != depth-1 ground truth"
assert m2["spec_repaired"] > 0, m2
print("SPEC-MATRIX-OK")
"""


# ISSUE-17 rows: SPEC_RESOLVE=1 x {RESIDENT 0/1, WAVE_COMMIT=1, and the
# PACKED=0 corner where the knob must be inert}. Each child asserts the
# import-once gating, 3-way verdict parity (speculative x serial x
# oracle), and the all-windows-mis-speculate adversarial stream against
# the depth-1 revocation-aware baseline. The RESIDENT=1 and
# WAVE_COMMIT=1 subprocess rows ride the slow tier: both interactions
# are exercised in-process every tier-1 run by test_spec_resolve.py
# (its engines inherit the resident default, and the resolver parity
# test runs wave_commit=True), so tier-1 keeps only the non-resident
# canonical row and the PACKED=0 inertness gate under its time budget.
_SPEC_ROWS = [
    {"FDB_TPU_SPEC_RESOLVE": "1", "FDB_TPU_RESIDENT": "0"},
    pytest.param({"FDB_TPU_SPEC_RESOLVE": "1", "FDB_TPU_RESIDENT": "1"},
                 marks=pytest.mark.slow),
    pytest.param({"FDB_TPU_SPEC_RESOLVE": "1", "FDB_TPU_WAVE_COMMIT": "1"},
                 marks=pytest.mark.slow),
    {"FDB_TPU_SPEC_RESOLVE": "1", "FDB_TPU_PACKED": "0"},
]


@pytest.mark.parametrize(
    "flags", _SPEC_ROWS,
    ids=lambda f: ",".join(f"{k.replace('FDB_TPU_', '')}={v}"
                           for k, v in f.items()),
)
def test_spec_resolve_design_rows(flags):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for k in ["FDB_TPU_SPEC_RESOLVE", "FDB_TPU_RESIDENT", "FDB_TPU_PACKED",
              "FDB_TPU_WAVE_COMMIT", "FDB_TPU_NATIVE_WINDOW_PACK"]:
        env.pop(k, None)
    env.update(flags)
    r = subprocess.run(
        [sys.executable, "-c", _SPEC_CHILD], env=env, capture_output=True,
        text=True, timeout=600, cwd=_REPO,
    )
    assert r.returncode == 0, f"{flags}: {r.stderr[-2000:]}"
    assert r.stdout.strip().splitlines()[-1] == "SPEC-MATRIX-OK"


# ISSUE-13 rows: WAVE_COMMIT=1 x n_resolvers in {2,4} x PACKED=1 x
# RESIDENT in {0,1}, 3-way parity (mesh x single x oracle incl. wave
# levels), plus the auto-reshard-mid-stream schedule-parity row.
# Tier-1 keeps one row per axis value (RESIDENT 0 via the 2-shard row,
# RESIDENT 1 via the 4-shard and reshard rows; shards 2 and 4 both
# present); the remaining cross terms ride the slow tier with the full
# flag matrix so the suite stays under its time budget.
_MESH_ROWS = [
    pytest.param({"FDB_TPU_WAVE_COMMIT": "1", "FDB_TPU_RESIDENT": "1",
                  "MESH_SHARDS": "2"}, marks=pytest.mark.slow),
    {"FDB_TPU_WAVE_COMMIT": "1", "FDB_TPU_RESIDENT": "0",
     "MESH_SHARDS": "2"},
    {"FDB_TPU_WAVE_COMMIT": "1", "FDB_TPU_RESIDENT": "1",
     "MESH_SHARDS": "4"},
    pytest.param({"FDB_TPU_WAVE_COMMIT": "1", "FDB_TPU_RESIDENT": "0",
                  "MESH_SHARDS": "4"}, marks=pytest.mark.slow),
    {"FDB_TPU_WAVE_COMMIT": "1", "FDB_TPU_RESIDENT": "1",
     "MESH_SHARDS": "2", "MESH_RESHARD": "1"},
]


@pytest.mark.parametrize(
    "flags", _MESH_ROWS,
    ids=lambda f: ",".join(f"{k.replace('FDB_TPU_', '')}={v}"
                           for k, v in f.items()),
)
def test_mesh_wave_design_rows(flags):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **flags)
    for k in ["FDB_TPU_WAVE_COMMIT", "FDB_TPU_RESIDENT", "FDB_TPU_PACKED",
              "MESH_RESHARD"]:
        env.pop(k, None)
    env.update(flags)
    r = subprocess.run(
        [sys.executable, "-c", _MESH_CHILD], env=env, capture_output=True,
        text=True, timeout=600, cwd=_REPO,
    )
    assert r.returncode == 0, f"{flags}: {r.stderr[-2000:]}"
    assert r.stdout.strip().splitlines()[-1] == "MESH-MATRIX-OK"


_TIERED_CHILD = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
from foundationdb_tpu.utils import enable_compilation_cache
enable_compilation_cache()
import numpy as np
from foundationdb_tpu.core.types import KeyRange, TxnConflictInfo
from foundationdb_tpu.models import conflict_kernel as ck
from foundationdb_tpu.models.conflict_set import (
    TPUConflictSet, encode_resolve_batch,
)
from foundationdb_tpu.sim.oracle import OracleConflictSet

wave = os.environ.get("FDB_TPU_WAVE_COMMIT", "0") == "1"
spec = os.environ.get("FDB_TPU_SPEC_RESOLVE", "0") == "1"
assert ck._WAVE_COMMIT == wave and ck._SPEC_RESOLVE == (spec and ck._PACKED)

KW = dict(capacity=512, batch_size=16, max_read_ranges=4,
          max_write_ranges=4, max_key_bytes=8, window_versions=100)
TIER = dict(dict_hot_capacity=384, dict_delta_slots=128)
rng = np.random.default_rng(17)


def txn(center, rv):
    ks = [b"k%05d" % (center + int(rng.integers(0, 40))) for _ in range(3)]
    return TxnConflictInfo(
        read_version=rv,
        read_ranges=[KeyRange(k, k + b"\x00") for k in ks[:2]],
        write_ranges=[KeyRange(ks[2], ks[2] + b"\x00")],
    )


if spec:
    # Wire-window speculative path: tiered+spec vs untiered serial. The
    # _DemotePlan handler must reconcile the ring BEFORE evicting (spec
    # snapshots hold pre-evict ranks).
    cs_t = TPUConflictSet(spec_resolve=True, spec_depth=2, **TIER, **KW)
    cs_u = TPUConflictSet(**KW)
    cv, bidx = 0, 0
    for _ in range(20):
        wire, cvs = b"", []
        for _ in range(2):
            cv += 10
            center = 0 if bidx >= 30 else (bidx // 5) * 150
            wire += encode_resolve_batch(
                [txn(center, max(0, cv - 60)) for _ in range(16)])
            cvs.append(cv)
            bidx += 1
        got = np.asarray(cs_t.resolve_wire_window_async(wire, cvs, 16)())
        want = np.asarray(cs_u.resolve_wire_window_async(wire, cvs, 16)())
        assert np.array_equal(got, want)
else:
    cs_t = TPUConflictSet(**TIER, **KW)
    cs_u = TPUConflictSet(**KW)
    oracle = OracleConflictSet(wave_commit=wave)
    cv = 1000
    for step in range(55):
        cv += 10
        center = 0 if step >= 40 else (step // 5) * 150
        txns = [txn(center, max(0, cv - 60)) for _ in range(12)]
        oldest = cv - 100
        got = cs_t.resolve(txns, cv, oldest_version=oldest)
        want_u = cs_u.resolve(txns, cv, oldest_version=oldest)
        oracle.oldest_version = max(oracle.oldest_version, oldest)
        want = oracle.resolve(txns, cv)
        assert got == want_u == want, f"step {step}"
        if wave:
            assert cs_t.last_wave == cs_u.last_wave == oracle.last_wave, (
                f"step {step} wave levels"
            )
st = cs_t.dict_stats
assert st["tiered"] and st["demotions"] > 0, st
assert st["full_repacks"] == 0, st
assert not cs_t.overflowed
print("TIERED-MATRIX-OK")
"""


# ISSUE-18 rows: the tiered dictionary (a per-engine knob, not an
# import-once kernel flag) crossed with the import-once designs it must
# stay invisible to — wave commit's level schedule and speculative
# resolve's snapshot/repair ring. Each child runs the shifting-hotspot
# regime and asserts parity PLUS the tier economics (demotions > 0,
# zero hot-path full repacks).
# Subprocess rows are ~12s each (fresh JAX import + compile), so they
# ride the slow tier like the other heavy matrix variants; tier-1 keeps
# the in-process tiered gates (tests/test_tiered_dict.py).
_TIERED_ROWS = [
    pytest.param({"FDB_TPU_WAVE_COMMIT": "1"}, marks=pytest.mark.slow),
    pytest.param({"FDB_TPU_SPEC_RESOLVE": "1"}, marks=pytest.mark.slow),
]


@pytest.mark.parametrize(
    "flags", _TIERED_ROWS,
    ids=lambda f: "TIERED," + ",".join(
        f"{k.replace('FDB_TPU_', '')}={v}" for k, v in f.items()),
)
def test_tiered_design_rows(flags):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for k in ["FDB_TPU_WAVE_COMMIT", "FDB_TPU_SPEC_RESOLVE",
              "FDB_TPU_RESIDENT", "FDB_TPU_PACKED",
              "FDB_TPU_DICT_HOT_CAPACITY"]:
        env.pop(k, None)
    env.update(flags)
    r = subprocess.run(
        [sys.executable, "-c", _TIERED_CHILD], env=env, capture_output=True,
        text=True, timeout=600, cwd=_REPO,
    )
    assert r.returncode == 0, f"{flags}: {r.stderr[-2000:]}"
    assert r.stdout.strip().splitlines()[-1] == "TIERED-MATRIX-OK"


_FLAGS = {
    "FDB_TPU_RMQ": ("sparse", "blocked"),
    "FDB_TPU_HISTORY": ("window", "batch"),
    "FDB_TPU_ACCEPT": ("wave", "seq"),
    "FDB_TPU_PACKED": ("1", "0"),
    "FDB_TPU_RESIDENT": ("1", "0"),
}


def _run_combo(env_flags: dict) -> None:
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_flags)
    for k in list(_FLAGS) + ["FDB_TPU_WAVE_COMMIT"]:
        env.pop(k, None)
    env.update(env_flags)
    r = subprocess.run(
        [sys.executable, "-c", _CHILD], env=env, capture_output=True,
        text=True, timeout=600, cwd=_REPO,
    )
    assert r.returncode == 0, f"{env_flags}: {r.stderr[-2000:]}"
    assert r.stdout.strip().splitlines()[-1] == "MATRIX-OK"


# Fast tier: each non-default value flipped alone, plus the all-flipped
# corner (defaults themselves are exercised in-process by the whole suite)
# and the RESIDENT cross cases the ISSUE-8 design matrix names:
# RESIDENT×PACKED=0 (must be inert) and RESIDENT×WAVE_COMMIT=1.
_FAST = [
    {"FDB_TPU_PACKED": "0"},
    # RMQ=blocked / ACCEPT=seq / RESIDENT=1+PACKED=0 flipped-alone rows
    # ride the slow tier (their values are still exercised every tier-1
    # run by the all-flipped corner below and the PACKED=0 row); tier-1
    # keeps the rows whose value appears nowhere else.
    pytest.param({"FDB_TPU_RMQ": "blocked"}, marks=pytest.mark.slow),
    {"FDB_TPU_HISTORY": "batch"},
    pytest.param({"FDB_TPU_ACCEPT": "seq"}, marks=pytest.mark.slow),
    {"FDB_TPU_RESIDENT": "0"},
    pytest.param({"FDB_TPU_RESIDENT": "1", "FDB_TPU_PACKED": "0"},
                 marks=pytest.mark.slow),
    {"FDB_TPU_RESIDENT": "1", "FDB_TPU_WAVE_COMMIT": "1"},
    {"FDB_TPU_RMQ": "blocked", "FDB_TPU_HISTORY": "batch",
     "FDB_TPU_ACCEPT": "seq", "FDB_TPU_PACKED": "0",
     "FDB_TPU_RESIDENT": "0"},
]


@pytest.mark.parametrize(
    "flags", _FAST, ids=lambda f: ",".join(f"{k[8:]}={v}" for k, v in f.items())
)
def test_design_flag_parity(flags):
    _run_combo(flags)


_TWO_PHASE_CHILD = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
from foundationdb_tpu.utils import enable_compilation_cache
enable_compilation_cache()
from foundationdb_tpu.models import conflict_kernel as ck
from tests import test_wide_txn_parity as wide

assert ck._HIST_DESIGN == os.environ.get("FDB_TPU_HISTORY", "window")
assert ck._PACKED == (os.environ.get("FDB_TPU_PACKED", "1") != "0")
assert ck._RESIDENT == (
    os.environ.get("FDB_TPU_RESIDENT", "1") != "0" and ck._PACKED
)
wide.test_the_two_phase_wave_exchange_judges_wide_transactions_exactly(
    17, 9, ck._RESIDENT)
print("TWO-PHASE-OK")
"""


# The role-level wave exchange (resolve_edges / resolve_apply) has an entry
# point of its own for each batch format and history design; the two the
# defaults give run in-process (tests/test_wide_txn_parity.py), the other
# three here: wide transactions, clipped to two shards, against the oracle.
@pytest.mark.parametrize("flags", [
    {"FDB_TPU_PACKED": "0"},
    {"FDB_TPU_PACKED": "0", "FDB_TPU_HISTORY": "batch"},
    {"FDB_TPU_RESIDENT": "0", "FDB_TPU_HISTORY": "batch"},
], ids=lambda f: ",".join(f"{k[8:]}={v}" for k, v in f.items()))
def test_two_phase_wave_exchange_of_wide_transactions_by_design(flags):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for k in list(_FLAGS) + ["FDB_TPU_WAVE_COMMIT"]:
        env.pop(k, None)
    env.update(flags)
    r = subprocess.run(
        [sys.executable, "-c", _TWO_PHASE_CHILD], env=env,
        capture_output=True, text=True, timeout=600, cwd=_REPO,
    )
    assert r.returncode == 0, f"{flags}: {r.stderr[-2000:]}"
    assert r.stdout.strip().splitlines()[-1] == "TWO-PHASE-OK"


_FULL = [
    dict(zip(_FLAGS, combo))
    for combo in itertools.product(*_FLAGS.values())
]


@pytest.mark.slow
@pytest.mark.parametrize(
    "flags", _FULL, ids=lambda f: ",".join(f"{k[8:]}={v}" for k, v in f.items())
)
def test_design_flag_parity_full_matrix(flags):
    _run_combo(flags)
