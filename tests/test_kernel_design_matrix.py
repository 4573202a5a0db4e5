"""Oracle parity of the one kernel design under its import-once env flags.

The kernel holds one design (ROADMAP C1); what the environment still
selects are the engine DEFAULTS of two features, FDB_TPU_WAVE_COMMIT and
FDB_TPU_SPEC_RESOLVE, read ONCE at import (flipping mid-process would
split jit caches), so each is exercised in a fresh subprocess that asserts
inside the child: the randomized multi-batch oracle-parity workload (with
the loser-range report where the design has one), the mesh against one
chip and the oracle, the speculation ring against serial and the oracle.
The defaults themselves run in-process in the rest of the suite.
"""

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
# with — a false pass here would mean the row never left the defaults.
wave = os.environ.get("FDB_TPU_WAVE_COMMIT", "0") == "1"
assert ck._WAVE_COMMIT == wave

rng = np.random.default_rng(29)
cs = TPUConflictSet(capacity=512, batch_size=32, max_read_ranges=4,
                    max_write_ranges=4, max_key_bytes=8)
oracle = OracleConflictSet(wave_commit=wave)
cv = 1000
for batch_i in range(6):
    cv += int(rng.integers(1, 40))
    # Every fourth transaction has up to nine ranges of a kind on four
    # slots (continuation rows), judged exactly. Wave
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

assert ck._SPEC_RESOLVE == (
    os.environ.get("FDB_TPU_SPEC_RESOLVE", "0") == "1")
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


def _run_child(child: str, flags: dict, ok: str) -> None:
    """`child` in a fresh interpreter with exactly `flags` of the kernel's
    import-once environment set; it prints `ok` last when every assertion
    in it held."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for k in ["FDB_TPU_WAVE_COMMIT", "FDB_TPU_SPEC_RESOLVE",
              "FDB_TPU_DICT_HOT_CAPACITY", "MESH_RESHARD"]:
        env.pop(k, None)
    env.update(flags)
    r = subprocess.run(
        [sys.executable, "-c", child], env=env, capture_output=True,
        text=True, timeout=600, cwd=_REPO,
    )
    assert r.returncode == 0, f"{flags}: {r.stderr[-2000:]}"
    assert r.stdout.strip().splitlines()[-1] == ok


def _ids(prefix=""):
    return lambda f: prefix + ",".join(
        f"{k.replace('FDB_TPU_', '')}={v}" for k, v in f.items())


# Each child asserts the import-once snapshot, 3-way verdict parity
# (speculative x serial x oracle), and the all-windows-mis-speculate
# adversarial stream against the depth-1 revocation-aware baseline.
@pytest.mark.parametrize("flags", [
    {"FDB_TPU_SPEC_RESOLVE": "1"},
    {"FDB_TPU_SPEC_RESOLVE": "1", "FDB_TPU_WAVE_COMMIT": "1"},
], ids=_ids())
def test_spec_resolve_design_rows(flags):
    _run_child(_SPEC_CHILD, flags, "SPEC-MATRIX-OK")


# WAVE_COMMIT=1 x n_resolvers in {2, 4}, 3-way parity (mesh x single x
# oracle incl. wave levels), plus the auto-reshard-mid-stream
# schedule-parity row.
@pytest.mark.parametrize("flags", [
    {"FDB_TPU_WAVE_COMMIT": "1", "MESH_SHARDS": "2"},
    {"FDB_TPU_WAVE_COMMIT": "1", "MESH_SHARDS": "4"},
    {"FDB_TPU_WAVE_COMMIT": "1", "MESH_SHARDS": "2", "MESH_RESHARD": "1"},
], ids=_ids())
def test_mesh_wave_design_rows(flags):
    _run_child(_MESH_CHILD, flags, "MESH-MATRIX-OK")


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
assert ck._WAVE_COMMIT == wave and ck._SPEC_RESOLVE == spec

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


# The tiered dictionary (a per-engine knob, not an import-once kernel
# flag) crossed with the import-once defaults it must stay invisible to —
# wave commit's level schedule and speculative resolve's snapshot/repair
# ring. Each child runs the shifting-hotspot regime and asserts parity
# PLUS the tier economics (demotions > 0, zero hot-path full repacks).
# Subprocess rows are ~12s each (fresh JAX import + compile); these ride
# the slow tier, tier-1 keeps the in-process tiered gates
# (tests/test_tiered_dict.py).
@pytest.mark.slow
@pytest.mark.parametrize("flags", [
    {"FDB_TPU_WAVE_COMMIT": "1"},
    {"FDB_TPU_SPEC_RESOLVE": "1"},
], ids=_ids("TIERED,"))
def test_tiered_design_rows(flags):
    _run_child(_TIERED_CHILD, flags, "TIERED-MATRIX-OK")


# The randomized oracle-parity workload on an engine whose wave default
# came from the environment (the defaults run in-process everywhere else).
@pytest.mark.parametrize("flags", [{"FDB_TPU_WAVE_COMMIT": "1"}], ids=_ids())
def test_design_flag_parity(flags):
    _run_child(_CHILD, flags, "MATRIX-OK")
