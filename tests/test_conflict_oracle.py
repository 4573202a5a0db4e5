"""TPUConflictSet vs brute-force oracle — the ConflictRange-style test.

Randomized batches of transactions with range reads/writes, skewed keys,
stale read versions, write-only and read-only txns; verdicts must match the
O(n²) oracle verdict-for-verdict across many consecutive batches (history
carries over).
"""

import numpy as np
import pytest

from foundationdb_tpu.core.types import KeyRange, TxnConflictInfo, Verdict
from foundationdb_tpu.models.conflict_set import TPUConflictSet
from foundationdb_tpu.sim.oracle import OracleConflictSet


def rand_key(rng, alphabet=4, max_len=6):
    n = int(rng.integers(0, max_len + 1))
    lo = 0 if alphabet > 128 else 97  # wide alphabets span the full byte space
    vals = rng.integers(lo, lo + alphabet, size=n) % 256
    return bytes(vals.astype(np.uint8))


def rand_range(rng, **kw):
    a, b = sorted([rand_key(rng, **kw), rand_key(rng, **kw)])
    if rng.random() < 0.4:  # point "range"
        return KeyRange(a, a + b"\x00")
    return KeyRange(a, b)


def rand_txn(rng, read_version, n_ranges=4, **kw):
    kind = rng.random()
    reads = [] if kind < 0.1 else [
        rand_range(rng, **kw) for _ in range(int(rng.integers(1, n_ranges + 1)))
    ]
    writes = [] if 0.1 <= kind < 0.2 else [
        rand_range(rng, **kw) for _ in range(int(rng.integers(1, n_ranges + 1)))
    ]
    return TxnConflictInfo(read_version=read_version, read_ranges=reads, write_ranges=writes)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_matches_oracle_across_batches(seed):
    rng = np.random.default_rng(seed)
    cs = TPUConflictSet(capacity=512, batch_size=32, max_read_ranges=4,
                        max_write_ranges=4, max_key_bytes=8)
    oracle = OracleConflictSet()
    cv = 1000
    for batch_i in range(12):
        cv += int(rng.integers(1, 50))
        # read versions span recent history, including some stale ones
        txns = [
            rand_txn(rng, read_version=int(rng.integers(max(0, cv - 300), cv)))
            for _ in range(int(rng.integers(1, 40)))
        ]
        oldest = cv - 200  # tight window → exercises TOO_OLD + GC
        got = cs.resolve(txns, cv, oldest_version=oldest)
        oracle.oldest_version = max(oracle.oldest_version, oldest)
        want = oracle.resolve(txns, cv)
        assert got == want, f"batch {batch_i}: {got} != {want}"
    assert not cs.overflowed


def test_chunked_batches_match_oracle():
    """A batch larger than batch_size splits into chunks at the same cv —
    must still behave as one ordered batch."""
    rng = np.random.default_rng(7)
    cs = TPUConflictSet(capacity=512, batch_size=8, max_read_ranges=4,
                        max_write_ranges=4, max_key_bytes=8)
    oracle = OracleConflictSet()
    cv = 100
    for _ in range(4):
        cv += 10
        txns = [rand_txn(rng, read_version=cv - int(rng.integers(1, 20)))
                for _ in range(30)]  # ~4 chunks
        got = cs.resolve(txns, cv)
        want = oracle.resolve(txns, cv)
        assert got == want


def test_basic_semantics():
    cs = TPUConflictSet(capacity=256, batch_size=16, max_key_bytes=8)
    t = lambda rv, r, w: TxnConflictInfo(rv, r, w)
    pt = lambda k: KeyRange(k, k + b"\x00")

    # Batch 1 at cv=10: both blind writes commit.
    got = cs.resolve([t(5, [], [pt(b"a")]), t(5, [], [pt(b"b")])], 10)
    assert got == [Verdict.COMMITTED, Verdict.COMMITTED]

    # Batch 2 at cv=20: read of "a" at rv=5 (< write@10) conflicts;
    # read at rv=15 (> write@10) commits; read of untouched key commits.
    got = cs.resolve(
        [t(5, [pt(b"a")], []), t(15, [pt(b"a")], []), t(5, [pt(b"z")], [])], 20
    )
    assert got == [Verdict.CONFLICT, Verdict.COMMITTED, Verdict.COMMITTED]

    # Batch 3: intra-batch — txn0 writes "q", txn1 reads "q" (earlier accepted
    # write wins), txn2 reads "q" but txn1's write lost → check ordering.
    got = cs.resolve(
        [
            t(15, [], [pt(b"q")]),
            t(15, [pt(b"q")], [pt(b"r")]),  # conflicts with txn0's write
            t(15, [pt(b"r")], []),  # txn1 rejected → its write not painted
        ],
        30,
    )
    assert got == [Verdict.COMMITTED, Verdict.CONFLICT, Verdict.COMMITTED]


def test_too_old_only_with_reads():
    cs = TPUConflictSet(capacity=256, batch_size=8, max_key_bytes=8)
    pt = lambda k: KeyRange(k, k + b"\x00")
    got = cs.resolve(
        [
            TxnConflictInfo(1, [pt(b"a")], []),  # stale reader → TOO_OLD
            TxnConflictInfo(1, [], [pt(b"b")]),  # stale blind writer → COMMITS
        ],
        commit_version=1000,
        oldest_version=500,
    )
    assert got == [Verdict.TOO_OLD, Verdict.COMMITTED]


def test_more_ranges_than_slots_are_judged_exactly():
    """Four writes on a 2-slot engine take two rows, and every one of them
    is painted as it was sent: nothing is widened. Every verdict is the
    oracle's: a reader of each written key conflicts, and a reader of the
    keys BETWEEN them, which a covering range would have swallowed,
    commits."""
    cs = TPUConflictSet(capacity=256, batch_size=8, max_read_ranges=2,
                        max_write_ranges=2, max_key_bytes=8)
    oracle = OracleConflictSet()
    pt = lambda k: KeyRange(k, k + b"\x00")
    first = [TxnConflictInfo(5, [], [pt(b"a"), pt(b"c"), pt(b"e"), pt(b"g")])]
    assert cs.resolve(first, 10) == oracle.resolve(first, 10)
    readers = (
        [TxnConflictInfo(5, [pt(k)], []) for k in (b"a", b"c", b"e", b"g")]
        + [TxnConflictInfo(5, [pt(k)], []) for k in (b"b", b"d", b"f")]
        + [TxnConflictInfo(15, [pt(b"e")], []),
           # four reads on two slots: only the last one meets a write
           TxnConflictInfo(5, [pt(b"b"), pt(b"d"), pt(b"f"), pt(b"g")], []),
           TxnConflictInfo(5, [pt(b"b"), pt(b"d"), pt(b"f"), pt(b"h")], [])]
    )
    got = cs.resolve(readers, 20)
    assert got == oracle.resolve(readers, 20)
    assert got == [Verdict.CONFLICT] * 4 + [Verdict.COMMITTED] * 4 + [
        Verdict.CONFLICT, Verdict.COMMITTED]


def test_commit_version_must_advance():
    cs = TPUConflictSet(capacity=256, batch_size=8, max_key_bytes=8)
    cs.resolve([], 10)
    with pytest.raises(ValueError):
        cs.resolve([], 10)


def test_wide_range_limits_match_oracle(monkeypatch):
    """R*Q above _OVERLAP_UNROLL_LIMIT switches _overlap_rows to the
    vectorized 4D reduce — verdicts must be identical to the oracle (and
    hence to the unrolled form). The limit is forced low so the fallback
    stays covered now that tpcc-scale 12x8 rides the unrolled form."""
    from foundationdb_tpu.models import conflict_kernel as ck

    import jax

    monkeypatch.setattr(ck, "_OVERLAP_UNROLL_LIMIT", 16)
    # The module-level @jax.jit cache is keyed by shapes only: an earlier
    # same-shape trace would make the patched limit a silent no-op (and
    # our limit=16 trace would poison later tests) — clear both ways.
    jax.clear_caches()
    try:
        assert 12 * 8 > ck._OVERLAP_UNROLL_LIMIT  # the fallback is hit
        rng = np.random.default_rng(11)
        cs = TPUConflictSet(capacity=512, batch_size=16, max_read_ranges=12,
                            max_write_ranges=8, max_key_bytes=8)
        oracle = OracleConflictSet()
        cv = 500
        for batch_i in range(6):
            cv += int(rng.integers(1, 30))
            txns = [
                rand_txn(rng,
                         read_version=int(rng.integers(max(0, cv - 100), cv)),
                         n_ranges=10)
                for _ in range(int(rng.integers(1, 16)))
            ]
            got = cs.resolve(txns, cv)
            want = oracle.resolve(txns, cv)
            assert got == want, f"batch {batch_i}: {got} != {want}"
    finally:
        jax.clear_caches()  # drop the limit=16 traces


@pytest.mark.parametrize("seed", [7, 8])
def test_multiblock_acceptance_matches_oracle(seed):
    """batch_size > _ACCEPT_BLOCK so the production block-scan acceptance
    runs with several blocks (cross-block matvec + dynamic_slice offsets
    are live, not the degenerate nblk=1 case)."""
    from foundationdb_tpu.models import conflict_kernel as ck

    assert ck._ACCEPT_BLOCK < 1024
    rng = np.random.default_rng(seed)
    cs = TPUConflictSet(capacity=4096, batch_size=1024, max_read_ranges=2,
                        max_write_ranges=2, max_key_bytes=8)
    oracle = OracleConflictSet()
    cv = 1000
    for batch_i in range(3):
        cv += int(rng.integers(1, 50))
        # One full 1024-txn batch on a small hot keyspace: dense
        # intra-batch conflicts across block boundaries.
        txns = [
            rand_txn(rng, read_version=int(rng.integers(max(0, cv - 100), cv)),
                     n_ranges=2, alphabet=3, max_len=2)
            for _ in range(1024)
        ]
        got = cs.resolve(txns, cv)
        want = oracle.resolve(txns, cv)
        assert got == want, f"batch {batch_i}: first diff at " \
            f"{next(i for i, (g, w) in enumerate(zip(got, want)) if g != w)}"


def test_block_accept_variants_agree():
    """_wave_accept ≡ _block_accept ≡ _block_accept_fused on random rank
    intervals spanning many blocks."""
    import jax.numpy as jnp

    from foundationdb_tpu.models import conflict_kernel as ck

    rng = np.random.default_rng(11)
    b, r, q, space = 2048, 2, 1, 64  # 4 blocks of 512, hot rank space
    rb = rng.integers(0, space, size=(b, r)).astype(np.int32)
    re_ = rb + rng.integers(1, 4, size=(b, r)).astype(np.int32)
    wb = rng.integers(0, space, size=(b, q)).astype(np.int32)
    we = wb + rng.integers(1, 4, size=(b, q)).astype(np.int32)
    read_live = rng.random((b, r)) < 0.9
    write_live = rng.random((b, q)) < 0.6
    base = rng.random((b,)) < 0.95

    m = np.asarray(ck._overlap_rows(
        jnp.asarray(rb), jnp.asarray(re_), jnp.asarray(read_live),
        jnp.asarray(wb), jnp.asarray(we), jnp.asarray(write_live)))
    wave = np.asarray(ck._wave_accept(jnp.asarray(base), jnp.asarray(m)))
    blk = np.asarray(ck._block_accept(jnp.asarray(base), jnp.asarray(m)))
    fused = np.asarray(ck._block_accept_fused(
        jnp.asarray(base), jnp.asarray(rb), jnp.asarray(re_),
        jnp.asarray(read_live), jnp.asarray(wb), jnp.asarray(we),
        jnp.asarray(write_live)))

    # The reference acceptance order as a fixed B-step sequential loop
    # (what the kernel's `seq` arm ran on the device until ROADMAP C1).
    acc = np.zeros(b, bool)
    for i in range(b):
        if not base[i]:
            continue
        acc[i] = not (m[i, :i] & acc[:i]).any()
    assert (wave == acc).all()
    assert (blk == acc).all()
    assert (fused == acc).all()
