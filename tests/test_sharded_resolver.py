"""Sharded mesh resolver ≡ single-device resolver ≡ oracle (8-dev CPU mesh)."""

import numpy as np
import pytest

from foundationdb_tpu.core.types import KeyRange, TxnConflictInfo, Verdict
from foundationdb_tpu.models.conflict_set import TPUConflictSet
from foundationdb_tpu.parallel.sharded_resolver import ShardedConflictSet
from foundationdb_tpu.sim.oracle import OracleConflictSet
from tests.test_conflict_oracle import rand_txn


def make_sharded(n_shards, **kw):
    kw.setdefault("capacity", 256)
    kw.setdefault("batch_size", 32)
    kw.setdefault("max_read_ranges", 4)
    kw.setdefault("max_write_ranges", 4)
    kw.setdefault("max_key_bytes", 8)
    return ShardedConflictSet(n_shards=n_shards, **kw)


@pytest.mark.parametrize("n_shards", [2, 8])
def test_sharded_matches_oracle(n_shards):
    rng = np.random.default_rng(5)
    cs = make_sharded(n_shards)
    oracle = OracleConflictSet()
    cv = 1000
    for batch_i in range(8):
        cv += int(rng.integers(1, 40))
        # Keys from a wide byte alphabet so ranges straddle shard splits.
        txns = [
            rand_txn(rng, read_version=int(rng.integers(max(0, cv - 250), cv)),
                     alphabet=256, max_len=5)
            for _ in range(int(rng.integers(1, 40)))
        ]
        oldest = cv - 150
        got = cs.resolve(txns, cv, oldest_version=oldest)
        oracle.oldest_version = max(oracle.oldest_version, oldest)
        want = oracle.resolve(txns, cv)
        assert got == want, f"shards={n_shards} batch {batch_i}"
    assert not cs.overflowed


def test_cross_shard_range_reads():
    """A single range spanning every shard must conflict with a write in any
    one shard (the psum AND-of-verdicts path)."""
    cs = make_sharded(8)
    t = TxnConflictInfo
    # Write one key deep inside shard ~5 (first byte 0xb0).
    cs.resolve([t(5, [], [KeyRange(b"\xb0x", b"\xb0x\x00")])], 10)
    got = cs.resolve(
        [
            t(5, [KeyRange(b"", b"\xff\xff")], []),  # spans all shards → hit
            t(15, [KeyRange(b"", b"\xff\xff")], []),  # newer rv → clean
            t(5, [KeyRange(b"\x10", b"\x20")], []),  # different shard → clean
        ],
        20,
    )
    assert got == [Verdict.CONFLICT, Verdict.COMMITTED, Verdict.COMMITTED]


def test_sharded_equals_single_device():
    """Same workload through the mesh engine and the single-chip engine."""
    rng = np.random.default_rng(17)
    a = make_sharded(4)
    b = TPUConflictSet(capacity=1024, batch_size=32, max_read_ranges=4,
                       max_write_ranges=4, max_key_bytes=8)
    cv = 50
    for _ in range(6):
        cv += int(rng.integers(1, 30))
        txns = [
            rand_txn(rng, read_version=int(rng.integers(max(0, cv - 100), cv)),
                     alphabet=256, max_len=4)
            for _ in range(24)
        ]
        assert a.resolve(txns, cv) == b.resolve(txns, cv)


def test_windowed_resolve_parity():
    """resolve_wire_window (k batches per dispatch via lax.scan) must agree
    with per-batch resolve_wire on BOTH engines — the window path is the
    bench's production dispatch mode."""
    from foundationdb_tpu.models.conflict_set import encode_resolve_batch

    rng = np.random.default_rng(23)
    kw = dict(capacity=512, batch_size=16, max_read_ranges=4,
              max_write_ranges=4, max_key_bytes=8)
    window = ShardedConflictSet(n_shards=4, **kw)
    seq_single = TPUConflictSet(**kw)
    seq_sharded = make_sharded(4, capacity=512, batch_size=16)

    k, count = 4, 16
    cvs = [10, 21, 35, 36]
    batches = [
        [rand_txn(rng, read_version=int(rng.integers(0, cv)), alphabet=64,
                  max_len=3) for _ in range(count)]
        for cv in cvs
    ]
    wire = b"".join(encode_resolve_batch(txns) for txns in batches)
    got = window.resolve_wire_window(wire, cvs, count)
    assert got.shape == (k, count)

    for i, (cv, txns) in enumerate(zip(cvs, batches)):
        expect_single = seq_single.resolve(txns, cv)
        expect_sharded = seq_sharded.resolve(txns, cv)
        assert [int(v) for v in got[i]] == [int(v) for v in expect_single]
        assert expect_single == expect_sharded


class TestDensitySplits:
    def test_density_splits_quantiles_and_fallbacks(self):
        from foundationdb_tpu.parallel.sharded_resolver import (
            density_splits, interior_uniform,
        )

        # Zipf-ish sample concentrated low in the keyspace: quantile splits
        # must land inside the hot region, not at uniform prefixes.
        rng = np.random.default_rng(3)
        ids = np.minimum(rng.geometric(0.01, 4096), 4000)
        sample = [int(i).to_bytes(8, "big") for i in ids]
        splits = density_splits(4, sample)
        assert len(splits) == 3 and splits == sorted(splits)
        assert all(s < (4001).to_bytes(8, "big") for s in splits)
        # Degenerate samples fall back to uniform prefixes.
        assert density_splits(4, [b"k"] * 100) == interior_uniform(4)
        assert density_splits(4, []) == interior_uniform(4)

    def test_density_splits_balance_occupancy(self):
        """Under a skewed key stream, quantile splits keep per-shard
        history occupancy within ~2x; uniform splits leave it pathological
        (VERDICT r2 weak-4's done-criterion)."""
        from foundationdb_tpu.parallel.sharded_resolver import density_splits

        rng = np.random.default_rng(11)
        n_txns, cv = 512, 0
        ids = np.minimum(rng.zipf(1.3, (n_txns, 2)) - 1, 2000)
        keyss = [
            [int(i).to_bytes(8, "big") for i in row] for row in ids
        ]

        def run(splits, reshard_every=0):
            # auto_reshard off: this test A/Bs split POLICIES explicitly —
            # the engine's (new) default auto-resharding would fix the
            # uniform baseline mid-run and erase the comparison.
            cs = ShardedConflictSet(
                n_shards=4, splits=splits, capacity=4096, batch_size=16,
                max_read_ranges=2, max_write_ranges=2, max_key_bytes=12,
                auto_reshard=False,
            )
            v = 0
            seen: list[bytes] = []
            for i in range(0, n_txns, 16):
                v += 1
                batch_keys = keyss[i : i + 16]
                seen += [k for ks in batch_keys for k in ks]
                txns = [
                    TxnConflictInfo(
                        read_version=v - 1,
                        read_ranges=[KeyRange(k, k + b"\x00") for k in ks],
                        write_ranges=[KeyRange(k, k + b"\x00") for k in ks],
                    )
                    for ks in batch_keys
                ]
                cs.resolve(txns, v)
                if reshard_every and v % reshard_every == 0:
                    # The between-windows re-split path: quantiles of ALL
                    # keys observed so far (what DD density feedback gives
                    # the proxy in the runtime analogue).
                    cs.reshard(density_splits(4, seen))
            return cs.shard_occupancy()

        sample = [k for ks in keyss[:128] for k in ks]
        occ_uniform = run(None)
        # Uniform first-byte splits put EVERY 8-byte int key in shard 0.
        assert max(occ_uniform[1:]) <= 1, occ_uniform
        # Static quantiles of an early sample already help massively…
        occ_static = run(density_splits(4, sample))
        assert max(occ_static) <= 8 * max(1, min(occ_static))
        # …and periodic re-splits from the full observed stream land the
        # done-criterion: per-shard occupancy within ~2x.
        occ_resplit = run(density_splits(4, sample), reshard_every=8)
        lo, hi = min(occ_resplit), max(occ_resplit)
        assert hi <= 2 * lo, (occ_resplit, occ_static, occ_uniform)

    def test_auto_reshard_is_the_default_and_bounds_skew(self):
        """Density resharding as the RUNTIME DEFAULT: a Zipf-skewed stream
        on out-of-the-box uniform splits must trigger the engine's own
        occupancy-driven re-split (no harness involvement) and land
        bounded per-shard skew — never the [N, 1, 1, 1] degeneracy."""
        rng = np.random.default_rng(29)
        n_txns = 512
        ids = np.minimum(rng.zipf(1.3, (n_txns, 2)) - 1, 2000)
        keyss = [[int(i).to_bytes(8, "big") for i in row] for row in ids]

        def run(auto: bool):
            cs = ShardedConflictSet(
                n_shards=4, capacity=4096, batch_size=16,
                max_read_ranges=2, max_write_ranges=2, max_key_bytes=12,
                auto_reshard=auto, reshard_interval=4,
            )
            assert cs.auto_reshard == auto
            v = 0
            for i in range(0, n_txns, 16):
                v += 1
                txns = [
                    TxnConflictInfo(
                        read_version=v - 1,
                        read_ranges=[KeyRange(k, k + b"\x00") for k in ks],
                        write_ranges=[KeyRange(k, k + b"\x00") for k in ks],
                    )
                    for ks in keyss[i : i + 16]
                ]
                cs.resolve(txns, v)
            return cs

        off = run(auto=False)
        occ_off = off.shard_occupancy()
        # 8-byte int keys all share first byte 0: uniform splits leave
        # every boundary in shard 0 — the degeneracy the default fixes.
        assert max(occ_off[1:]) <= 1 and off.auto_reshards == 0

        on = run(auto=True)
        occ_on = on.shard_occupancy()
        assert on.auto_reshards >= 1  # the default policy actually fired
        lo, hi = max(1, min(occ_on)), max(occ_on)
        assert hi <= on.reshard_skew * lo, (occ_on, occ_off)

    def test_auto_reshard_preserves_verdicts_vs_oracle(self):
        """The default policy must never change a verdict: same stream
        through the auto-resharding engine and the oracle."""
        rng = np.random.default_rng(41)
        cs = make_sharded(4, capacity=1024, auto_reshard=True,
                          reshard_interval=2, reshard_skew=1.5)
        oracle = OracleConflictSet()
        cv = 0
        for step in range(10):
            cv += int(rng.integers(1, 10))
            txns = [rand_txn(rng, read_version=max(0, cv - 5))
                    for _ in range(int(rng.integers(1, 24)))]
            assert cs.resolve(txns, cv) == oracle.resolve(txns, cv), step
        assert not cs.overflowed

    def test_reshard_preserves_verdicts(self):
        """reshard() between batches must not change any verdict: the
        history is re-clipped, not altered."""
        from foundationdb_tpu.parallel.sharded_resolver import density_splits

        rng = np.random.default_rng(17)
        a = make_sharded(4, capacity=1024)
        b = make_sharded(4, capacity=1024)
        oracle = OracleConflictSet()
        cv = 0
        seen_keys: list[bytes] = []
        for step in range(8):
            cv += int(rng.integers(1, 10))
            txns = [rand_txn(rng, read_version=max(0, cv - 5))
                    for _ in range(int(rng.integers(1, 24)))]
            for t in txns:
                for r in t.read_ranges + t.write_ranges:
                    seen_keys.append(r.begin)
            va = a.resolve(txns, cv)
            vb = b.resolve(txns, cv)
            want = oracle.resolve(txns, cv)
            assert va == vb == want, step
            if step % 3 == 2:  # re-split mid-stream from observed keys
                b.reshard(density_splits(4, seen_keys))
        assert not a.overflowed and not b.overflowed
        # The resharded engine actually moved its bounds at least once.
        assert b._interior_splits is not None


# -- the window history a shard (PR 45) --------------------------------------
#
# A shard keeps what one chip keeps: a base frozen between
# merges, its table, a delta that every dispatch probes and paints. The delta
# here holds 2 x 16 x 2 + 2 = 66 rows, so a shard folds it into its base
# every few batches, on the demand of ITS slice of the batch alone.


def small_window(**kw):
    args = dict(capacity=512, batch_size=16, max_read_ranges=4,
                max_write_ranges=2, max_key_bytes=8, auto_reshard=False)
    args.update(kw)
    return args


def merges_a_shard(cs):
    return [int(x) for x in np.asarray(cs._hist_core.merges)]


def key3(rng):
    return bytes(rng.integers(0, 256, 3).astype(np.uint8))


def wide_txn(rng, read_version, written=()):
    """One or two point writes (a fifth of them a short range) and one to
    four reads, over the whole byte space: every shard of a first-byte
    split sees its share. Reads are keys written before, short ranges and,
    now and then, a range over several shards."""
    def short(k):
        return KeyRange(k, k[:2] + bytes([min(255, k[2] + 9)]) + b"\x00")

    writes = []
    for _ in range(int(rng.integers(1, 3))):
        k = key3(rng)
        writes.append(short(k) if rng.random() < 0.2
                      else KeyRange(k, k + b"\x00"))
    reads = []
    for _ in range(int(rng.integers(1, 5))):
        kind = rng.random()
        if kind < 0.5 and len(written):
            k = written[int(rng.integers(0, len(written)))]
            reads.append(KeyRange(k, k + b"\x00"))
        elif kind < 0.9:
            reads.append(short(key3(rng)))
        else:
            a, b = sorted([key3(rng), key3(rng)])
            reads.append(KeyRange(a, b + b"\x00"))
    return TxnConflictInfo(read_version=read_version, read_ranges=reads,
                           write_ranges=writes)


def assert_report_covers(oracle, cs, where):
    for i, ranges in oracle.last_conflicting.items():
        kernel = cs.last_conflicting.get(i)
        assert kernel is not None, f"{where} txn {i}: no report"
        for r in ranges:
            assert any(k.begin <= r.begin and r.end <= k.end
                       for k in kernel), f"{where} txn {i}: {r} not covered"


RESPLITS = ["never", "right_after_a_merge", "between_two_merges"]


@pytest.mark.parametrize("resplit", RESPLITS)
def test_the_window_history_a_shard_is_one_historys(resplit):
    """A stream long enough that every shard merges several times, through
    the mesh engine, the one-chip engine and the oracle, a third of the
    transactions asking for the conflicting-keys report (the mesh's
    `report=True` program). With `resplit`, the bounds move to the live
    history's quantiles at a dispatch right behind one in which a shard
    merged, or behind one in which none did (every delta part full)."""
    rng = np.random.default_rng(45)
    mesh = ShardedConflictSet(n_shards=4, **small_window())
    one = TPUConflictSet(capacity=2048, batch_size=16, max_read_ranges=4,
                         max_write_ranges=2, max_key_bytes=8)
    assert mesh.delta_capacity == 66
    oracle = OracleConflictSet()
    cv, moved, before, written = 1000, 0, merges_a_shard(mesh), []
    for step in range(48):
        cv += int(rng.integers(5, 40))
        txns = [wide_txn(rng, int(rng.integers(max(0, cv - 320), cv)),
                         written) for _ in range(16)]
        written = (written + [w.begin for t in txns
                              for w in t.write_ranges])[-256:]
        for t in txns[::3]:
            object.__setattr__(t, "report_conflicting_keys", True)
        oldest = cv - 260
        got = mesh.resolve(txns, cv, oldest_version=oldest)
        oracle.oldest_version = max(oracle.oldest_version, oldest)
        assert got == oracle.resolve(txns, cv), f"batch {step}"
        assert got == one.resolve(txns, cv, oldest_version=oldest), step
        assert mesh.last_conflicting == one.last_conflicting, step
        assert_report_covers(oracle, mesh, f"batch {step}")
        after = merges_a_shard(mesh)
        merged = after != before
        before = after
        due = {"never": False, "right_after_a_merge": merged,
               "between_two_merges": not merged}[resplit]
        if due and step >= 6 and moved < 4:
            splits = mesh.density_splits_from_history()
            if splits is not None and splits != mesh._interior_splits:
                mesh.reshard(splits)
                moved += 1
            before = merges_a_shard(mesh)  # the fold is a merge a shard
    assert not mesh.overflowed
    assert min(merges_a_shard(mesh)) >= 3, merges_a_shard(mesh)
    assert moved >= (0 if resplit == "never" else 2)
    assert set(got) <= set(Verdict) and Verdict.CONFLICT in got


def test_a_window_that_slides_past_a_whole_base_is_reclaimed():
    """_maybe_merge's other arm, a shard: once capacity // 8 = 64 base rows
    of a shard have expired, its next dispatch merges although its delta
    has room, and the base lets them go. Verdicts across it are the
    oracle's; the policy's probe counts LIVE rows before and after."""
    rng = np.random.default_rng(9)
    mesh = ShardedConflictSet(n_shards=4, **small_window())
    oracle = OracleConflictSet()
    cv = 1000
    for step in range(14):  # ~110 boundaries a shard, most in its base
        cv += 10
        txns = [TxnConflictInfo(
            read_version=cv - 5, read_ranges=[],
            write_ranges=[KeyRange(k, k + b"\x00") for k in (
                bytes(rng.integers(0, 256, 3).astype(np.uint8)),
                bytes(rng.integers(0, 256, 3).astype(np.uint8)))])
            for _ in range(16)]
        assert mesh.resolve(txns, cv, oldest_version=cv - 500) == \
            oracle.resolve(txns, cv)
    hc = mesh._hist_core
    base_rows = [int(x) for x in np.asarray(hc.base.n_used)]
    assert min(base_rows) >= 64 + 8, base_rows
    live = mesh.shard_occupancy()
    assert min(live) >= 64 and not mesh.overflowed
    # The window slides past all of it in one step; the next batch writes
    # one key and reads two old ones, at an old and at a new read version.
    before = merges_a_shard(mesh)
    room = [int(x) for x in np.asarray(hc.delta.n_used)]
    assert max(room) + 2 * 2 <= mesh.delta_capacity  # no delta is full
    cv += 10_000
    old = TxnConflictInfo(read_version=cv - 9_000,
                          read_ranges=[KeyRange(b"", b"\xff\xff")],
                          write_ranges=[])
    new = TxnConflictInfo(read_version=cv - 5,
                          read_ranges=[KeyRange(b"", b"\xff\xff")],
                          write_ranges=[KeyRange(b"\x90k", b"\x90k\x00")])
    oracle.oldest_version = cv - 500
    want = oracle.resolve([old, new], cv)
    assert mesh.resolve([old, new], cv, oldest_version=cv - 500) == want
    assert want == [Verdict.TOO_OLD, Verdict.COMMITTED]
    assert merges_a_shard(mesh) == [m + 1 for m in before]
    hc = mesh._hist_core
    assert [int(x) for x in np.asarray(hc.base.n_used)] == [1, 1, 1, 1]
    assert mesh.shard_occupancy() == [1, 1, 3, 1]  # shard 2 took the write
    assert mesh.headroom() == mesh.capacity - 4  # its base's row, its delta's 3


def test_one_shard_alone_fills_its_delta():
    """A skewed stream: every write under the first bytes of shard 1. Its
    delta fills and is folded in, again and again, at dispatches where the
    other three shards' `cond` takes the other branch: a merge holds no
    collective, so the shards may differ. Reads span all four."""
    rng = np.random.default_rng(13)
    mesh = ShardedConflictSet(n_shards=4, **small_window())
    oracle = OracleConflictSet()
    cv = 1000
    for step in range(24):
        cv += 10
        txns = []
        for j in range(16):
            keys = [bytes([0x40 + int(rng.integers(0, 0x40))])
                    + bytes(rng.integers(0, 256, 2).astype(np.uint8))
                    for _ in range(2)]
            reads = [KeyRange(keys[0], keys[0] + b"\x00")]
            if j % 4 == 0:
                reads.append(KeyRange(b"\x10", b"\xf0"))  # every shard
            txns.append(TxnConflictInfo(
                read_version=cv - int(rng.integers(1, 60)),
                read_ranges=reads,
                write_ranges=[KeyRange(k, k + b"\x00") for k in keys]))
        # five batches of <= 64 boundaries live: a shard holds 512
        got = mesh.resolve(txns, cv, oldest_version=cv - 50)
        oracle.oldest_version = max(oracle.oldest_version, cv - 50)
        assert got == oracle.resolve(txns, cv), f"batch {step}"
    merges = merges_a_shard(mesh)
    assert merges[1] >= 6 and merges[0] == merges[2] == merges[3] == 0
    occ = mesh.shard_occupancy()
    assert occ[1] > 100 and occ[0] == occ[2] == occ[3] == 1
    assert not mesh.overflowed


def test_the_window_scan_merges_a_shard_inside_the_scan():
    """resolve_wire_window's scan carries the stacked HistState: a shard's
    merge inside a scan step gives the verdicts of batch-by-batch
    dispatch, on both engines."""
    from foundationdb_tpu.models.conflict_set import encode_resolve_batch

    rng = np.random.default_rng(31)
    window = ShardedConflictSet(n_shards=4, **small_window())
    seq = TPUConflictSet(capacity=2048, batch_size=16, max_read_ranges=4,
                         max_write_ranges=2, max_key_bytes=8)
    cv = 1000
    for _ in range(4):
        cvs, batches = [], []
        for _k in range(4):
            cv += 10
            cvs.append(cv)
            batches.append([wide_txn(rng, cv - int(rng.integers(1, 80)))
                            for _ in range(16)])
        wire = b"".join(encode_resolve_batch(t) for t in batches)
        got = window.resolve_wire_window(wire, cvs, 16)
        for i, (v, txns) in enumerate(zip(cvs, batches)):
            assert [int(x) for x in got[i]] == [
                int(x) for x in seq.resolve(txns, v)]
    assert min(merges_a_shard(window)) >= 1 and not window.overflowed
