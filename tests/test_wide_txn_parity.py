"""A transaction with more conflict ranges than the engine's padded row has
slots is judged EXACTLY, at any width: it takes continuation rows
(conflict_set._pack, native/keypack.cpp, conflict_kernel.txn_segments) and
no range is widened, merged or dropped.

Six judges must agree verdict for verdict on every stream below: the engine
on the object path and on the wire path, the served `Resolver` role over it,
the repo's brute-force oracle, upstream's algorithm in native/skiplist.cpp,
and the benchmark's plain reference for range lists
(benchmark/lib/reference_ranges.py). One engine shape throughout (the
served role's 8 + 8 slots), so the programs compile once a worker.
"""

import numpy as np
import pytest

from benchmark.lib import reference_ranges
from foundationdb_tpu.core.types import KeyRange, TxnConflictInfo, Verdict
from foundationdb_tpu.models import conflict_kernel as ck
from foundationdb_tpu.models.conflict_set import (
    TPUConflictSet,
    encode_resolve_batch,
)
from foundationdb_tpu.models.cpu_conflict_set import CPUSkipListConflictSet
from foundationdb_tpu.runtime.flow import Loop
from foundationdb_tpu.runtime.resolver import Resolver
from foundationdb_tpu.sim.oracle import OracleConflictSet

SLOTS = 8
ENGINE = dict(capacity=2048, batch_size=32, max_read_ranges=SLOTS,
              max_write_ranges=SLOTS, max_key_bytes=8)


def engine(**kw) -> TPUConflictSet:
    return TPUConflictSet(**dict(ENGINE, **kw))


def pt(k: bytes) -> KeyRange:
    return KeyRange(k, k + b"\x00")


def key(rng, alphabet: int = 6, max_len: int = 3) -> bytes:
    n = int(rng.integers(1, max_len + 1))
    return bytes((97 + rng.integers(0, alphabet, n)).astype(np.uint8))


def some_range(rng) -> KeyRange:
    """Point and true ranges mixed; now and then an empty or repeated one
    (both keep the treatment they always had: dropped, and kept)."""
    a, b = sorted([key(rng), key(rng)])
    u = rng.random()
    if u < 0.55:
        return pt(a)
    if u < 0.62:
        return KeyRange(a, a)  # empty
    return KeyRange(a, b)  # may be empty when a == b


def txn(rng, rv: int, n_reads: int, n_writes: int) -> TxnConflictInfo:
    reads = [some_range(rng) for _ in range(n_reads)]
    if n_reads > 2 and rng.random() < 0.3:
        reads[-1] = reads[0]  # a duplicate
    return TxnConflictInfo(
        read_version=rv, read_ranges=reads,
        write_ranges=[some_range(rng) for _ in range(n_writes)])


def stream(seed: int, n_reads: int, n_writes: int, batches: int = 5):
    """[(commit version, oldest version, txns)]: transactions of the asked
    width with narrow ones between them, read versions from stale
    (TOO_OLD) to current, a small alphabet so that they conflict with the
    history and with one another."""
    rng = np.random.default_rng(seed)
    cv, out = 1000, []
    for _ in range(batches):
        cv += int(rng.integers(5, 40))
        txns = []
        for _i in range(int(rng.integers(4, 12))):
            rv = int(rng.integers(cv - 120, cv))
            if rng.random() < 0.35:
                txns.append(txn(rng, rv, int(rng.integers(0, 3)),
                                int(rng.integers(0, 3))))
            else:
                txns.append(txn(rng, rv, n_reads, n_writes))
        out.append((cv, cv - 90, txns))
    return out


def as_pairs(t: TxnConflictInfo):
    return (t.read_version, [(r.begin, r.end) for r in t.read_ranges],
            [(w.begin, w.end) for w in t.write_ranges])


class Judges:
    """The six, fed the same batches in the same order."""

    def __init__(self, **engine_kw):
        self.obj = engine(**engine_kw)
        self.wire = engine(**engine_kw)
        self.loop = Loop(seed=1)
        self.role = Resolver(self.loop, engine(**engine_kw))
        self.oracle = OracleConflictSet()
        self.skiplist = CPUSkipListConflictSet()
        self.reference = reference_ranges.RangeHistory()
        self.prev = 0

    def resolve(self, cv: int, oldest: int, txns) -> dict:
        self.oracle.oldest_version = max(self.oracle.oldest_version, oldest)
        role, _conf, fail_safe, _wave = self.loop.run(self.role.resolve(
            self.prev, cv, txns, oldest_version=oldest))
        assert not fail_safe
        self.prev = cv
        return {
            "oracle": self.oracle.resolve(txns, cv),
            "object": self.obj.resolve(txns, cv, oldest),
            "wire": self.wire.resolve_wire(
                encode_resolve_batch(txns), cv, oldest),
            "role": list(role),
            "skiplist": self.skiplist.resolve(txns, cv, oldest),
            "reference": [Verdict(v) for v in reference_ranges.range_verdicts(
                self.reference, [as_pairs(t) for t in txns], cv, oldest)],
        }


def assert_agree(got: dict, where: str) -> None:
    want = got["oracle"]
    for name, verdicts in got.items():
        assert verdicts == want, f"{where}: {name} {verdicts} != {want}"


WIDTHS = [(r, w) for r in (1, 8, 9, 16, 17, 40) for w in (1, 2, 9)]


@pytest.mark.parametrize("n_reads,n_writes", WIDTHS)
def test_every_judge_agrees_at_this_width(n_reads, n_writes):
    judges = Judges()
    seen = set()
    for i, (cv, oldest, txns) in enumerate(
            stream(100 * n_reads + n_writes, n_reads, n_writes)):
        got = judges.resolve(cv, oldest, txns)
        assert_agree(got, f"{n_reads}r{n_writes}w batch {i}")
        seen.update(got["oracle"])
    # The streams are not all of one verdict: each shows conflicts.
    assert Verdict.CONFLICT in seen and Verdict.COMMITTED in seen


def test_the_only_conflicting_read_sits_in_the_last_row():
    """17 reads are three rows of eight slots; only the 17th, alone in the
    last row, meets the write. Against the history, and inside one batch
    against an earlier accepted transaction whose write sits in ITS last
    row (nine writes are two rows)."""
    judges = Judges()
    quiet = [pt(b"q%02d" % i) for i in range(16)]
    got = judges.resolve(10, 0, [TxnConflictInfo(5, [], [pt(b"hot")])])
    assert_agree(got, "paint")
    reader = TxnConflictInfo(5, quiet + [pt(b"hot")], [pt(b"w0")])
    clean = TxnConflictInfo(5, quiet + [pt(b"cold")], [pt(b"w1")])
    newer = TxnConflictInfo(15, quiet + [pt(b"hot")], [pt(b"w2")])
    got = judges.resolve(20, 0, [reader, clean, newer])
    assert_agree(got, "history")
    assert got["object"] == [Verdict.CONFLICT, Verdict.COMMITTED,
                             Verdict.COMMITTED]
    writer = TxnConflictInfo(
        25, [pt(b"r")], [pt(b"x%d" % i) for i in range(8)] + [pt(b"last")])
    loser = TxnConflictInfo(25, quiet + [pt(b"last")], [pt(b"w3")])
    # The loser paints nothing: a reader of its write commits.
    after = TxnConflictInfo(25, quiet + [pt(b"w3")], [])
    # A transaction never conflicts with itself.
    own = TxnConflictInfo(25, quiet + [pt(b"mine")],
                          [pt(b"y%d" % i) for i in range(8)] + [pt(b"mine")])
    got = judges.resolve(30, 0, [writer, loser, after, own])
    assert_agree(got, "intra-batch")
    assert got["object"] == [Verdict.COMMITTED, Verdict.CONFLICT,
                             Verdict.COMMITTED, Verdict.COMMITTED]


def test_a_rejected_wide_transaction_paints_none_of_its_rows():
    judges = Judges()
    assert_agree(judges.resolve(10, 0, [TxnConflictInfo(
        5, [], [pt(b"hot")])]), "paint")
    many = [pt(b"k%02d" % i) for i in range(20)]
    # Conflicts on `hot` (first row); its 20 writes span three rows.
    assert_agree(judges.resolve(20, 0, [TxnConflictInfo(
        5, [pt(b"hot")], many)]), "rejected")
    got = judges.resolve(30, 0, [TxnConflictInfo(15, [k], []) for k in many])
    assert_agree(got, "readers")
    assert set(got["object"]) == {Verdict.COMMITTED}


def test_too_old_is_the_transactions_and_write_only_never_is():
    judges = Judges()
    got = judges.resolve(1000, 500, [
        TxnConflictInfo(1, [pt(b"a%02d" % i) for i in range(9)], []),
        TxnConflictInfo(1, [], [pt(b"b%02d" % i) for i in range(9)]),
        TxnConflictInfo(1, [KeyRange(b"e", b"e")] * 9,
                        [pt(b"c%02d" % i) for i in range(9)]),
    ])
    assert_agree(got, "too old")
    assert got["object"] == [Verdict.TOO_OLD, Verdict.COMMITTED,
                             Verdict.COMMITTED]


def kernel_conts(cs: TPUConflictSet) -> list:
    """Record the `cont` of every batch `cs` hands its kernel from now on
    (True where it had one)."""
    seen, inner = [], cs._pack_resident

    def spy(bt, **kw):
        seen.append(bt.cont is not None)
        return inner(bt, **kw)

    cs._pack_resident = spy
    return seen


def bulk_load(n_txns: int, width: int, first: int = 0) -> list:
    """A loader's transactions: `width` sets of new keys each, no read."""
    return [TxnConflictInfo(1, [], [pt(b"u%04d" % (first + i * width + j))
                                    for j in range(width)])
            for i in range(n_txns)]


@pytest.mark.parametrize("width", [9, 20, 100])
def test_a_bulk_load_is_judged_exactly_by_the_narrow_program(width):
    """Wide transactions WITHOUT a read (a benchmark's loader: 100 sets a
    transaction) take continuation rows like any other, but no row of
    theirs can lose, so the kernel's row -> transaction reduce has
    nothing to decide: the batch runs the program narrow batches run (no
    second program is compiled or loaded for a load), and every judge
    still agrees: on the load, on narrow readers of its keys in the same
    batch (after it: they lose; before it: they commit) and in a later
    one."""
    judges = Judges(capacity=4096, batch_size=64)
    seen = [kernel_conts(judges.obj), kernel_conts(judges.wire)]
    n = 64 // -(-width // SLOTS) - 2
    load = bulk_load(n, width)
    before = TxnConflictInfo(5, [pt(b"u0000")], [pt(b"w0")])
    after = TxnConflictInfo(5, [pt(b"u%04d" % (width - 1))], [pt(b"w1")])
    got = judges.resolve(10, 0, [before] + load + [after])
    assert_agree(got, "load")
    assert got["object"] == ([Verdict.COMMITTED] * (n + 1)
                             + [Verdict.CONFLICT])
    layout = judges.obj._pack(load)  # the rows are laid out as ever
    assert layout.cont is not None and layout.cont.sum() == (
        n * (-(-width // SLOTS) - 1))
    # later: a reader from before the load loses, one from after commits;
    # the loser of the first batch painted nothing
    got = judges.resolve(20, 0, [
        TxnConflictInfo(5, [pt(b"u%04d" % (width * n - 1))], []),
        TxnConflictInfo(15, [pt(b"u%04d" % (width * n - 1))], []),
        TxnConflictInfo(5, [pt(b"w1")], []),
    ] + bulk_load(2, width, first=width * n))
    assert_agree(got, "after the load")
    assert got["object"][:3] == [Verdict.CONFLICT, Verdict.COMMITTED,
                                 Verdict.COMMITTED]
    assert seen == [[False, False], [False, False]]


@pytest.mark.parametrize("case", ["one wide reader", "wave schedule"])
def test_the_reduce_stays_where_it_decides_something(case):
    """One wide transaction WITH a read among a load's, and the batch
    keeps `cont` on its way to the kernel; the wave schedule, which levels
    row by row, keeps it always."""
    cs = engine(wave_commit=case == "wave schedule")
    oracle = OracleConflictSet(wave_commit=cs.wave_commit)
    seen = kernel_conts(cs)
    txns = bulk_load(2, 20)
    if case == "one wide reader":
        txns.insert(1, TxnConflictInfo(
            5, [pt(b"u0003")], [pt(b"v%02d" % i) for i in range(9)]))
    assert cs.resolve(txns, 10, 0) == oracle.resolve(txns, 10)
    assert seen == [True]


def g8ui(rng, rv: int, rows: int, next_insert: list) -> TxnConflictInfo:
    """mako's g8ui: 8 GETs, 1 UPDATE (a get and a set of one row), 1 INSERT
    (a set of a key never seen): 9 point reads, 2 point writes."""
    picks = rng.integers(0, rows, 9)
    next_insert[0] += 1
    k = [b"mako%012d" % int(i) for i in picks]
    return TxnConflictInfo(
        rv, [pt(x) for x in k],
        [pt(k[8]), pt(b"mako%012d" % (rows + next_insert[0]))])


def test_a_g8ui_stream_of_64_batches_gets_the_references_verdicts():
    """Uniform rows, read version eight batches behind. On the parent
    commit (789d63d), which widened nine reads to five covering ranges,
    this stream read 1,371 of 2,048 verdicts wrong: 1,344 CONFLICT where
    the reference COMMITTED, and 27 the other way round, later readers of
    writes that the parent had refused."""
    rng = np.random.default_rng(2024)
    cs = engine(max_key_bytes=16)
    history = reference_ranges.RangeHistory()
    step, rows, ins = 100, 4000, [0]
    wrong = conflicts = total = 0
    for n in range(64):
        cv = (n + 1) * step
        rv = max(0, cv - 8 * step)
        txns = [g8ui(rng, rv, rows, ins) for _ in range(32)]
        got = cs.resolve(txns, cv, max(0, cv - 40 * step))
        ref = reference_ranges.range_verdicts(
            history, [as_pairs(t) for t in txns], cv, max(0, cv - 40 * step))
        wrong += sum(1 for a, b in zip(got, ref) if int(a) != b)
        conflicts += sum(1 for v in ref if v == reference_ranges.CONFLICT)
        total += len(ref)
    assert total == 2048 and 0 < conflicts < total // 2
    assert wrong == 0


def plain_layout(cs: TPUConflictSet, txns) -> ck.BatchTensors:
    """The padded tensors as they have always been for transactions that
    fit a row each: transaction i is row i, its range c is slot c."""
    bt = cs._empty_batch()
    for i, t in enumerate(txns):
        bt.txn_mask[i] = True
        bt.read_version[i] = cs._rel_read(t.read_version)
        reads = [x for x in t.read_ranges if not x.empty]
        writes = [x for x in t.write_ranges if not x.empty]
        for ranges, begin, end, mask in (
                (reads, bt.read_begin, bt.read_end, bt.read_mask),
                (writes, bt.write_begin, bt.write_end, bt.write_mask)):
            if ranges:
                b, e = cs.codec.pack_ranges(
                    [(x.begin, x.end) for x in ranges])
                begin[i, : len(ranges)] = b
                end[i, : len(ranges)] = e
                mask[i, : len(ranges)] = True
    return bt


def same_tensors(a: ck.BatchTensors, b: ck.BatchTensors) -> None:
    for name in ck.BatchTensors._fields:
        x, y = getattr(a, name), getattr(b, name)
        if x is None or y is None:
            assert x is None and y is None, name
        else:
            assert np.array_equal(np.asarray(x), np.asarray(y)), name


@pytest.mark.parametrize("seed", [0, 1])
def test_a_batch_with_no_wide_transaction_keeps_its_layout(seed):
    """Byte for byte the tensors of before, no `cont`: such a batch runs
    the program it always ran. Both packers."""
    rng = np.random.default_rng(seed)
    cs = engine()
    cs.base_version = 0
    txns = [txn(rng, int(rng.integers(0, 50)), int(rng.integers(0, 9)),
                int(rng.integers(0, 9))) for _ in range(32)]
    want = plain_layout(cs, txns)
    got = cs._pack(txns)
    assert got.cont is None
    same_tensors(got, want)
    buf = np.frombuffer(encode_resolve_batch(txns), np.uint8)
    wired, off, taken = cs._pack_wire(buf, 0, len(txns))
    assert (off, taken, wired.cont) == (buf.size, len(txns), None)
    same_tensors(wired, want)
    assert cs._chunks(txns) == [(0, 32)]
    assert cs.txn_rows(txns) == (32, 0)


@pytest.mark.parametrize("n_reads,n_writes", [(9, 2), (17, 9), (40, 1)])
def test_the_wire_packer_lays_wide_batches_out_like_the_object_packer(
        n_reads, n_writes):
    rng = np.random.default_rng(n_reads)
    cs = engine()
    cs.base_version = 0
    txns = [txn(rng, int(rng.integers(0, 50)), n_reads, n_writes)
            if i % 3 else txn(rng, 7, 2, 1) for i in range(40)]
    buf = np.frombuffer(encode_resolve_batch(txns), np.uint8)
    offset, seen = 0, 0
    for lo, hi in cs._chunks(txns):
        bt = cs._pack(txns[lo:hi])
        wired, offset, taken = cs._pack_wire(
            buf, offset, min(len(txns) - seen, cs.batch_size))
        assert taken == hi - lo, "both stop before the same transaction"
        same_tensors(bt, wired)
        assert bt.cont is not None and bt.cont.any() and not bt.cont[0]
        # No transaction is cut by the end of a dispatch.
        rows = int(bt.txn_mask.sum())
        assert rows <= cs.batch_size and not bt.cont[rows:].any()
        seen += taken
    assert (offset, seen) == (buf.size, len(txns))
    # Wide by its NON-EMPTY ranges: nine reads of which one is empty fit.
    def live(ranges):
        return sum(1 for x in ranges if not x.empty)

    want = [max(1, -(-live(t.read_ranges) // SLOTS),
                -(-live(t.write_ranges) // SLOTS)) for t in txns]
    assert cs.txn_rows(txns) == (sum(want), sum(1 for k in want if k > 1))
    assert sum(want) > len(txns)


def test_the_role_counts_rows_and_wide_transactions():
    loop = Loop(seed=3)
    role = Resolver(loop, engine())
    nine = [pt(b"n%02d" % i) for i in range(9)]
    txns = [TxnConflictInfo(5, nine, [pt(b"w")]),
            TxnConflictInfo(5, [pt(b"a")], [pt(b"b")]),
            TxnConflictInfo(5, [], nine + nine)]
    loop.run(role.resolve(0, 10, txns))
    m = loop.run(role.get_metrics())
    assert (m["txns_resolved"], m["wide_txns"], m["rows_dispatched"]) == (
        3, 2, 2 + 1 + 3)
    assert m["ranges_received"] == 10 + 2 + 18
    # An engine with no slots gives every transaction one row.
    plain = Resolver(Loop(seed=4), OracleConflictSet())
    plain.loop.run(plain.resolve(0, 10, txns))
    m = plain.loop.run(plain.get_metrics())
    assert (m["wide_txns"], m["rows_dispatched"]) == (0, 3)


def test_a_transaction_wider_than_a_whole_dispatch_is_refused():
    cs = engine(batch_size=4)
    huge = TxnConflictInfo(5, [pt(b"%04d" % i) for i in range(4 * SLOTS + 1)],
                           [])
    with pytest.raises(ValueError, match="rows"):
        cs.resolve([huge], 10)
    with pytest.raises(ValueError):
        engine(batch_size=4).resolve_wire(encode_resolve_batch([huge]), 10)


def test_the_scan_window_path_refuses_a_wide_transaction():
    """One row a transaction there: refused, not widened."""
    cs = engine()
    wide = TxnConflictInfo(5, [pt(b"n%02d" % i) for i in range(9)], [])
    with pytest.raises(ValueError, match="one row a transaction"):
        cs.resolve_wire_window(encode_resolve_batch([wide, wide]),
                               [10, 20], 1)
    # and the engine is as it was
    assert cs.resolve([wide], 10) == [Verdict.COMMITTED]


@pytest.mark.parametrize("n_reads,n_writes", [(9, 2), (17, 9)])
def test_the_loser_report_names_the_reads_that_lost(n_reads, n_writes):
    """With continuation rows the report still maps a lost slot back to the
    transaction's own range: exactly the oracle's conflicting ranges."""
    cs, oracle = engine(), OracleConflictSet()
    for i, (cv, oldest, txns) in enumerate(
            stream(7 * n_reads, n_reads, n_writes, batches=4)):
        for t in txns[::2]:
            object.__setattr__(t, "report_conflicting_keys", True)
        oracle.oldest_version = max(oracle.oldest_version, oldest)
        assert cs.resolve(txns, cv, oldest) == oracle.resolve(txns, cv)
        assert set(cs.last_conflicting) == set(oracle.last_conflicting), i
        for j, ranges in oracle.last_conflicting.items():
            assert sorted((r.begin, r.end) for r in ranges) == sorted(
                (r.begin, r.end) for r in cs.last_conflicting[j]), (i, j)


@pytest.mark.parametrize("kw", [
    pytest.param(dict(wave_commit=True), id="wave-commit"),
])
def test_the_other_engine_designs_judge_wide_transactions_exactly(kw):
    cs = engine(**kw)
    oracle = OracleConflictSet(wave_commit=kw.get("wave_commit", False))
    for i, (cv, oldest, txns) in enumerate(stream(11, 17, 9, batches=4)):
        oracle.oldest_version = max(oracle.oldest_version, oldest)
        # One schedule domain a call: the wave oracle levels the whole
        # list, the engine one dispatch at a time.
        txns = txns[: cs._chunks(txns)[0][1]]
        assert cs.resolve(txns, cv, oldest) == oracle.resolve(txns, cv), i
        if kw.get("wave_commit"):
            assert cs.last_wave == oracle.last_wave, i


@pytest.mark.parametrize("wave", [False, True], ids=["sequential", "wave"])
def test_the_mesh_engine_judges_wide_transactions_exactly(wave):
    from foundationdb_tpu.parallel.sharded_resolver import ShardedConflictSet

    cs = ShardedConflictSet(n_shards=4, wave_commit=wave, **ENGINE)
    oracle = OracleConflictSet(wave_commit=wave)
    for i, (cv, oldest, txns) in enumerate(stream(13, 9, 2, batches=4)):
        oracle.oldest_version = max(oracle.oldest_version, oldest)
        txns = txns[: cs._chunks(txns)[0][1]]
        assert cs.resolve(txns, cv, oldest) == oracle.resolve(txns, cv), i
        if wave:
            assert cs.last_wave == oracle.last_wave, i


SHARDS = [(b"", b"c"), (b"c", b"\xff\xff")]  # the streams' keys are a..f


def two_phase(shards, txns, cv, oldest):
    """The commit proxy's part of the global wave exchange
    (commit_proxy._resolve_wave_global) over engines or roles."""
    from foundationdb_tpu.core.wavemesh import (
        WaveEdges, WaveGraph, clip_txns, combine_edges)

    edges = [WaveEdges.from_wire(
        sh.resolve_edges(clip_txns(txns, lo, hi), cv, oldest).to_wire())
        for (lo, hi), sh in zip(SHARDS, shards)]
    graph = WaveGraph.from_wire(combine_edges(edges).to_wire())
    return [sh.resolve_apply(graph) for sh in shards]


@pytest.mark.parametrize("n_reads,n_writes", [(17, 9), (40, 2)])
def test_the_two_phase_wave_exchange_judges_wide_transactions_exactly(
        n_reads, n_writes):
    """Every shard is sent every transaction, clipped to its keys, so a
    wide one takes other rows on each: the exchange goes by transaction."""
    kw = dict(wave_commit=True)
    single, shards = engine(**kw), [engine(**kw) for _ in SHARDS]
    oracle = OracleConflictSet(wave_commit=True)
    rows_differ = wide_on_a_shard = False
    for i, (cv, oldest, txns) in enumerate(
            stream(19 * n_reads, n_reads, n_writes, batches=5)):
        from foundationdb_tpu.core.wavemesh import clip_txns

        txns = txns[: single._chunks(txns)[0][1]]
        oracle.oldest_version = max(oracle.oldest_version, oldest)
        want = oracle.resolve(txns, cv)
        assert single.resolve(txns, cv, oldest) == want, i
        rows, wide = zip(*(sh.txn_rows(clip_txns(txns, lo, hi))
                           for (lo, hi), sh in zip(SHARDS, shards)))
        rows_differ |= len({*rows, single.txn_rows(txns)[0]}) > 1
        wide_on_a_shard |= any(wide)
        for got, sh in zip(two_phase(shards, txns, cv, oldest), shards):
            assert got == want, i
            assert sh.last_wave == oracle.last_wave == single.last_wave, i
            assert sh.last_reordered == single.last_reordered, i
    assert rows_differ and wide_on_a_shard  # else the case shows nothing


def test_the_two_phase_exchange_feeds_the_admission_filter_by_row():
    from foundationdb_tpu.admission.filter import RecentWritesFilter

    cs, seen = engine(wave_commit=True), RecentWritesFilter()
    cs.attach_admission_filter(seen)
    wide = TxnConflictInfo(5, [], [pt(b"a%02d" % i) for i in range(20)])
    lost = TxnConflictInfo(5, [pt(b"a03")], [pt(b"b")])
    assert cs.resolve([wide], 10) == [Verdict.COMMITTED]
    assert seen.recorded == 20
    got, = two_phase([cs], [lost, wide, lost], 20, 0)
    assert got == [Verdict.CONFLICT, Verdict.COMMITTED, Verdict.CONFLICT]
    assert seen.recorded == 40  # the wide one's three rows, no one else's


class WaveRole:
    """A resolver role behind the two-phase exchange, as two_phase asks."""

    def __init__(self, **kw):
        self.role = Resolver(Loop(seed=3), engine(wave_commit=True, **kw))
        self.prev = 0

    def resolve_edges(self, txns, cv, oldest):
        from foundationdb_tpu.core.wavemesh import WaveEdges

        self.cv = cv
        return WaveEdges.from_wire(self.role.loop.run(
            self.role.resolve_edges(self.prev, cv, txns, oldest)))

    def resolve_apply(self, graph):
        self.prev = self.cv
        return self.role.loop.run(
            self.role.resolve_apply(self.cv, graph.to_wire()))


def test_the_role_judges_wide_transactions_through_the_wave_exchange():
    roles, oracle = [WaveRole() for _ in SHARDS], OracleConflictSet(
        wave_commit=True)
    wide = sent = 0
    for i, (cv, oldest, txns) in enumerate(stream(23, 17, 9, batches=4)):
        txns = txns[: roles[0].role.cs._chunks(txns)[0][1]]
        oracle.oldest_version = max(oracle.oldest_version, oldest)
        want = oracle.resolve(txns, cv)
        for verdicts, _conf, fail_safe, wave in two_phase(
                roles, txns, cv, oldest):
            assert not fail_safe
            assert list(verdicts) == want and wave == oracle.last_wave, i
        sent += len(txns)
    for r in roles:
        m = r.role.loop.run(r.role.get_metrics())
        wide += m["wide_txns"]
        assert m["txns_resolved"] == sent <= m["rows_dispatched"]
        assert m["resolve_failures"] == m["txns_rejected_fail_safe"] == 0
    assert wide > 0


def test_a_window_too_wide_for_one_exchange_conflicts_and_the_chain_goes_on():
    """A client's wide transactions can need more rows than one dispatch
    holds. The exchange carries one schedule domain, so such a window
    conflicts as a whole on every shard, through the fail-safe reply;
    nothing of it is painted, and the next window resolves."""
    roles = [WaveRole() for _ in SHARDS]
    load = [TxnConflictInfo(5, [], [pt(b"a%d%03d" % (j, i))
                                    for i in range(100)])
            for j in range(5)]  # 13 rows each on the first shard: 65 > 32
    assert roles[0].role.cs.txn_rows(load)[0] > ENGINE["batch_size"]
    with pytest.raises(ValueError, match="one schedule domain"):
        roles[0].role.cs.resolve_edges(load, 10)
    for verdicts, _conf, fail_safe, wave in two_phase(roles, load, 10, 0):
        assert fail_safe and wave is None
        assert list(verdicts) == [Verdict.CONFLICT] * 5
    # The chain stands at 10 on both shards; what was rejected is not in
    # the history (a read at version 5 of one of its keys commits).
    after = [load[0], TxnConflictInfo(5, [pt(b"a1007")], [pt(b"e")])]
    for verdicts, _conf, fail_safe, wave in two_phase(roles, after, 20, 0):
        assert not fail_safe and wave == [0, 0]
        assert list(verdicts) == [Verdict.COMMITTED] * 2
    late = [TxnConflictInfo(15, [pt(b"a0050")], []),
            TxnConflictInfo(25, [pt(b"a0050")], [])]
    for verdicts, _conf, _fs, _wave in two_phase(roles, late, 30, 0):
        assert list(verdicts) == [Verdict.CONFLICT, Verdict.COMMITTED]
    for r in roles:
        m = r.role.loop.run(r.role.get_metrics())
        assert m["txns_rejected_fail_safe"] == 5
        assert m["resolve_failures"] == 0


def test_a_speculative_engine_takes_wide_batches_the_serial_way():
    cs, oracle = engine(spec_resolve=True), OracleConflictSet()
    role = Resolver(Loop(seed=5), cs)
    prev = 0
    for cv, oldest, txns in stream(17, 9, 2, batches=3):
        oracle.oldest_version = max(oracle.oldest_version, oldest)
        got, _c, _f, _w = role.loop.run(role.resolve(
            prev, cv, txns, oldest_version=oldest))
        assert list(got) == oracle.resolve(txns, cv)
        prev = cv
