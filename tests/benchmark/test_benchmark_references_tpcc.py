"""The plain reference of the TPC-C cell (benchmark/lib/reference_prefix.py):
held to the reference the mako cell is judged by (reference_ranges.py, the
same rule with one sorted list) and to the program's C++ skiplist on the
TPC-C generator's own stream, at W = 8, where most transactions conflict,
and at W = 2,000; exact on ranges that lie in no prefix it was told of; and
shown to FAIL on the faults it is there to catch."""

import numpy as np
import pytest

from benchmark.lib import reference_prefix, reference_ranges, tpcc

STEP = 1000


def tpcc_batches(warehouses: int, seed: int, n_batches=30, batch=64):
    """(pairs, version, oldest) a batch, read version eight steps behind,
    as the cell sends them."""
    deal = tpcc.Deal(warehouses, [2302, seed], n_batches * batch)
    for n in range(n_batches):
        version = (n + 1) * STEP
        rv = max(0, version - 8 * STEP)
        yield ([(rv, reads, writes)
                for _kind, reads, writes in deal.batch(n, batch)],
               version, max(0, version - 20 * STEP))


@pytest.mark.parametrize("warehouses,seed", [(8, 1), (8, 2), (2000, 3),
                                             (2000, 4)])
def test_on_the_tpcc_stream_it_is_the_reference_for_range_lists(
        warehouses, seed):
    plain = reference_ranges.RangeHistory()
    fast = reference_prefix.PrefixHistory(tpcc.PREFIX_LEN)
    conflicts = total = 0
    for pairs, version, oldest in tpcc_batches(warehouses, seed):
        want = reference_ranges.range_verdicts(plain, pairs, version, oldest)
        assert reference_prefix.prefix_verdicts(
            fast, pairs, version, oldest) == want
        conflicts += want.count(reference_ranges.CONFLICT)
        total += len(want)
    share = conflicts / total
    assert share > 0.3 if warehouses == 8 else 0.02 < share < 0.3
    assert (reference_prefix.COMMITTED, reference_prefix.CONFLICT,
            reference_prefix.TOO_OLD) == (
        reference_ranges.COMMITTED, reference_ranges.CONFLICT,
        reference_ranges.TOO_OLD)
    # the prefixes did the work: new-order keys by district, lines by order
    assert {p[:1] for p in fast.by_prefix} == {b"N", b"P"}
    assert max(len(v) for v in fast.by_prefix.values()) < len(fast.points) / 4


@pytest.mark.parametrize("warehouses,seed", [(8, 5), (2000, 6)])
def test_on_the_tpcc_stream_it_agrees_with_the_cpp_skiplist(warehouses, seed):
    """Not how `correct` is decided (the reference imports nothing of the
    program): a check of the reference itself against upstream's structure
    on this stream's point writes and true range reads."""
    from foundationdb_tpu.core.types import KeyRange, TxnConflictInfo
    from foundationdb_tpu.models.cpu_conflict_set import CPUSkipListConflictSet

    cs = CPUSkipListConflictSet()
    history = reference_prefix.PrefixHistory(tpcc.PREFIX_LEN)
    seen = set()
    for pairs, version, oldest in tpcc_batches(warehouses, seed):
        txns = [TxnConflictInfo(rv, [KeyRange(*r) for r in reads],
                                [KeyRange(*w) for w in writes])
                for rv, reads, writes in pairs]
        got = [int(v) for v in cs.resolve(txns, version, oldest)]
        assert got == reference_prefix.prefix_verdicts(
            history, pairs, version, oldest)
        seen.update(got)
    assert {reference_prefix.COMMITTED, reference_prefix.CONFLICT} <= seen


def _key(rng) -> bytes:
    return bytes((97 + rng.integers(0, 5, int(rng.integers(1, 4)))).astype(
        np.uint8))


def _range(rng):
    a, b = sorted([_key(rng), _key(rng)])
    return (a, a + b"\x00") if rng.random() < 0.5 else (a, b)


@pytest.mark.parametrize("prefix_len", [{}, {ord("a"): 1, ord("c"): 2}],
                         ids=["no prefix known", "ranges cross prefixes"])
@pytest.mark.parametrize("seed", [21, 22])
def test_it_is_exact_on_ranges_that_lie_in_no_prefix(seed, prefix_len):
    """Reads AND writes of point and true ranges over a tiny alphabet,
    most of them across the prefixes it was told of or in none: slower,
    and the same verdicts."""
    rng = np.random.default_rng(seed)
    plain = reference_ranges.RangeHistory()
    fast = reference_prefix.PrefixHistory(prefix_len)
    seen = set()
    for n in range(30):
        version = (n + 1) * 100
        oldest = max(0, version - 1500)
        pairs = [(int(rng.integers(max(0, version - 2000), version)),
                  [_range(rng) for _ in range(int(rng.integers(0, 13)))],
                  [_range(rng) for _ in range(int(rng.integers(0, 5)))])
                 for _ in range(int(rng.integers(1, 20)))]
        want = reference_ranges.range_verdicts(plain, pairs, version, oldest)
        assert reference_prefix.prefix_verdicts(
            fast, pairs, version, oldest) == want
        seen.update(want)
    assert seen == {0, 1, 2}


def pt(k: bytes):
    return (k, k + b"\x00")


def test_a_range_read_sees_its_prefixs_writes_and_no_neighbours():
    h = reference_prefix.PrefixHistory({ord("N"): 3})
    assert reference_prefix.prefix_verdicts(h, [
        (5, [], [pt(b"N\x01\x01\x07"), pt(b"N\x01\x02\x01"), pt(b"Nx")]),
    ], 10, 0) == [0]
    got = reference_prefix.prefix_verdicts(h, [
        (5, [(b"N\x01\x01", b"N\x01\x01\x07\x00")], []),  # head, found
        (5, [(b"N\x01\x01", b"N\x01\x01\x07")], []),      # ends before it
        (5, [(b"N\x01\x01", b"N\x01\x02")], []),          # the whole prefix
        (5, [(b"N\x01\x03", b"N\x01\x04")], []),          # a quiet one
        (5, [(b"N\x01\x00", b"N\x01\x03")], []),          # across prefixes
        (15, [(b"N\x01\x01", b"N\x01\x02")], []),         # read after it
        (5, [(b"N", b"O")], []),                          # shorter than any
    ], 20, 0)
    assert got == [1, 0, 1, 0, 1, 0, 1]
    assert h.by_prefix == {b"N\x01\x01": [b"N\x01\x01\x07"],
                           b"N\x01\x02": [b"N\x01\x02\x01"]}


def test_a_batchs_earlier_accepted_writes_count_and_rejected_ones_do_not():
    h = reference_prefix.PrefixHistory(tpcc.PREFIX_LEN)
    head = tpcc._WD(b"N", 1, 1)
    order = tpcc._WDO(b"N", 1, 1, 2101)
    got = reference_prefix.prefix_verdicts(h, [
        (5, [(head, order + b"\x00")], [pt(order)]),     # a delivery
        (5, [(head, order + b"\x00")], [pt(b"zz")]),     # a second: loses
        (5, [pt(b"zz")], []),                            # it painted nothing
        (1, [(b"e", b"e")], [pt(b"c"), (b"k", b"k")]),   # empty ranges
    ], 10, 0)
    assert got == [0, 1, 0, 0]
    assert h.newest(*pt(b"zz")) == -1 and h.newest(*pt(order)) == 10
    assert reference_prefix.prefix_verdicts(
        h, [(1, [pt(b"a")], []), (1, [], [pt(b"b")])], 1000, 500) == [2, 0]


def test_a_stream_with_one_verdict_flipped_is_not_correct():
    ref = []
    h = reference_prefix.PrefixHistory(tpcc.PREFIX_LEN)
    for pairs, version, oldest in tpcc_batches(40, 9, n_batches=10):
        ref.append(reference_prefix.prefix_verdicts(h, pairs, version, oldest))
    got = [list(b) for b in ref]
    got[7][3] ^= 1
    assert sum(a != b for g, r in zip(got, ref) for a, b in zip(g, r)) == 1
