"""The plain reference for lists of ranges
(benchmark/lib/reference_ranges.py): shown to agree with the point
reference that the accepted cells are judged by where both apply, with the
program's C++ skiplist on point and true ranges mixed, and to FAIL on the
faults it is there to catch."""

import numpy as np
import pytest

from benchmark.lib import reference, reference_ranges, ycsb


def _point_stream(seed: int, n_batches=40, batch=32, n_keys=64):
    rng = np.random.default_rng(seed)
    for n in range(n_batches):
        keys = [ycsb.record_key(int(i))
                for i in rng.integers(0, n_keys, batch)]
        version = (n + 1) * 1000
        yield keys, max(0, version - 3000), version, max(0, version - 20000)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_on_a_point_stream_it_is_the_point_reference(seed):
    last_write: dict = {}
    history = reference_ranges.RangeHistory()
    seen = set()
    for keys, rv, version, oldest in _point_stream(seed):
        want = reference.point_verdicts(last_write, keys, [rv] * len(keys),
                                        version, oldest)
        got = reference_ranges.range_verdicts(
            history, [(rv, [(k, k + b"\x00")], [(k, k + b"\x00")])
                      for k in keys], version, oldest)
        assert got == want
        seen.update(got)
    assert {reference.CONFLICT, reference.COMMITTED} <= seen
    assert (reference_ranges.COMMITTED, reference_ranges.CONFLICT,
            reference_ranges.TOO_OLD) == (
        reference.COMMITTED, reference.CONFLICT, reference.TOO_OLD)


def _key(rng) -> bytes:
    return bytes((97 + rng.integers(0, 5, int(rng.integers(1, 4)))).astype(
        np.uint8))


def _range(rng):
    a, b = sorted([_key(rng), _key(rng)])
    return (a, a + b"\x00") if rng.random() < 0.5 else (a, b)


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_it_agrees_with_the_cpp_skiplist_on_lists_of_ranges(seed):
    """Not how `correct` is decided (the reference imports nothing of the
    program); a check of the reference itself against upstream's structure:
    up to 12 reads and 4 writes a transaction, point and true ranges."""
    from foundationdb_tpu.core.types import KeyRange, TxnConflictInfo
    from foundationdb_tpu.models.cpu_conflict_set import CPUSkipListConflictSet

    rng = np.random.default_rng(seed)
    cs, history = CPUSkipListConflictSet(), reference_ranges.RangeHistory()
    for n in range(30):
        version = (n + 1) * 100
        oldest = max(0, version - 1500)
        pairs = [(int(rng.integers(max(0, version - 2000), version)),
                  [_range(rng) for _ in range(int(rng.integers(0, 13)))],
                  [_range(rng) for _ in range(int(rng.integers(0, 5)))])
                 for _ in range(int(rng.integers(1, 20)))]
        txns = [TxnConflictInfo(rv, [KeyRange(*r) for r in reads],
                                [KeyRange(*w) for w in writes])
                for rv, reads, writes in pairs]
        assert [int(v) for v in cs.resolve(txns, version, oldest)] == \
            reference_ranges.range_verdicts(history, pairs, version, oldest)


def pt(k: bytes):
    return (k, k + b"\x00")


def test_any_one_of_many_reads_is_enough_and_none_is_widened():
    h = reference_ranges.RangeHistory()
    assert reference_ranges.range_verdicts(
        h, [(5, [], [pt(b"c"), (b"m", b"p")])], 10, 0) == [0]
    quiet = [pt(b"a%d" % i) for i in range(8)]
    got = reference_ranges.range_verdicts(h, [
        (5, quiet + [pt(b"c")], []),       # the ninth read meets the write
        (5, quiet + [pt(b"b"), pt(b"d")], []),   # its neighbours do not
        (5, quiet + [(b"a", b"d")], []),   # a true range over the point
        (5, quiet + [pt(b"n")], []),       # a point inside the true range
        (5, quiet + [pt(b"p")], []),       # its end is not in it
        (15, quiet + [pt(b"c")], []),      # read after the write
    ], 20, 0)
    assert got == [1, 0, 1, 1, 0, 0]


def test_a_batchs_earlier_accepted_writes_count_and_rejected_ones_do_not():
    h = reference_ranges.RangeHistory()
    got = reference_ranges.range_verdicts(h, [
        (5, [pt(b"r")], [pt(b"x"), pt(b"y")]),
        (5, [pt(b"q"), pt(b"y")], [pt(b"z")]),  # reads the first one's write
        (5, [pt(b"z")], []),                    # the second painted nothing
        (5, [], [pt(b"w")]),
    ], 10, 0)
    assert got == [0, 1, 0, 0]
    assert h.newest(*pt(b"z")) == -1 and h.newest(*pt(b"y")) == 10


def test_too_old_needs_a_read_and_empty_ranges_take_no_part():
    h = reference_ranges.RangeHistory()
    got = reference_ranges.range_verdicts(h, [
        (1, [pt(b"a")], []),
        (1, [], [pt(b"b")]),
        (1, [(b"e", b"e")], [pt(b"c"), (b"k", b"k")]),
    ], 1000, 500)
    assert got == [2, 0, 0]
    assert h.ranges == []


def test_a_stream_with_one_verdict_flipped_is_not_correct():
    rng = np.random.default_rng(4)
    h = reference_ranges.RangeHistory()
    ref = []
    for n in range(10):
        version = (n + 1) * 100
        ref.append(reference_ranges.range_verdicts(h, [
            (version - 300, [_range(rng) for _ in range(9)],
             [_range(rng) for _ in range(2)]) for _ in range(16)],
            version, 0))
    got = [list(b) for b in ref]
    got[7][3] ^= 1
    assert sum(a != b for g, r in zip(got, ref) for a, b in zip(g, r)) == 1
