"""The plain references (benchmark/lib/reference.py) and the generator's
data (benchmark/lib/ycsb.py): each reference shown to FAIL on
the fault it is there to catch, and the point-key reference shown to agree
with the program's C++ skiplist where both apply."""

import numpy as np
import pytest

from benchmark.lib import hist, reference, ycsb


@pytest.fixture(scope="module")
def records():
    return ycsb.Records(200, seed=7)


def _acked(records, increments: dict) -> reference.CounterReplay:
    replay = reference.CounterReplay(records)
    for i, n in increments.items():
        for _ in range(n):
            replay.ack(i)
    return replay


def test_a_sound_history_is_correct(records):
    replay = _acked(records, {3: 2, 9: 1})
    ids = replay.touched()
    values = [records.value(i, replay.acked[i]) for i in ids]
    assert replay.count_wrong(ids, values) == (0, None)


def test_one_lost_increment_is_not_correct(records):
    replay = _acked(records, {3: 2, 9: 1})
    wrong, why = replay.count_wrong([3, 9], [records.value(3, 1),
                                             records.value(9, 1)])
    assert wrong == 1 and "holds 1 increments, 2 were acknowledged" in why


def test_a_replica_missing_one_acknowledged_write_is_not_correct(records):
    replay = _acked(records, {3: 2, 9: 1})
    good = [records.value(3, 2), records.value(9, 1)]
    stale = [records.value(3, 2), records.value(9, 0)]
    absent = [records.value(3, 2), None]
    assert replay.count_wrong([3, 9], good)[0] == 0
    assert replay.count_wrong([3, 9], stale)[0] == 1
    assert replay.count_wrong([3, 9], absent) == (
        1, f"record 9 ({records.keys[9]!r}): missing")


def test_an_unknown_result_widens_one_key_by_one_and_no_more(records):
    replay = _acked(records, {3: 2})
    replay.unknown_result(3)
    assert replay.wrong(3, records.value(3, 2)) is None
    assert replay.wrong(3, records.value(3, 3)) is None
    assert replay.wrong(3, records.value(3, 4)) is not None
    assert replay.wrong(3, records.value(3, 1)) is not None


def test_a_record_of_the_wrong_length_or_bytes_is_not_correct(records):
    replay = _acked(records, {})
    assert replay.wrong(5, records.value(5, 0)) is None
    assert "bytes, not 1000" in replay.wrong(5, records.value(5, 0)[:-1])
    torn = bytearray(records.value(5, 0))
    torn[500] ^= 1
    assert "differ" in replay.wrong(5, bytes(torn))


def _stream(seed: int, n_batches=40, batch=32, n_keys=64):
    rng = np.random.default_rng(seed)
    for n in range(n_batches):
        keys = [ycsb.record_key(int(i))
                for i in rng.integers(0, n_keys, batch)]
        version = (n + 1) * 1000
        yield keys, max(0, version - 3000), version, max(0, version - 20000)


def _reference_verdicts(seed: int) -> list:
    last_write: dict = {}
    return [reference.point_verdicts(last_write, keys, [rv] * len(keys),
                                     version, oldest)
            for keys, rv, version, oldest in _stream(seed)]


def test_the_point_reference_finds_conflicts_and_commits():
    flat = [v for b in _reference_verdicts(1) for v in b]
    assert reference.CONFLICT in flat and reference.COMMITTED in flat


def test_a_stream_with_one_verdict_flipped_is_not_correct():
    ref = _reference_verdicts(2)
    got = [list(b) for b in ref]
    got[7][3] ^= 1
    wrong = sum(a != b for g, r in zip(got, ref) for a, b in zip(g, r))
    assert wrong == 1


def test_a_read_below_the_window_is_too_old():
    out = reference.point_verdicts({}, [b"k"], [5], version=100,
                                   oldest_version=10)
    assert out == [reference.TOO_OLD]


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_the_point_reference_agrees_with_the_cpp_skiplist(seed):
    """Not how `correct` is decided (the reference imports nothing of the
    program); a check of the reference itself against upstream's structure."""
    from foundationdb_tpu.core.types import KeyRange, TxnConflictInfo
    from foundationdb_tpu.models.cpu_conflict_set import CPUSkipListConflictSet

    cs = CPUSkipListConflictSet()
    for (keys, rv, version, oldest), ref in zip(_stream(seed),
                                                _reference_verdicts(seed)):
        txns = [TxnConflictInfo(rv, [KeyRange(k, k + b"\x00")],
                                [KeyRange(k, k + b"\x00")]) for k in keys]
        assert [int(v) for v in cs.resolve(txns, version, oldest)] == ref


def _java_fnvhash64(val: int) -> int:
    """`Utils.fnvhash64` transcribed line by line, in Java's signed longs."""
    def signed(x):
        x &= (1 << 64) - 1
        return x - (1 << 64) if x >= 1 << 63 else x

    hashval = signed(0xCBF29CE484222325)
    for _ in range(8):
        octet = val & 0x00FF
        val = val >> 8
        hashval = hashval ^ octet
        hashval = signed(hashval * 1099511628211)
    return abs(hashval)


def _java_zipfian_next(u: float) -> int:
    """`ZipfianGenerator.nextLong(itemcount)` as ScrambledZipfianGenerator
    builds it: 10,000,000,000 items, constant 0.99, the published zetan."""
    items, theta, zetan = 10_000_000_000, 0.99, 26.46902820178302
    zeta2theta = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1 - (2.0 / items) ** (1 - theta)) / (1 - zeta2theta / zetan)
    uz = u * zetan
    if uz < 1.0:
        return 0
    if uz < 1.0 + 0.5 ** theta:
        return 1
    return int(items * (eta * u - eta + 1) ** alpha)


def test_keys_and_records_have_ycsbs_shapes(records):
    assert int(ycsb.fnvhash64([0])[0]) == 2 ** 64 - 0xA8C7F832281A39C5
    some = [0, 1, 255, 256, 49_999, 2 ** 31 + 5, 9_999_999_999]
    assert ycsb.fnvhash64(some).tolist() == [_java_fnvhash64(v) for v in some]
    assert len(set(records.keys)) == records.count
    assert records.keys[17] == b"user%d" % _java_fnvhash64(17)
    assert len(records.value(17)) == 1000
    assert ycsb.record_keys([5, 5, 199]) == {5: records.keys[5],
                                             199: records.keys[199]}


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_request_distribution_is_ycsbs_generator_as_written(seed):
    u = np.random.default_rng(seed).random(2000)
    u[:4] = [0.0, 1 / 26.46902820178302, 0.0568, 0.999999999]
    assert ycsb.zipfian_ranks(u).tolist() == [_java_zipfian_next(float(x))
                                              for x in u]
    # ScrambledZipfianGenerator.nextValue: fnvhash64(rank) % itemcount
    _kinds, items = ycsb.plan(777, 1024, 1.0, seed, base_seed=seed)
    offset = np.random.default_rng(seed).random(1)[0]
    want = sorted(_java_fnvhash64(_java_zipfian_next((j + offset) / 1024))
                  % 777 for j in range(1024))
    assert sorted(items.tolist()) == want


def test_every_seed_gets_the_same_work_in_another_order():
    k1, a = ycsb.plan(1000, 5000, 0.5, seed=1, base_seed=9)
    k2, b = ycsb.plan(1000, 5000, 0.5, seed=2 ** 31 + 5, base_seed=9)
    assert not np.array_equal(a, b)
    again = ycsb.plan(1000, 5000, 0.5, 1, 9)
    assert np.array_equal(a, again[1]) and np.array_equal(k1, again[0])
    # the same records and the same mix in every stretch of the run
    hot = np.bincount(a).argmax()
    assert len(a) == 5120  # whole blocks
    for lo in range(0, 5120, 1024):
        block = slice(lo, lo + 1024)
        assert np.array_equal(np.sort(a[block]), np.sort(b[block]))
        assert 38 <= int((a[block] == hot).sum()) <= 44  # 38.7 and strays
        assert int((k1[block] == ycsb.RMW).sum()) == 512
        hot_rmw = int(((a[block] == hot) & (k1[block] == ycsb.RMW)).sum())
        assert abs(2 * hot_rmw - int((a[block] == hot).sum())) <= 1
        assert np.array_equal(np.sort(a[block][k1[block] == ycsb.RMW]),
                              np.sort(b[block][k2[block] == ycsb.RMW]))


def test_the_zipf_skew_is_ycsbs():
    _kinds, items = ycsb.plan(50000, 200000, 1.0, seed=5, base_seed=6)
    share = np.sort(np.bincount(items, minlength=50000))[::-1] / items.size
    assert 0.0375 < share[0] < 0.0381  # 1 / ZETAN = 0.03778
    assert 0.0186 < share[1] < 0.0194  # 0.5 ** 0.99 / ZETAN = 0.01902
    # the far ranks, over half of the mass, are spread by the hash: no
    # bounded Zipf over 50,000 items (hottest 8.3 %) does this
    assert np.unique(items).size > 40000
    assert 0.0022 < float((share ** 2).sum()) < 0.0028


def test_a_window_is_the_difference_of_two_cumulative_dumps():
    def dump(samples):
        h = hist.LatencyHistogram()
        for ms in samples:
            h.counts[np.searchsorted(h._EDGES, ms)] += 1
            h.sum_ms += ms
        return {"stages": {"resolve_wait": {
            "bins": [[int(i), int(n)] for i, n in enumerate(h.counts) if n],
            "sum_ms": h.sum_ms, "max_ms": max(samples)}}}

    before = [dump([1.0, 2.0]), dump([4.0])]
    after = [dump([1.0, 2.0, 10.0, 30.0]), dump([4.0, 20.0])]
    window = hist.stages_between(before, after)["resolve_wait"]
    assert window.count == 3 and window.mean() == pytest.approx(20.0)
    assert 29.0 < window.percentile(99) < 32.0
    with pytest.raises(ValueError):
        hist.stages_between(after, before)
    assert hist.percentile_of([5, 1, 3, 2, 4], 95) == 5
    assert hist.percentile_of(range(1, 101), 95) == 95
