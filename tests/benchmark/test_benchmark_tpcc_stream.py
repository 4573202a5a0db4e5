"""The TPC-C stream (benchmark/lib/tpcc.py): the mix a deck holds, NURand as
clause 2.1.6 writes it, every key inside the engine's 32 bytes, the
districts' two counters, the widths the clauses reckon and how evenly a
batch holds them, and the same bytes from the same seed."""

import collections
import struct

import numpy as np
import pytest

from benchmark.lib import tpcc

BATCH = 512


def deal(warehouses=228_200, seed=7, batches=24, batch=BATCH) -> tpcc.Deal:
    return tpcc.Deal(warehouses, [2302, seed], batches * batch)


def rows_of(reads, writes) -> int:
    return max(1, -(-len(reads) // 8), -(-len(writes) // 8))


def is_point(r) -> bool:
    return r[1] == r[0] + b"\x00"


def test_a_deck_holds_the_mix_and_a_resolver_is_sent_92_of_its_100_cards():
    assert len(tpcc.DECK) == 100
    assert collections.Counter(tpcc.DECK) == {
        tpcc.NEW_ORDER: 45, tpcc.PAYMENT: 43, tpcc.DELIVERY: 4,
        tpcc.READ_ONLY: 8}
    d = deal(batches=20)
    for n in range(20):
        d.batch(n, BATCH)
    sent = np.bincount(d.kinds, minlength=3)
    dropped = d.dropped()
    cards = 20 * BATCH + sum(dropped.values())
    # whole decks but for the one the last batch ends in
    assert abs(sent[tpcc.PAYMENT] / cards - 0.43) < 0.002
    assert abs(sent[tpcc.DELIVERY] / cards - 0.04) < 0.001
    assert abs(dropped["read_only_dropped"] / cards - 0.08) < 0.002
    new_orders = sent[tpcc.NEW_ORDER] + dropped["rolled_back_dropped"]
    assert abs(new_orders / cards - 0.45) < 0.002
    # one new-order in a hundred rolls back at the client (2.4.1.4)
    assert abs(dropped["rolled_back_dropped"] / new_orders - 0.01) < 0.001


@pytest.mark.parametrize("a,x,y", [(1023, 1, 3000), (8191, 1, 100_000),
                                   (255, 0, 999)])
def test_nurand_is_clause_2_1_6s(a, x, y):
    assert (tpcc.A_CUSTOMER, tpcc.A_ITEM, tpcc.A_LAST) == (1023, 8191, 255)
    rng = np.random.default_rng(3)
    got = tpcc.nurand(rng, a, x, y, 123, 200_000)
    assert got.min() >= x and got.max() <= y
    # non-uniform: the OR of two draws favours ids with many bits set, so
    # the most drawn tenth of the ids takes far more than a tenth
    counts = np.sort(np.bincount(got - x, minlength=y - x + 1))[::-1]
    assert counts[: (y - x + 1) // 10].sum() > 0.2 * len(got)
    # and it is the formula: the same draws, written out
    rng = np.random.default_rng(3)
    first = rng.integers(0, a + 1, 200_000)
    second = rng.integers(x, y + 1, 200_000)
    assert got[:50].tolist() == [
        ((int(p) | int(q)) + 123) % (y - x + 1) + x
        for p, q in zip(first[:50], second[:50])]


def test_every_key_is_inside_32_bytes_and_byte_order_is_id_order():
    d = deal(batches=4)
    tables = collections.Counter()
    for n in range(4):
        for _kind, reads, writes in d.batch(n, BATCH):
            for b, e in reads + writes:
                assert 5 <= len(b) <= 16 and len(e) <= 17 and b < e
                tables[b[:1]] += 1
    assert d.longest_key == 17
    assert set(tables) == set(b"WDCLHNOPIS"[i:i + 1] for i in range(10))
    # big-endian fixed-width ids: order 255 sorts before order 256, line
    # 9 before line 10, and a prefix covers exactly its own keys
    key = tpcc._WDOL(b"P", 70_000, 10, 3001, 9)
    assert key < tpcc._WDOL(b"P", 70_000, 10, 3001, 10) \
        < tpcc._WDOL(b"P", 70_000, 10, 3002, 1)
    prefix = tpcc._WDO(b"P", 70_000, 10, 3001)
    assert prefix < key < tpcc.strinc(prefix) \
        <= tpcc._WDOL(b"P", 70_000, 10, 3002, 1)
    assert tpcc._WDO(b"N", 1, 1, 255) < tpcc._WDO(b"N", 1, 1, 256)
    assert tpcc.strinc(b"N\x00\x00\x00\x01\xff") == b"N\x00\x00\x00\x02"
    assert tpcc.PREFIX_LEN == {ord("N"): 6, ord("P"): 10, ord("L"): 8}


def test_the_profiles_are_the_clauses():
    d = deal(batches=8)
    seen = collections.Counter()
    by_name = remote = payments = 0
    for n in range(8):
        for kind, reads, writes in d.batch(n, BATCH):
            seen[kind] += 1
            true = [r for r in reads if not is_point(r)]
            assert all(is_point(w) for w in writes)
            if kind == tpcc.NEW_ORDER:
                n_lines = (len(reads) - 3) // 2
                assert 5 <= n_lines <= 15 and not true
                assert len(reads) == len(writes) == 3 + 2 * n_lines
                assert [r[0][:1] for r in reads[:3]] == [b"W", b"D", b"C"]
                assert [w[0][:1] for w in writes[-n_lines - 2:]] == (
                    [b"O", b"N"] + [b"P"] * n_lines)
                assert 2 <= rows_of(reads, writes) <= 5
            elif kind == tpcc.PAYMENT:
                payments += 1
                by_name += len(true)
                assert len(reads) == 3 + len(true) and len(writes) == 4
                assert all(r[0][:1] == b"L" and len(r[0]) == 8 for r in true)
                assert writes[-1][0][:1] == b"H"
                # a remote customer: another warehouse than the payment's
                remote += reads[-1][0][1:5] != reads[-3][0][1:5]
                assert rows_of(reads, writes) == 1
            else:
                assert len(reads) == 40 and len(true) == 20
                assert 80 <= len(writes) <= 180
                assert 10 <= rows_of(reads, writes) <= 23
                # ten districts of one warehouse, each: the head of its
                # new-order prefix, the order, its lines' prefix, a customer
                assert [r[0][:1] for r in reads[:4]] == [b"N", b"O", b"P",
                                                         b"C"]
                assert len({r[0][1:5] for r in reads}) == 1
                assert sorted({r[0][5] for r in reads[::4]}) == list(
                    range(1, 11))
    assert by_name / payments == pytest.approx(0.60, abs=0.01)
    assert remote / payments == pytest.approx(0.15, abs=0.01)
    assert seen[tpcc.NEW_ORDER] > seen[tpcc.PAYMENT] > seen[tpcc.DELIVERY]


def test_what_the_clauses_reckon_a_transaction():
    d = deal(batches=40)
    rows = ranges = 0
    for n in range(40):
        for _kind, reads, writes in d.batch(n, BATCH):
            rows += rows_of(reads, writes)
            ranges += len(reads) + len(writes)
    sent = 40 * BATCH
    assert ranges == d.ranges
    assert ranges / sent == pytest.approx(33.4, rel=0.01)
    assert rows / sent == pytest.approx(2.84, rel=0.01)
    assert d.true_ranges / sent == pytest.approx(1.15, rel=0.02)
    assert ranges / (rows * 16) == pytest.approx(0.73, abs=0.01)


def test_a_batchs_rows_are_within_five_percent_of_the_mean():
    d = deal(batches=60, seed=11)
    rows, kinds = [], []
    for n in range(60):
        dealt = d.batch(n, BATCH)
        rows.append(sum(rows_of(r, w) for _k, r, w in dealt))
        kinds.append(np.bincount([k for k, _r, _w in dealt], minlength=3))
    rows, kinds = np.array(rows), np.array(kinds)
    assert np.abs(rows / rows.mean() - 1).max() < 0.05
    # 5.6 decks a batch, each exact: what moves is where the cuts fall
    assert np.abs(kinds[:, tpcc.NEW_ORDER] - 250).max() <= 12
    assert np.abs(kinds[:, tpcc.PAYMENT] - 239).max() <= 12
    assert np.abs(kinds[:, tpcc.DELIVERY] - 22).max() <= 5


def test_order_numbers_rise_by_one_a_district_and_delivery_takes_the_oldest():
    """At two warehouses every district is met again and again: a
    new-order takes its district's next number, a delivery the oldest
    order not delivered, starting with the load's 2,101."""
    d = deal(warehouses=2, batches=40, batch=64)
    next_order = collections.defaultdict(lambda: tpcc.FIRST_NEW_ORDER)
    oldest = collections.defaultdict(lambda: tpcc.FIRST_UNDELIVERED)
    deliveries = 0
    for n in range(40):
        for kind, reads, writes in d.batch(n, 64):
            if kind == tpcc.NEW_ORDER:
                _t, w, dd, o = struct.unpack(">cIBI", writes[-1][0][:10])
                assert o == next_order[w, dd]
                next_order[w, dd] += 1
            elif kind == tpcc.DELIVERY:
                deliveries += 1
                for i in range(0, 40, 4):
                    head, order, lines, _customer = reads[i:i + 4]
                    _t, w, dd, o = struct.unpack(">cIBI", order[0])
                    assert o == oldest[w, dd]
                    oldest[w, dd] += 1
                    assert head == (tpcc._WD(b"N", w, dd),
                                    tpcc._WDO(b"N", w, dd, o) + b"\x00")
                    assert lines == (tpcc._WDO(b"P", w, dd, o),
                                     tpcc.strinc(tpcc._WDO(b"P", w, dd, o)))
                    assert 5 <= tpcc.loaded_order(w, dd, o)[0] <= 15
    assert deliveries > 50 and min(next_order.values()) > 3030
    assert max(oldest.values()) < min(next_order.values())


def test_a_delivery_of_a_generated_order_writes_that_orders_lines():
    d = deal(warehouses=1, batches=1, batch=64)
    d.undelivered[(1, 3)] = tpcc.FIRST_NEW_ORDER  # the backlog is gone
    d.next_order[(1, 3)] = tpcc.FIRST_NEW_ORDER + 1
    d.orders[(1, 3, tpcc.FIRST_NEW_ORDER)] = (7, 42)
    d.undelivered[(1, 4)] = d.next_order[(1, 4)] = 3500  # nothing to deliver
    t = int(np.flatnonzero(d.kinds == tpcc.DELIVERY)[0])
    reads, writes = d._delivery(t)
    mine = [w[0] for w in writes if w[0][5] == 3]
    assert mine == [tpcc._WDO(b"N", 1, 3, 3001), tpcc._WDO(b"O", 1, 3, 3001)] \
        + [tpcc._WDOL(b"P", 1, 3, 3001, ol) for ol in range(1, 8)] \
        + [tpcc._WDC(b"C", 1, 3, 42)]
    # an empty district: the getRange read its whole prefix, nothing else
    assert [r for r in reads if r[0][5:6] == b"\x04"] == [
        (tpcc._WD(b"N", 1, 4), tpcc.strinc(tpcc._WD(b"N", 1, 4)))]
    assert not [w for w in writes if w[0][5] == 4]
    assert len(reads) == 9 * 4 + 1


def test_the_same_seed_gives_the_same_bytes_and_a_replay_draws_nothing():
    a, b, c = deal(seed=5, batches=3), deal(seed=5, batches=3), \
        deal(seed=2 ** 31 + 6, batches=3)
    first = [a.batch(n, BATCH) for n in range(3)]
    assert first == [b.batch(n, BATCH) for n in range(3)]
    assert first[0] != c.batch(0, BATCH)
    again = a.replay()
    assert [again.batch(n, BATCH) for n in range(3)] == first
    assert (again.ranges, again.true_ranges) == (a.ranges, a.true_ranges)
    with pytest.raises(ValueError, match="out of turn"):
        a.batch(1, BATCH)
    with pytest.raises(ValueError, match="holds"):
        a.batch(3, BATCH)


def test_a_payment_by_name_takes_the_names_middle_customer():
    middle = tpcc.load_last_names()
    assert middle.shape == (1000,) and 1 <= middle.min() \
        and middle.max() <= 3000
    assert (middle == tpcc.load_last_names()).all()
    d = deal(batches=2)
    for n in range(2):
        for kind, reads, _writes in d.batch(n, BATCH):
            if kind == tpcc.PAYMENT and not is_point(reads[0]):
                _t, _w, _d, name = struct.unpack(">cIBH", reads[0][0])
                _t, _w, _d, c = struct.unpack(">cIBH", reads[-1][0])
                assert c == middle[name]
