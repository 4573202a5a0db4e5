"""TPC-C's mix through the engine: new-orders (2-5 rows), payments (1 row,
some with a true range read) and deliveries (10-23 rows, 20 true range
reads) side by side in every batch, judged exactly on every path.

The benchmark's plain reference for this stream
(benchmark/lib/reference_prefix.py) decides; held to it here, on the TPC-C
generator's own seeded streams at a small size: the engine through
`resolve`, through `resolve_async` collected one batch behind (as the
served role holds it), through the wire path, the served `Resolver` role
(over TCP it is tests/benchmark/test_benchmark_rehearsal_tpcc.py), and the
mesh engine (parallel/sharded_resolver.py). And the two packers lay a mixed
batch out alike, bit for bit, with a 23-row transaction that does not fit
what is left of a dispatch.
"""

import numpy as np
import pytest

from benchmark.lib import reference_prefix, tpcc
from foundationdb_tpu.core.types import KeyRange, TxnConflictInfo
from foundationdb_tpu.models.conflict_set import (
    TPUConflictSet,
    encode_resolve_batch,
)
from foundationdb_tpu.runtime.flow import Loop
from foundationdb_tpu.runtime.resolver import Resolver
from tests.test_wide_txn_parity import same_tensors

# The tiny rehearsal cell's engine, so a worker compiles these programs once.
ENGINE = dict(capacity=1 << 16, dict_capacity=1 << 16, batch_size=64,
              max_read_ranges=8, max_write_ranges=8, max_key_bytes=32)
STEP, LAG, WINDOW = 1000, 3, 12
BATCH = 64


def txns_of(pairs) -> list:
    return [TxnConflictInfo(rv, [KeyRange(*r) for r in reads],
                            [KeyRange(*w) for w in writes])
            for rv, reads, writes in pairs]


def stream(warehouses: int, seed: int, n_batches: int = 12):
    """(pairs, commit version, oldest version) a batch of 64 transactions
    dealt 45 : 43 : 4, read version three batches behind."""
    deal = tpcc.Deal(warehouses, [2302, seed], n_batches * BATCH)
    for n in range(n_batches):
        cv = (n + 1) * STEP
        rv = max(0, cv - LAG * STEP)
        yield ([(rv, reads, writes)
                for _kind, reads, writes in deal.batch(n, BATCH)],
               cv, max(0, cv - WINDOW * STEP))


def reference(warehouses: int, seed: int) -> list:
    history = reference_prefix.PrefixHistory(tpcc.PREFIX_LEN)
    return [reference_prefix.prefix_verdicts(history, pairs, cv, oldest)
            for pairs, cv, oldest in stream(warehouses, seed)]


def resting_on_a_true_range(warehouses: int, seed: int) -> int:
    """The reference's verdicts that differ once every true range is read
    as its begin's point (a history of its own): what holds a path above
    to judging an INTERVAL."""
    history = reference_prefix.PrefixHistory(tpcc.PREFIX_LEN)
    narrowed = [reference_prefix.prefix_verdicts(
        history, reference_prefix.reads_as_points(pairs), cv, oldest)
        for pairs, cv, oldest in stream(warehouses, seed)]
    return sum(a != b for want, got in zip(reference(warehouses, seed),
                                           narrowed)
               for a, b in zip(want, got))


def through_resolve(cs, batches):
    for pairs, cv, oldest in batches:
        yield cs.resolve(txns_of(pairs), cv, oldest)


def through_resolve_async(cs, batches):
    """One batch on the device while the next is packed: the collector of
    batch N is read after batch N+1 was dispatched (runtime/resolver.py
    `_dispatch_entry`)."""
    held = None
    for pairs, cv, oldest in batches:
        collect = cs.resolve_async(txns_of(pairs), cv, oldest)
        collect.enqueue_reading()
        if held is not None:
            yield held()
            assert held.reading()[1] is False  # no overflow behind it
        held = collect
    yield held()


def through_the_wire(cs, batches):
    for pairs, cv, oldest in batches:
        yield cs.resolve_wire(encode_resolve_batch(txns_of(pairs)), cv,
                              oldest)


def through_the_role(cs, batches):
    loop = Loop(seed=1)
    role, prev = Resolver(loop, cs), 0
    sent = true = rows = 0
    for pairs, cv, oldest in batches:
        verdicts, _conf, fail_safe, _wave = loop.run(role.resolve(
            prev, cv, txns_of(pairs), oldest_version=oldest))
        assert not fail_safe
        prev = cv
        for _rv, reads, writes in pairs:
            sent += len(reads) + len(writes)
            true += sum(1 for b, e in reads if e != b + b"\x00")
            rows += max(-(-len(reads) // 8), -(-len(writes) // 8))
        yield list(verdicts)
    m = loop.run(role.get_metrics())
    # the role's counters say what it was sent: every range, the true ones
    # as true ones, each in a slot of its own; no key was widened
    assert (m["ranges_received"], m["true_ranges_received"],
            m["slots_filled"], m["rows_dispatched"]) == (sent, true, sent,
                                                         rows)
    assert m["wide_txns"] > 0.4 * m["txns_resolved"]
    assert m["engine"]["keys_widened"] == 0
    assert 3 * m["batches_resolved"] <= m["engine"]["dispatches"] \
        <= 4 * m["batches_resolved"]


PATHS = {"resolve": through_resolve, "resolve_async": through_resolve_async,
         "wire": through_the_wire, "role": through_the_role}


@pytest.mark.parametrize("warehouses,seed,on_true_ranges",
                         [(8, 1, 14), (40, 1, 3), (2000, 2, 0)])
def test_the_streams_below_hold_an_engine_to_its_intervals(
        warehouses, seed, on_true_ranges):
    """Few enough warehouses and a delivery meets the one that emptied its
    district first; at the cell's 228,200 hardly ever, so interval
    exactness rests on these streams and not on the chip's."""
    assert resting_on_a_true_range(warehouses, seed) == on_true_ranges


@pytest.mark.parametrize("warehouses,seed", [(8, 1), (40, 1), (2000, 2)])
@pytest.mark.parametrize("path", sorted(PATHS))
def test_the_engine_judges_the_tpcc_mix_as_the_reference_does(
        path, warehouses, seed):
    want = reference(warehouses, seed)
    got = [[int(v) for v in verdicts] for verdicts in PATHS[path](
        TPUConflictSet(**ENGINE), stream(warehouses, seed))]
    assert got == want
    conflicts = sum(v.count(reference_prefix.CONFLICT) for v in want)
    total = sum(len(v) for v in want)
    assert 0 < conflicts < total
    if warehouses == 40:
        assert conflicts > 0.3 * total  # the issue's probe: 476 of 768


def test_the_mesh_engine_judges_the_tpcc_mix_as_the_reference_does():
    """parallel/sharded_resolver.py, four shards split by first byte: a
    delivery's prefix lies inside one table and so inside one shard here;
    the kernel clips whatever crosses a bound."""
    from foundationdb_tpu.parallel.sharded_resolver import ShardedConflictSet

    cs = ShardedConflictSet(n_shards=4, **ENGINE)
    want = reference(40, 3)
    got = [[int(v) for v in verdicts]
           for verdicts in through_resolve(cs, stream(40, 3))]
    assert got == want


def profile(kind: str, w: int, lines: int, o: int) -> TxnConflictInfo:
    """One transaction of the asked profile and width, keys as the
    generator makes them."""
    pt = tpcc.point
    if kind == "payment":
        name = tpcc._WDC(b"L", w, 1, 7)
        rows = [tpcc._W(b"W", w), tpcc._WD(b"D", w, 1),
                tpcc._WDC(b"C", w, 1, 9)]
        reads = [(name, tpcc.strinc(name))] + [pt(k) for k in rows]
        writes = [pt(k) for k in rows + [tpcc._HIST(b"H", w, 1, 9, o)]]
    elif kind == "new_order":
        stocks = [tpcc._WI(b"S", w, 100 + i) for i in range(lines)]
        reads = [pt(k) for k in [tpcc._W(b"W", w), tpcc._WD(b"D", w, 2),
                                 tpcc._WDC(b"C", w, 2, 5)]
                 + [tpcc._W(b"I", 100 + i) for i in range(lines)] + stocks]
        writes = [pt(k) for k in [tpcc._WD(b"D", w, 2)] + stocks
                  + [tpcc._WDO(b"O", w, 2, o), tpcc._WDO(b"N", w, 2, o)]
                  + [tpcc._WDOL(b"P", w, 2, o, ol + 1)
                     for ol in range(lines)]]
    else:  # a delivery of ten orders of `lines` lines each
        reads, writes = [], []
        for d in range(1, 11):
            head, found = tpcc._WD(b"N", w, d), tpcc._WDO(b"N", w, d, o)
            order_lines = tpcc._WDO(b"P", w, d, o)
            reads += [(head, found + b"\x00"), pt(tpcc._WDO(b"O", w, d, o)),
                      (order_lines, tpcc.strinc(order_lines)),
                      pt(tpcc._WDC(b"C", w, d, 11))]
            writes += [pt(found), pt(tpcc._WDO(b"O", w, d, o))] + [
                pt(tpcc._WDOL(b"P", w, d, o, ol + 1)) for ol in range(lines)
            ] + [pt(tpcc._WDC(b"C", w, d, 11))]
    return TxnConflictInfo(5, [KeyRange(*r) for r in reads],
                           [KeyRange(*w) for w in writes])


def test_both_packers_lay_a_mixed_batch_out_alike_bit_for_bit():
    """Dispatches of 32 rows. Three new-orders and a payment take 13; the
    23-row delivery (ten orders of 15 lines) does not fit the 19 left and
    opens the next dispatch, which a 5-row new-order and a payment fill to
    29; the 4-row new-order after them opens a third. No transaction is
    cut by a dispatch's end, and every dispatch holds another number of
    transactions."""
    cs = TPUConflictSet(**dict(ENGINE, capacity=1 << 12,
                               dict_capacity=1 << 13, batch_size=32))
    cs.base_version = 0
    txns = [profile("new_order", 1, 10, 3001), profile("new_order", 2, 12, 1),
            profile("new_order", 3, 13, 2), profile("payment", 4, 0, 3),
            profile("delivery", 5, 15, 2101),
            profile("new_order", 6, 15, 4), profile("payment", 7, 0, 5),
            profile("new_order", 8, 11, 6), profile("delivery", 9, 5, 2101),
            profile("payment", 10, 0, 7)]
    want_rows = [3, 4, 4, 1, 23, 5, 1, 4, 10, 1]
    assert cs.txn_rows(txns) == (sum(want_rows), 7)
    assert cs._chunks(txns) == [(0, 4), (4, 7), (7, 10)]
    buf = np.frombuffer(encode_resolve_batch(txns), np.uint8)
    offset, seen = 0, 0
    for (lo, hi), rows in zip(cs._chunks(txns), (12, 29, 15)):
        bt = cs._pack(txns[lo:hi])
        wired, offset, taken = cs._pack_wire(
            buf, offset, min(len(txns) - seen, cs.batch_size))
        assert taken == hi - lo, "both stop before the same transaction"
        same_tensors(bt, wired)
        assert int(bt.txn_mask.sum()) == rows
        heads = np.flatnonzero(bt.txn_mask & ~bt.cont)
        assert np.diff(np.append(heads, rows)).tolist() == want_rows[lo:hi]
        seen += taken
    assert (offset, seen) == (buf.size, len(txns))
    assert cs.codec.keys_widened == 0


def test_a_key_the_codec_widens_is_counted():
    cs = TPUConflictSet(**dict(ENGINE, capacity=1 << 12,
                               dict_capacity=1 << 13, batch_size=32))
    long_key = b"k" * 33
    loop = Loop(seed=2)
    role = Resolver(loop, cs)
    loop.run(role.resolve(0, 10, [TxnConflictInfo(
        5, [KeyRange(long_key, long_key + b"\x00")],
        [KeyRange(b"short", b"short\x00")])]))
    m = loop.run(role.get_metrics())
    assert m["engine"]["keys_widened"] == 2  # the begin and the end
    # widened to its first 32 bytes' range: no true range left the client
    assert (m["true_ranges_received"], m["slots_filled"]) == (0, 2)


def test_the_role_tells_true_ranges_points_and_empty_ranges_apart():
    from foundationdb_tpu.sim.oracle import OracleConflictSet

    loop = Loop(seed=3)
    role = Resolver(loop, OracleConflictSet())
    loop.run(role.resolve(0, 10, [
        TxnConflictInfo(5, [KeyRange(b"a", b"a\x00"), KeyRange(b"a", b"b"),
                            KeyRange(b"c", b"c")],
                        [KeyRange(b"d", b"d\x00\x00")]),
        TxnConflictInfo(5, [], []),
        TxnConflictInfo(5, [], [KeyRange(b"", b"\x00")]),
    ]))
    m = loop.run(role.get_metrics())
    assert (m["ranges_received"], m["txns_with_ranges"],
            m["true_ranges_received"], m["slots_filled"]) == (5, 2, 2, 4)
