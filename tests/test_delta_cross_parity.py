"""What the host ships as ``delta_cross`` against what the device would find.

``apply_delta`` searches nothing: every new key arrives with its ``cross``
rank, the count of resident keys below it, which the host mirror computes to
splice the key into its own sorted view (``_ResidentMirror.insert_new``). That
is only right while the mirror's hot view IS the device's dictionary at the
moment the delta lands. So each engine's resolve entry points are wrapped by a
spy that, at dispatch time and against the ``state.dict_keys`` the program is
about to read, runs the search the kernel used to run
(``searchsorted_words_fp(dict_keys, delta_keys, "right")``) and requires the
shipped vector to equal it row for row, ``+inf`` padding (``D + 1``) included;
after the call the merged dictionary must equal a NumPy merge of the same
rows. Verdicts are held to the brute-force oracle's on the same stream.

The orderings this rests on, each driven here: the pipelined packer packs
window N+1 against the mirror after N's insert (``window``); a demotion is
its own dispatch and lands before the delta (``tiered``); a full repack ships
the new keys inside the repacked table and an all-``D + 1`` delta
(``after_repack``); the mesh engine replicates dictionary and delta, so every
shard takes the same ranks (``mesh``).
"""

import numpy as np
import pytest

from foundationdb_tpu.core.keypack import INT32_MAX
from foundationdb_tpu.models import conflict_kernel as ck
from foundationdb_tpu.models.conflict_set import (
    TPUConflictSet,
    encode_resolve_batch,
)
from foundationdb_tpu.ops.lex import searchsorted_words_fp
from foundationdb_tpu.sim.oracle import OracleConflictSet
from tests.test_conflict_oracle import rand_txn
from tests.test_dict_insert import reference as numpy_merge
from tests.test_tiered_dict import TIER, _hotspot_steps

KW = dict(capacity=512, batch_size=32, max_read_ranges=4,
          max_write_ranges=4, max_key_bytes=8)


class Spy:
    """Wraps an engine's resolve entry points (state, batch, ...) -> (...,
    state); ``seen`` holds the count of real delta rows of each dispatch."""

    def __init__(self, cs, *names):
        self.seen = []
        for name in names:
            setattr(cs, name, self.wrap(getattr(cs, name)))

    def wrap(self, fn):
        def spied(state, batch, *rest):
            dict_keys = np.asarray(state.dict_keys)
            d1 = len(dict_keys)
            n = int(state.n_keys)
            delta = np.asarray(batch.delta_keys)
            shipped = np.asarray(batch.delta_cross)
            assert shipped.dtype == np.int32 and shipped.shape == delta.shape[:1]
            searched = np.asarray(
                searchsorted_words_fp(dict_keys, delta, side="right"))
            np.testing.assert_array_equal(shipped, searched)
            real = shipped < d1
            np.testing.assert_array_equal(
                real, ~(delta == INT32_MAX).all(axis=1))
            out = fn(state, batch, *rest)
            m = int(real.sum())  # real rows come first: the delta is sorted
            want, want_n, _shift = numpy_merge(dict_keys, n, delta, m)
            np.testing.assert_array_equal(np.asarray(out[-1].dict_keys), want)
            assert int(out[-1].n_keys) == want_n
            self.seen.append(m)
            return out

        return spied


def oracle_stream(rng, n_batches, n_txns):
    """(txns, cv, oldest) a batch: random ranges over a keyspace wide enough
    that every batch brings keys the dictionary has not seen."""
    cv = 1000
    for _ in range(n_batches):
        cv += int(rng.integers(1, 50))
        txns = [
            rand_txn(rng, read_version=int(rng.integers(max(0, cv - 300), cv)))
            for _ in range(int(rng.integers(*n_txns)))
        ]
        yield txns, cv, cv - 200


def drive(cs, steps):
    oracle = OracleConflictSet()
    for i, (txns, cv, oldest) in enumerate(steps):
        got = cs.resolve(txns, cv, oldest_version=oldest)
        oracle.oldest_version = max(oracle.oldest_version, oldest)
        assert got == oracle.resolve(txns, cv), f"batch {i}"
    np.testing.assert_array_equal(
        np.asarray(cs.state.dict_keys)[: cs._mirror.n], cs._mirror.rows)


def single():
    cs = TPUConflictSet(**KW)
    spy = Spy(cs, "_resolve_fn")
    drive(cs, oracle_stream(np.random.default_rng(27), 10, n_txns=(8, 32)))
    assert len(spy.seen) == 10 and min(spy.seen) > 0, spy.seen
    assert cs.dict_stats["full_repacks"] == 0


def window():
    """The threaded packer runs ahead: every window is submitted before the
    first is dispatched, so window N+1's ranks were taken against the mirror
    after N's insert while the device still held the dictionary before it."""
    from foundationdb_tpu.sched.packing import PipelinedWindowRunner

    rng = np.random.default_rng(28)
    kw = dict(KW, batch_size=16)
    cs = TPUConflictSet(**kw)
    spy = Spy(cs, "_resolve_many_fn")
    runner = PipelinedWindowRunner(cs, threaded=True)
    oracle = OracleConflictSet()
    k, count, n_windows = 2, 16, 5
    cv, want = 1, []
    for _ in range(n_windows):
        txns = [rand_txn(rng, read_version=max(0, cv - 1))
                for _ in range(k * count)]
        cvs = list(range(cv, cv + k))
        for i, c in enumerate(cvs):
            want.append([int(v) for v in oracle.resolve(
                txns[i * count:(i + 1) * count], c)])
        runner.submit(encode_resolve_batch(txns), cvs, count)
        cv += k
    got = []
    for _ in range(n_windows):
        runner.dispatch_ready()
        got.extend(np.asarray(runner.collect_next()).tolist())
    runner.close()
    assert got == want
    assert len(spy.seen) == n_windows and min(spy.seen) > 0, spy.seen
    assert cs.dict_stats["full_repacks"] == 0


def tiered():
    cs = TPUConflictSet(**TIER, **KW)
    assert cs.tiered
    spy = Spy(cs, "_resolve_fn")
    evictions = []
    evict = cs._evict_fn
    cs._evict_fn = lambda *a: (evictions.append(len(spy.seen)), evict(*a))[1]
    drive(cs, _hotspot_steps())
    st = cs.dict_stats
    assert st["demotions"] > 0 and st["promotions"] > 0, st
    assert st["full_repacks"] == 0, st
    # A demotion landed on the device before the delta of the same dispatch,
    # and that delta (promotions among its rows, later on) was not empty.
    assert evictions and all(spy.seen[i] > 0 for i in evictions), (
        evictions, spy.seen)


def after_repack():
    """Four delta slots: any dispatch with more new keys repacks in full,
    ships them inside the table and an EMPTY delta (all D + 1) beside it; the
    next delta is ranked against the rebuilt mirror."""
    cs = TPUConflictSet(dict_delta_slots=4, **KW)
    spy = Spy(cs, "_resolve_fn")
    rng = np.random.default_rng(29)
    few = [(
        [rand_txn(rng, read_version=cv - 5, n_ranges=1) for _ in range(2)],
        cv, cv - 200) for cv in range(2000, 2100, 10)]
    drive(cs, list(oracle_stream(rng, 6, n_txns=(8, 24))) + few)
    st = cs.dict_stats
    assert st["full_repacks"] >= 2, st
    # Some dispatch right after a repack's empty delta carried keys again.
    assert any(a == 0 and b > 0 for a, b in zip(spy.seen, spy.seen[1:])), (
        spy.seen)
    assert st["delta_empty_dispatches"] == spy.seen.count(0), (st, spy.seen)


def mesh():
    from foundationdb_tpu.parallel.sharded_resolver import ShardedConflictSet

    cs = ShardedConflictSet(**dict(KW, auto_reshard=False, n_shards=2))
    assert isinstance(cs.state, ck.ResState)
    spy = Spy(cs, "_resolve_fn")
    drive(cs, oracle_stream(np.random.default_rng(31), 8, n_txns=(8, 32)))
    assert len(spy.seen) == 8 and min(spy.seen) > 0, spy.seen


@pytest.mark.parametrize(
    "path", [single, window, tiered, after_repack, mesh],
    ids=lambda f: f.__name__)
def test_shipped_delta_cross_equals_the_device_search_at_dispatch(path):
    path()
