"""When the resident dictionary repacks for fragmentation, and what is left.

A full repack keeps {device-live} ∪ {pinned} ∪ {the dispatch's keys} ∪ {keys
used at or after the MVCC floor} and drops the rest;
``_ResidentMirror.frag_due`` fires it only when that would free over half of
a dictionary that is over half full and has grown by a quarter of its capacity
since the last repack. Driven here on the untiered engine and on the mesh
engine, which share the mirror: a stream that brings in three dictionaries'
worth of never-seen keys, with the floor a fixed number of batches behind,
repacks a handful of times, every time for rows, and never fills; a floor that
jumps past keys the device history still holds fires once, for nothing, and
not again. Verdicts are held to the brute-force oracle's on every batch.
"""

import numpy as np
import pytest

from foundationdb_tpu.models import conflict_kernel as ck
from foundationdb_tpu.models.conflict_set import TPUConflictSet
from foundationdb_tpu.sim.oracle import OracleConflictSet
from tests.test_engine_stages import point_txns

BATCH = 32
STEP = 100  # versions a batch
BEHIND = 4  # batches between the newest commit and the MVCC floor
DICT = 1 << 10
# 128 delta slots: a batch brings at most 64 new endpoint keys, and the
# parent's refill target (capacity less the delta slots) sits over half.
KW = dict(capacity=1 << 10, dict_capacity=DICT, dict_delta_slots=128,
          batch_size=BATCH, max_read_ranges=2, max_write_ranges=2,
          max_key_bytes=16)
COUNTERS = ("full_repacks", "repacks_frag_due", "repacks_dict_full",
            "repacks_delta_overflow", "evictions", "delta_new_keys")


def untiered():
    return TPUConflictSet(**KW)


def mesh():
    from foundationdb_tpu.parallel.sharded_resolver import ShardedConflictSet

    cs = ShardedConflictSet(n_shards=2, auto_reshard=False, **KW)
    assert isinstance(cs.state, ck.ResState)
    return cs


def new_key(rng):
    return rng.bytes(8)  # first bytes uniform: both mesh shards get keys


def all_new(rng):
    return [new_key(rng) for _ in range(BATCH)]


HOT = [bytes([37 * i % 256]) + b"hot%04d" % i for i in range(48)]


def hot60(rng):
    return [new_key(rng) if rng.random() < 0.6
            else HOT[int(rng.integers(len(HOT)))] for _ in range(BATCH)]


class Driven:
    """An engine beside the oracle: after every batch the verdicts are
    equal, and ``steps`` holds what the batch added to each counter, with
    the dictionary's key count after it."""

    def __init__(self, cs):
        self.cs = cs
        self.oracle = OracleConflictSet()
        self.version = 0
        self.steps: list[dict] = []

    def batch(self, keys, oldest, read_behind=1):
        self.version += STEP
        v = self.version
        txns = point_txns(keys, max(0, v - read_behind * STEP))
        before = self.cs.dict_stats
        got = self.cs.resolve(txns, v, oldest_version=oldest)
        assert got == self.oracle.resolve(txns, v, oldest), f"version {v}"
        after = self.cs.dict_stats
        self.steps.append(dict({k: after[k] - before[k] for k in COUNTERS},
                               n=after["resident_keys"]))


@pytest.mark.parametrize("stream", [all_new, hot60], ids=lambda f: f.__name__)
@pytest.mark.parametrize("engine", [untiered, mesh], ids=lambda f: f.__name__)
def test_a_stream_of_new_keys_repacks_rarely_and_always_for_rows(engine,
                                                                 stream):
    rng = np.random.default_rng(41)
    d = Driven(engine())
    while d.cs.dict_stats["delta_new_keys"] < 3 * DICT:
        # Reads two batches back: some conflict with the hot set's writes.
        d.batch(stream(rng), max(0, d.version + STEP - BEHIND * STEP),
                read_behind=2)
    st = d.cs.dict_stats
    # The parent repacked on every batch once over half full, for 0 rows
    # and then for the batch's own; a quarter of the capacity in new keys
    # is the least the dictionary grows between two repacks now.
    assert 1 <= st["full_repacks"] <= st["delta_new_keys"] / (DICT / 4), st
    assert st["repacks_frag_due"] >= 1, st
    for i, step in enumerate(d.steps):
        assert step["n"] < DICT, (i, step)
        if step["repacks_frag_due"]:
            assert step["evictions"] > 0, (i, step)
    # The dictionary on the device is the mirror's, row for row.
    np.testing.assert_array_equal(
        np.asarray(d.cs.state.dict_keys)[: d.cs._mirror.n], d.cs._mirror.rows)


@pytest.mark.parametrize("engine", [untiered, mesh], ids=lambda f: f.__name__)
def test_stale_keys_the_device_still_holds_fire_once_and_not_again(engine):
    """The floor advances past every key while the device history, not yet
    merged, still references them all: the mirror counts them reclaimable,
    the repack finds them live and frees nothing, and frag_due stays shut
    until a forced repack has freed rows, however stale the mirror reads."""
    rng = np.random.default_rng(43)
    d = Driven(engine())
    while d.cs.dict_stats["resident_keys"] <= DICT // 2 + 2 * BATCH:
        d.batch(all_new(rng), 0)
    assert d.cs.dict_stats["full_repacks"] == 0
    floor = d.version  # past every key's last use
    d.batch(all_new(rng)[:1], floor)
    assert d.steps[-1]["repacks_frag_due"] == 1, d.steps[-1]
    assert d.steps[-1]["evictions"] == 0, d.steps[-1]
    for _ in range(4):  # each brings two keys; the history has let go by now
        d.batch(all_new(rng)[:1], floor)
        assert d.steps[-1]["full_repacks"] == 0, d.steps[-1]
    # Filling up forces a repack, which frees the rows and opens the
    # trigger again.
    while not d.steps[-1]["full_repacks"]:
        d.batch(all_new(rng), floor)
    assert d.steps[-1]["repacks_dict_full"] == 1, d.steps[-1]
    assert d.steps[-1]["evictions"] > DICT // 4, d.steps[-1]
    assert not d.cs._mirror._frag_barren
