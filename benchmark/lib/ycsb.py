"""YCSB core-workload data: keys, records, the scrambled Zipfian request
distribution, and the operation plan of a run. Everything is made from the
seed; the same seed gives the same data and the same operations.

Source: YCSB (Cooper et al., SoCC 2010), `CoreWorkload.java` defaults:
`fieldcount=10`, `fieldlength=100`, keys `"user" + fnvhash64(keynum)`
(`insertorder=hashed`, `zeropadding=1`), `requestdistribution=zipfian`,
which builds a `ScrambledZipfianGenerator`: a Zipfian draw with constant
0.99 over 10,000,000,000 items whatever the record count, then
`fnvhash64(draw) % recordcount`.
"""

from __future__ import annotations

import hashlib

import numpy as np

FIELD_COUNT = 10
FIELD_LENGTH = 100
RECORD_BYTES = FIELD_COUNT * FIELD_LENGTH
COUNTER_BYTES = 8  # `assumed`: a counter in field0's first bytes

# ScrambledZipfianGenerator.java: ITEM_COUNT, ZETAN (zeta(ITEM_COUNT, 0.99),
# "computed beforehand"), USED_ZIPFIAN_CONSTANT.
ZIPF_ITEMS = 10_000_000_000
ZIPF_ZETAN = 26.46902820178302
ZIPF_THETA = 0.99

READ, RMW = 0, 1

_FNV_OFFSET = 0xCBF29CE484222325
_FNV_PRIME = 0x100000001B3


def fnvhash64(values) -> np.ndarray:
    """YCSB's `Utils.fnvhash64` of each value: FNV-1a over the eight bytes
    of the long, low byte first, then `Math.abs` of the signed result.
    Returns non-negative int64 (uint64 arithmetic wraps as Java's does)."""
    n = np.asarray(values, np.int64).astype(np.uint64)
    h = np.full(n.shape, _FNV_OFFSET, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h = (h ^ (n & np.uint64(0xFF))) * np.uint64(_FNV_PRIME)
            n = n >> np.uint64(8)
    return np.abs(h.view(np.int64))


def record_keys(items) -> dict:
    """{item: its key} for every distinct item of `items`:
    `CoreWorkload.buildKeyName`, "user" and the hash in decimal."""
    uniq = np.unique(np.asarray(items, np.int64))
    return {int(i): b"user%d" % int(h)
            for i, h in zip(uniq, fnvhash64(uniq))}


def record_key(i: int) -> bytes:
    return record_keys([i])[i]


def field0(key: bytes, counter: int) -> bytes:
    """field0 as a read-modify-write leaves it: the counter, then bytes
    that are fresh for every (key, counter)."""
    fresh = hashlib.blake2b(key + counter.to_bytes(COUNTER_BYTES, "big"),
                            digest_size=(FIELD_LENGTH - COUNTER_BYTES) // 2)
    return counter.to_bytes(COUNTER_BYTES, "big") + fresh.hexdigest().encode()


class Records:
    """`count` records of 10 x 100 B. Fields 1-9 are one seeded block of
    random bytes stamped with the record's number, so a record is made in
    a microsecond and still differs from every other."""

    def __init__(self, count: int, seed: int):
        self.count = count
        rng = np.random.default_rng([seed, 0x59435342])
        self._tail = rng.integers(
            32, 127, RECORD_BYTES - FIELD_LENGTH - 8, dtype=np.uint8).tobytes()
        keys = record_keys(range(count))
        self.keys = [keys[i] for i in range(count)]

    def tail(self, i: int) -> bytes:
        return i.to_bytes(8, "big") + self._tail

    def value(self, i: int, counter: int = 0) -> bytes:
        return field0(self.keys[i], counter) + self.tail(i)


def zipfian_ranks(u: np.ndarray) -> np.ndarray:
    """`ZipfianGenerator.nextLong` over `ZIPF_ITEMS` items, for uniform
    draws `u` in [0, 1): rank 0 with probability 1 / zetan, rank 1 with
    0.5 ** theta / zetan, and beyond them Gray et al.'s closed form, as
    YCSB has it."""
    theta = ZIPF_THETA
    alpha = 1.0 / (1.0 - theta)
    zeta2 = 1.0 + 0.5 ** theta
    eta = ((1.0 - (2.0 / ZIPF_ITEMS) ** (1.0 - theta))
           / (1.0 - zeta2 / ZIPF_ZETAN))
    uz = u * ZIPF_ZETAN
    tail = (ZIPF_ITEMS * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    return np.where(uz < 1.0, 0, np.where(uz < zeta2, 1, tail))


BLOCK = 1024


def plan(n_items: int, n_ops: int, rmw_share: float, seed: int,
         base_seed: int):
    """(kinds, items) of at least `n_ops` operations, in whole blocks: READ
    or RMW, and the record, `fnvhash64(rank) % n_items` of a Zipfian rank
    (`ScrambledZipfianGenerator.nextValue`).

    The plan is made of blocks of 1,024 operations. Every block draws its
    ranks from the distribution's own quantiles, u = (j + u_b) / 1024 for
    j = 0..1023 with one offset u_b per block drawn from `base_seed` (the
    traffic file's own): the hottest record gets its 3.8 % of EVERY block,
    and the far ranks, which the hash spreads all but evenly over the
    records, change from block to block. Along the quantiles every record's
    operations alternate between RMW and READ in the mix's proportion. The
    run's seed then only shuffles each block. So every seed, and every
    stretch of a run, gets the same skew and the same mix in another order;
    a plain random draw gave the hottest key a share that differed by a
    ninth from run to run, and runs with different seeds differed four
    times as far as two runs of one seed (PR 23's first sets)."""
    n_blocks = -(-n_ops // BLOCK)
    offsets = np.random.default_rng(base_seed).random(n_blocks)
    u = (np.arange(BLOCK)[None, :] + offsets[:, None]) / BLOCK
    records = fnvhash64(zipfian_ranks(u)) % n_items
    j = np.arange(BLOCK)
    is_rmw = np.floor((j + 1) * rmw_share) > np.floor(j * rmw_share)
    order = np.random.default_rng([seed, 0x4F505321]).permuted(
        np.tile(j, (n_blocks, 1)), axis=1)
    items = np.take_along_axis(records, order, axis=1)
    kinds = np.where(is_rmw, RMW, READ).astype(np.int8)[order]
    return kinds.ravel(), items.ravel()
