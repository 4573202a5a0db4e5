"""O(1) range-maximum queries over per-segment version arrays.

The conflict history is a step function: sorted boundaries K[i] with V[i] =
last commit version writing into segment [K[i], K[i+1]). A read-range
conflict check is "max V over the touched segments > read_version" — the
role the per-node max-version annotations play in the reference skiplist
(fdbserver/SkipList.cpp propagates maxVersion up its levels). Here we build a
sparse table (doubling max) once per resolve and answer every query with two
gathers.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def sparse_table(values: jax.Array) -> jax.Array:
    """Build ST[l, i] = max(values[i : i + 2**l]) for l in [0, ceil_log2(N)].

    values: [N] int32. Returns [L, N] with out-of-range tails clamped to the
    last valid window (queries never read them thanks to the two-window
    trick).
    """
    n = values.shape[0]
    if n == 0:
        return jnp.zeros((1, 0), dtype=values.dtype)
    levels = max(1, math.ceil(math.log2(n))) + 1
    rows = [values]
    for l in range(1, levels):
        prev = rows[-1]
        shift = 1 << (l - 1)
        shifted = jnp.concatenate([prev[shift:], prev[-1:].repeat(shift)])
        rows.append(jnp.maximum(prev, shifted))
    return jnp.stack(rows)


def range_max(st: jax.Array, lo: jax.Array, hi: jax.Array, neg_inf: int) -> jax.Array:
    """max(values[lo:hi]) for int32 index arrays lo/hi (broadcasting).

    Empty ranges (hi <= lo) return neg_inf. Classic two-overlapping-windows
    sparse-table query; the level is computed with integer bit tricks so the
    whole thing is jit-safe on int32.
    """
    length = hi - lo
    valid = length > 0
    safe_len = jnp.maximum(length, 1)
    # level = floor(log2(safe_len)): position of highest set bit.
    lvl = 31 - _clz32(safe_len)
    w = jnp.int32(1) << lvl
    a = st[lvl, lo]
    b = st[lvl, jnp.maximum(hi - w, 0)]
    return jnp.where(valid, jnp.maximum(a, b), jnp.int32(neg_inf))


def _clz32(x: jax.Array) -> jax.Array:
    """Count leading zeros of positive int32 via float exponent extraction."""
    # For x in [1, 2^31): clz = 31 - floor(log2(x)). Bit-smearing approach
    # keeps everything in integer ops (exact, unlike float log).
    x = x.astype(jnp.uint32)
    x = x | (x >> 1)
    x = x | (x >> 2)
    x = x | (x >> 4)
    x = x | (x >> 8)
    x = x | (x >> 16)
    # popcount of the smeared mask = 32 - clz.
    pop = _popcount32(x)
    return (jnp.uint32(32) - pop).astype(jnp.int32)


def _popcount32(x: jax.Array) -> jax.Array:
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return (x * jnp.uint32(0x01010101)) >> 24


# ---------------------------------------------------------------------------
# Two-level blocked RMQ. The conflict kernel does NOT call it (its history
# check uses sparse_table above; the blocked arm went with ROADMAP C1 and
# this structure is the debt that item names). Its BUILD is ~3 passes over
# [N] (in-block prefix/suffix cummax + a small table over block maxima)
# instead of the sparse table's log2(N) passes; queries pay one [Nq, G]
# row gather for the same-block case.
# ---------------------------------------------------------------------------

RMQ_BLOCK = 256


class BlockTable:
    """Container for the blocked structure (host-built pytree of arrays)."""

    def __init__(self, rows, prefix, suffix, top):
        self.rows = rows  # [NB, G] original values, padded with neg_inf
        self.prefix = prefix  # [NB, G] cummax from block start
        self.suffix = suffix  # [NB, G] cummax toward block start
        self.top = top  # sparse table over block maxima [L, NB]


def block_table(values: jax.Array, neg_inf: int, block: int = RMQ_BLOCK) -> BlockTable:
    n = values.shape[0]
    nb = -(-n // block)
    pad = nb * block - n
    v = jnp.concatenate(
        [values, jnp.full((pad,), neg_inf, values.dtype)]) if pad else values
    rows = v.reshape(nb, block)
    prefix = jax.lax.cummax(rows, axis=1)
    suffix = jax.lax.cummax(rows, axis=1, reverse=True)
    top = sparse_table(rows.max(axis=1))
    return BlockTable(rows, prefix, suffix, top)


def range_max_blocked(bt: BlockTable, lo: jax.Array, hi: jax.Array,
                      neg_inf: int, block: int = RMQ_BLOCK) -> jax.Array:
    """max(values[lo:hi]) with numpy-slice semantics; empty -> neg_inf."""
    valid = hi > lo
    last = jnp.maximum(hi - 1, 0)
    safe_lo = jnp.minimum(jnp.maximum(lo, 0), bt.rows.shape[0] * block - 1)
    bl, il = safe_lo // block, safe_lo % block
    bh, ih = last // block, last % block

    # Cross-block: suffix of lo's block + prefix of hi's block + interior.
    cross = jnp.maximum(bt.suffix[bl, il], bt.prefix[bh, ih])
    interior = range_max(bt.top, bl + 1, bh, neg_inf)
    cross = jnp.maximum(cross, interior)

    # Same-block: masked max over row bl between il..ih.
    row = bt.rows[bl]  # [Nq, G]
    j = jnp.arange(block, dtype=jnp.int32)
    mask = (j[None, :] >= il[..., None]) & (j[None, :] <= ih[..., None])
    same = jnp.where(mask, row, neg_inf).max(axis=-1)

    out = jnp.where(bl == bh, same, cross)
    return jnp.where(valid, out, jnp.asarray(neg_inf, bt.rows.dtype))
