"""The delta merge (conflict_kernel._dict_insert) against a NumPy reference.

The reference merges lexicographically sorted rows with ``np.lexsort`` and
reads the rank-rebase table off ``np.searchsorted`` over each row's value as
one Python integer — neither shares code with the kernel, which builds the
same table from a histogram and a prefix sum and moves the rows by streaming
shifts. Every case must agree element for element: the merged dictionary,
the live count and ``shift`` (the dictionary's ``+inf`` padding rows read
exactly the delta's real count).

The kernel searches nothing: each delta row arrives with its ``cross`` rank
(resident keys below it; ``D + 1`` on a padding row). The search the kernel
used to run for it, ``searchsorted_words_fp(dict_keys, delta_keys, "right")``,
lives on here as the oracle of what the host ships
(``_ResidentMirror.insert_new``), over the same cases.
"""

import jax
import numpy as np
import pytest

from foundationdb_tpu.core.keypack import INT32_MAX
from foundationdb_tpu.models import conflict_kernel as ck
from foundationdb_tpu.models.conflict_set import (
    _insert_sorted,
    _ResidentMirror,
    _rows_to_u64,
)
from foundationdb_tpu.ops.lex import searchsorted_words_fp

D = 4096  # dictionary capacity: D + 1 rows, the last always +inf
M = 1024  # delta slots
N_DEFAULT = 2048
GAP = (1 << 24) // (D + 2)  # resident key i sits at (i + 1) * GAP
COUNTS = {"0": 0, "1": 1, "7": 7, "637": 637, "M-1": M - 1, "M": M}
PLACEMENTS = ("below_all", "above_all", "adjacent_runs", "every_gap",
              "exactly_full")


def encode(values, w):
    """Integers below 2**24 -> [n, w] int32 rows in the same order. The
    value is cut into w digits and each digit spread over the whole int32
    range, so every word decides some comparison and both signs occur."""
    v = np.asarray(values, np.int64)
    bits = -(-24 // w)
    rows = np.empty((len(v), w), np.int64)
    for j in range(w):
        digit = (v >> (bits * (w - 1 - j))) & ((1 << bits) - 1)
        rows[:, j] = (digit << (32 - bits)) - (1 << 31)
    return rows.astype(np.int32)


def place(placement, m):
    """(resident values, delta values): disjoint, each strictly rising."""
    n = D - m if placement == "exactly_full" else N_DEFAULT
    resident = (np.arange(n, dtype=np.int64) + 1) * GAP
    if placement == "below_all":
        delta = np.arange(m) + 1
    elif placement == "above_all":
        delta = resident[-1] + 1 + np.arange(m)
    elif placement == "adjacent_runs":
        # Three runs of neighbours: in the first gap, one in the middle,
        # and the last gap between two resident keys.
        cuts = [0, m // 3, 2 * (m // 3), m]
        gaps = [0, n // 2, n - 2]
        delta = np.concatenate([
            resident[g] + 1 + np.arange(hi - lo)
            for g, lo, hi in zip(gaps, cuts[:-1], cuts[1:])])
    elif placement == "every_gap":
        delta = resident[:m] + GAP // 2
    else:
        rng = np.random.default_rng(m)
        pool = np.unique(rng.integers(1, (n + 2) * GAP, size=3 * m + 8))
        pool = pool[pool % GAP != 0]
        delta = np.sort(rng.choice(pool, size=m, replace=False))
    return resident, np.asarray(delta, np.int64)


def padded(rows, n_rows, w):
    out = np.full((n_rows, w), INT32_MAX, np.int32)
    out[:len(rows)] = rows
    return out


def as_ints(rows):
    """Each row as one Python integer, ordered as the rows are."""
    u = rows.astype(np.int64) + (1 << 31)
    out = np.zeros(len(rows), object)
    for j in range(rows.shape[1]):
        out = out * (1 << 32) + u[:, j].astype(object)
    return out


def reference(dict_keys, n, delta_keys, m):
    d1, w = dict_keys.shape
    both = np.concatenate([dict_keys[:n], delta_keys[:m]])
    merged = both[np.lexsort(both[:, ::-1].T)]
    shift = np.full(d1, m, np.int32)
    shift[:n] = np.searchsorted(as_ints(delta_keys[:m]),
                                as_ints(dict_keys[:n]), side="left")
    return padded(merged, d1, w), n + m, shift


def reference_cross(dict_keys, n, delta_keys, m):
    """Resident keys below each delta row; D + 1 on the padding rows."""
    cross = np.full(len(delta_keys), len(dict_keys), np.int32)
    cross[:m] = np.searchsorted(as_ints(dict_keys[:n]),
                                as_ints(delta_keys[:m]), side="left")
    return cross


def case(w, count, placement):
    m = COUNTS[count]
    resident, delta = place(placement, m)
    n = len(resident)
    dict_keys = padded(encode(resident, w), D + 1, w)
    delta_keys = padded(encode(delta, w), M, w)
    return dict_keys, n, delta_keys, m


insert_jit = jax.jit(ck._dict_insert)
search_jit = jax.jit(lambda d, q: searchsorted_words_fp(d, q, side="right"))


@pytest.mark.parametrize("placement", PLACEMENTS)
@pytest.mark.parametrize("count", COUNTS)
@pytest.mark.parametrize("w", [1, 3, 9])
def test_dict_insert_equals_numpy_merge(w, count, placement):
    dict_keys, n, delta_keys, m = case(w, count, placement)
    want_keys, want_n, want_shift = reference(dict_keys, n, delta_keys, m)
    got_keys, got_n, got_shift = insert_jit(
        dict_keys, np.int32(n), delta_keys,
        reference_cross(dict_keys, n, delta_keys, m))
    assert int(got_n) == want_n
    np.testing.assert_array_equal(np.asarray(got_shift), want_shift)
    assert (np.asarray(got_shift)[n:] == m).all()
    np.testing.assert_array_equal(np.asarray(got_keys), want_keys)


@pytest.mark.parametrize("placement", PLACEMENTS)
@pytest.mark.parametrize("count", COUNTS)
@pytest.mark.parametrize("w", [1, 3, 9])
def test_shipped_cross_equals_the_deleted_device_search(w, count, placement):
    dict_keys, n, delta_keys, m = case(w, count, placement)
    mirror = _ResidentMirror(dict_keys[:n], D, M, 0.5)
    _ids, ins = mirror.insert_new(_rows_to_u64(delta_keys[:m]),
                                  delta_keys[:m], 0)
    shipped = np.full(M, D + 1, np.int32)  # _ranks_to_batch's padding
    shipped[:m] = ins
    np.testing.assert_array_equal(
        shipped, np.asarray(search_jit(dict_keys, delta_keys)))
    np.testing.assert_array_equal(
        shipped, reference_cross(dict_keys, n, delta_keys, m))
    # The mirror spliced the keys where it said it would.
    np.testing.assert_array_equal(
        mirror.rows, reference(dict_keys, n, delta_keys, m)[0][:n + m])


def test_apply_delta_lowers_without_a_loop():
    """A search on the device is a ``while`` (searchsorted_words_fp's column
    cascades were ``_while.55`` and ``_while.61`` of the chip's traces); the
    merge is a histogram, a prefix sum, conditional shifts and a scatter."""
    w = 9
    res = ck.init_res(encode([0], w), D, 256, delta_capacity=64)
    text = jax.jit(ck.apply_delta).lower(
        res, padded(encode([], w), M, w), np.full(M, D + 1, np.int32)
    ).as_text()
    assert "stablehlo.scatter" in text and "stablehlo.case" in text
    assert "stablehlo.while" not in text


def test_apply_delta_rebases_two_level_history_and_shard_bounds():
    w, n, m = 3, 600, 37
    rng = np.random.default_rng(25)
    values = np.sort(rng.choice(1 << 24, size=n + m, replace=False))
    is_new = np.zeros(n + m, bool)
    is_new[rng.choice(np.arange(1, n + m), size=m, replace=False)] = True
    dict_rows = encode(values[~is_new], w)
    delta_keys = padded(encode(values[is_new], w), 64, w)
    res = ck.init_res(dict_rows, 1023, 256, delta_capacity=64,
                      shard_lo=np.array([0, 200, 411], np.int32),
                      shard_hi=np.array([200, 411, INT32_MAX], np.int32))

    def with_ranks(state, ranks):
        keys = np.full(state.keys.shape, INT32_MAX, np.int32)
        keys[:len(ranks), 0] = ranks
        return state._replace(keys=keys, n_used=np.int32(len(ranks)))

    base_ranks = np.sort(rng.choice(n, size=100, replace=False))
    base_ranks[0] = 0
    delta_ranks = np.sort(rng.choice(n, size=20, replace=False))
    delta_ranks[0] = 0
    hist = res.hist
    res = res._replace(hist=hist._replace(
        base=with_ranks(hist.base, base_ranks),
        delta=with_ranks(hist.delta, delta_ranks)))

    out = jax.jit(ck.apply_delta)(
        res, delta_keys,
        reference_cross(np.asarray(res.dict_keys), n, delta_keys, m))

    want_keys, want_n, shift = reference(
        np.asarray(res.dict_keys), n, delta_keys, m)

    def rebased(ranks):
        ranks = np.asarray(ranks)
        live = ranks != INT32_MAX
        return np.where(live, ranks + shift[np.where(live, ranks, 0)], ranks)

    np.testing.assert_array_equal(np.asarray(out.dict_keys), want_keys)
    assert int(out.n_keys) == want_n
    for got, old in ((out.hist.base, res.hist.base),
                     (out.hist.delta, res.hist.delta)):
        np.testing.assert_array_equal(np.asarray(got.keys)[:, 0],
                                      rebased(old.keys[:, 0]))
        np.testing.assert_array_equal(np.asarray(got.versions),
                                      np.asarray(old.versions))
    np.testing.assert_array_equal(np.asarray(out.hist.base_st),
                                  np.asarray(res.hist.base_st))
    np.testing.assert_array_equal(np.asarray(out.shard_lo),
                                  rebased(res.shard_lo))
    np.testing.assert_array_equal(np.asarray(out.shard_hi),
                                  rebased(res.shard_hi))
    # The rebase moved something: a rank above the first new key shifts.
    assert (np.asarray(out.shard_lo) != np.asarray(res.shard_lo)).any()
    # The ranks still name the same keys in the merged dictionary.
    np.testing.assert_array_equal(
        want_keys[rebased(base_ranks)],
        np.asarray(res.dict_keys)[base_ranks])


@pytest.mark.parametrize("shape", [(), (4,), (9,)], ids=["vector", "u64", "rows"])
@pytest.mark.parametrize("n,m", [(0, 0), (0, 3), (5000, 0), (5000, 1),
                                 (5000, 4), (5000, 5), (5000, 700),
                                 (70000, 7), (70000, 68), (70000, 69)])
def test_the_mirrors_splice_equals_np_insert(n, m, shape):
    """_ResidentMirror.insert_new splices a few new rows by slice copies
    and many by np.insert: the same array either way, on both sides of the
    switch (a thousandth of the resident rows), with several rows landing
    at one position, at the first and past the last."""
    rng = np.random.default_rng([n, m, len(shape)])
    dtype = np.uint64 if shape == (4,) else np.int32
    arr = rng.integers(0, 1 << 30, (n, *shape)).astype(dtype)
    vals = rng.integers(0, 1 << 30, (m, *shape)).astype(dtype)
    ins = np.sort(rng.integers(0, n + 1, m))
    if m >= 4:
        ins[:2] = 0  # two at the front
        ins[-2:] = n  # two past the end
        ins = np.sort(ins)
    got = _insert_sorted(arr, ins, vals)
    want = np.insert(arr, ins, vals, axis=0)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert (got == want).all()
