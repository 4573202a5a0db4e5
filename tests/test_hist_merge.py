"""The window history's merge and the compaction tail it shares with the
paint (conflict_kernel._merge_delta, _dedup_compact) against the bodies they
had until PR 41.

Those bodies live on here, word for word, as the reference: three binary
searches over the capacity and a dozen gathers a history row, which the chip
timed at 257 ms a merge at capacity 1<<19 (ledger, PR 40, ``tpcc_share_mix``).
The kernel now builds the same arrays from a histogram, prefix sums and
streaming shifts. Every case must agree leaf for leaf: ``keys``,
``versions``, ``n_used``, ``oldest``, ``overflow``, the overflow case (more
survivors than the capacity: the first ``c_out`` kept) included.

Also here: the merge lowers without a loop over the capacity, and the
engine's ``hist_merges`` counts exactly the dispatches that merged.
"""

import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from foundationdb_tpu.core.keypack import INT32_MAX
from foundationdb_tpu.core.types import KeyRange, TxnConflictInfo
from foundationdb_tpu.models import conflict_kernel as ck
from foundationdb_tpu.models.conflict_kernel import NEG_VERSION
from foundationdb_tpu.models.conflict_set import TPUConflictSet
from foundationdb_tpu.ops.lex import (
    lex_lt,
    searchsorted_words,
    searchsorted_words_fp,
    sort_keys_with_payload,
)
from tests.test_dict_insert import encode


# -- the bodies as they were at 0984e98 --------------------------------------


def old_dedup_compact(skeys, newv, c_out, prior_overflow):
    """Shared compaction tail of every step-function rewrite (paint and
    the window-history merge): dedup equal keys, drop boundaries that no
    longer change the step function, compact survivors to the front.

    skeys [n, W] sorted (ties allowed), newv [n] already GC'd (expired and
    padding rows hold the sentinel). Returns (keys, versions, n_used,
    overflow) at capacity c_out."""
    n, w = skeys.shape
    is_inf = jnp.all(skeys == INT32_MAX, axis=-1)
    # Dedup equal keys: keep the LAST occurrence (it carries the full
    # coverage sum and the consistent old version).
    neq_next = jnp.any(skeys[:-1] != skeys[1:], axis=-1)
    keep1 = jnp.concatenate([neq_next, jnp.ones((1,), jnp.bool_)])
    # Drop boundaries whose version equals the previous KEPT boundary's —
    # they no longer change the step function (this is what erases interior
    # boundaries of freshly painted ranges and expired segments).
    idx = jnp.arange(n, dtype=jnp.int32)
    kept_idx = jnp.where(keep1, idx, -1)
    prev_kept = jnp.concatenate(
        [jnp.full((1,), -1, jnp.int32), jax.lax.cummax(kept_idx, axis=0)[:-1]]
    )
    prev_v = jnp.where(prev_kept >= 0, newv[jnp.maximum(prev_kept, 0)], NEG_VERSION - 1)
    keep = keep1 & (newv != prev_v) & ~is_inf

    # The keyspace minimum must always remain a boundary. Force its run's
    # LAST row (the keep-last dedup representative): forcing the first
    # would duplicate the boundary whenever a batch paints endpoints
    # equal to the minimum (e.g. shard-clamped delta-0 entries at lo).
    first_live = jnp.argmax(~is_inf)  # index of smallest real key (= min key)
    is_min = jnp.all(skeys == skeys[first_live], axis=-1) & ~is_inf
    min_last = n - 1 - jnp.argmax(is_min[::-1])
    keep = keep.at[min_last].set(True)

    # Compact survivors to the front, gather-style: output slot j pulls the
    # (j+1)-th kept entry (binary search into the keep prefix-sum) — the
    # scatter-free dual of a prefix-sum scatter compaction.
    keep_cum = jnp.cumsum(keep.astype(jnp.int32))  # [n], non-decreasing
    n_used = keep_cum[-1]
    out_j = jnp.arange(c_out, dtype=jnp.int32)
    src = jnp.searchsorted(keep_cum, out_j + 1, side="left").astype(jnp.int32)
    src = jnp.clip(src, 0, n - 1)
    live_out = out_j < n_used
    fkeys = jnp.where(
        live_out[:, None], skeys[src], jnp.full((w,), INT32_MAX, jnp.int32)
    )
    fv = jnp.where(live_out, newv[src], NEG_VERSION)
    overflow = prior_overflow | (n_used > c_out)
    return fkeys, fv, jnp.minimum(n_used, c_out), overflow




def old_merge_delta(base, delta, floor):
    """Fold the delta into the base: pointwise max of the two step
    functions over the union boundary set, then GC (≤ floor) + compact.
    Max is exact because delta writes postdate every base write they
    cover. Same merge-path construction as _paint_and_compact — all
    sorts-of-small + gathers, no scatters."""
    c, w = base.keys.shape
    cd = delta.keys.shape[0]
    n = c + cd
    _ss = searchsorted_words_fp
    cross_d = _ss(base.keys, delta.keys, side="right")  # [Cd]
    seg_b_for_d = jnp.maximum(cross_d - 1, 0)
    cross_b = _ss(delta.keys, base.keys, side="right")  # [C]
    seg_d_for_b = jnp.maximum(cross_b - 1, 0)

    # Merge-path: delta entry j lands at slot j + its cross-rank ('right'
    # puts base entries before equal delta entries → keep-last dedup keeps
    # the delta occurrence; both carry the same max so either is correct).
    pos_d = jnp.arange(cd, dtype=jnp.int32) + cross_d
    idx = jnp.arange(n, dtype=jnp.int32)
    cnt_le = jnp.searchsorted(pos_d, idx, side="right").astype(jnp.int32)
    k_d = jnp.maximum(cnt_le - 1, 0)
    from_d = (cnt_le > 0) & (pos_d[k_d] == idx)
    b_idx = jnp.clip(idx - cnt_le, 0, c - 1)

    skeys = jnp.where(from_d[:, None], delta.keys[k_d], base.keys[b_idx])
    vb = jnp.where(from_d, base.versions[seg_b_for_d[k_d]],
                   base.versions[b_idx])
    vd = jnp.where(from_d, delta.versions[k_d],
                   delta.versions[seg_d_for_b[b_idx]])
    v = jnp.maximum(vb, vd)
    is_inf = jnp.all(skeys == INT32_MAX, axis=-1)
    v = jnp.where((v <= floor) | is_inf, NEG_VERSION, v)

    fkeys, fv, n_used, overflow = old_dedup_compact(
        skeys, v, c, base.overflow | delta.overflow
    )
    return ck.ConflictState(
        keys=fkeys, versions=fv, n_used=n_used, oldest=floor,
        overflow=overflow,
    )


# -- states -------------------------------------------------------------------

SHAPES = {"pow2": (256, 64), "odd": (300, 50)}  # (C, Cd)
TOP = 1 << 24


def state(values, versions, capacity, w, oldest=0):
    """A step function of len(values) live rows, +inf / sentinel padded."""
    keys = np.full((capacity, w), INT32_MAX, np.int32)
    keys[: len(values)] = encode(values, w)
    vers = np.full(capacity, NEG_VERSION, np.int32)
    vers[: len(values)] = versions
    return ck.ConflictState(
        keys=keys, versions=vers, n_used=np.int32(len(values)),
        oldest=np.int32(oldest), overflow=np.zeros((), np.bool_))


def versions_for(rng, n, lo, hi):
    """Versions with neighbours that agree (a boundary that changes nothing)
    and rows that already hold the sentinel."""
    v = rng.integers(lo, hi, size=n)
    same = rng.random(n) < 0.25
    v[1:] = np.where(same[1:], v[:-1], v[1:])
    return np.where(rng.random(n) < 0.1, NEG_VERSION, v).astype(np.int32)


DELTAS = ("empty", "one", "full", "ties", "below_first", "above_last",
          "mixed", "base_full")
FLOORS = {"none": 0, "some": 700, "all": 5000}


def merge_case(w, shape, kind, seed=41):
    c, cd = SHAPES[shape]
    rng = np.random.default_rng([seed, c, w, DELTAS.index(kind)])
    nb = c if kind == "base_full" else (2 * c) // 3
    pool = 2 * np.sort(rng.choice(np.arange(500, TOP // 2 - 500),
                                  size=nb + cd, replace=False))  # all even
    in_base = np.zeros(nb + cd, bool)
    in_base[rng.choice(nb + cd, size=nb, replace=False)] = True
    base_vals, fresh = pool[in_base], pool[~in_base]
    if kind != "below_first":
        base_vals[0] = 0  # the keyspace minimum, as a served history has it
    if kind == "empty":  # what _reset_delta leaves
        delta_vals = np.array([0])
    elif kind == "one":
        delta_vals = np.array([0, fresh[len(fresh) // 2]])
    elif kind == "full":
        delta_vals = np.sort(np.concatenate(
            [base_vals[:: max(1, nb // (cd // 2))][: cd // 2],
             fresh]))[:cd]
    elif kind == "ties":  # every delta key is a base key
        delta_vals = np.sort(rng.choice(base_vals, size=cd // 2,
                                        replace=False))
    elif kind == "below_first":
        delta_vals = np.concatenate([[0], np.arange(1, cd // 2) * 7])
    elif kind == "above_last":
        delta_vals = np.concatenate(
            [[0], base_vals[-1] + 1 + np.arange(cd // 2) * 3])
    elif kind == "mixed":  # ties and new keys side by side
        delta_vals = np.unique(np.concatenate(
            [[0], rng.choice(base_vals, size=cd // 4, replace=False),
             fresh[: cd // 4]]))
    else:  # base_full: writes of one key each, between the base's keys
        at = fresh[: cd // 2 - 1]
        delta_vals = np.sort(np.concatenate([[0], at, at + 1]))
    base = state(base_vals, versions_for(rng, nb, 1, 1000), c, w)
    dvers = versions_for(rng, len(delta_vals), 500, 1500)
    if kind == "empty":
        dvers[:] = NEG_VERSION
    if kind == "base_full":  # nothing redundant on either side
        base = base._replace(
            versions=(1 + np.arange(c) % 2).astype(np.int32))
        dvers = np.where(delta_vals % 2 == 0, 1200 + np.arange(
            len(delta_vals)), NEG_VERSION).astype(np.int32)
        dvers[0] = NEG_VERSION
    delta = state(delta_vals, dvers, cd, w, oldest=3)
    return base, delta


def assert_states_equal(got, want):
    for name in ck.ConflictState._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name)), np.asarray(getattr(want, name)),
            err_msg=name)


new_merge = jax.jit(ck._merge_delta)
old_merge = jax.jit(old_merge_delta)


@pytest.mark.parametrize("floor", FLOORS)
@pytest.mark.parametrize("kind", DELTAS)
@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("w", [1, 3])
def test_merge_delta_equals_the_searching_merge(w, shape, kind, floor):
    base, delta = merge_case(w, shape, kind)
    f = np.int32(FLOORS[floor])
    want = old_merge(base, delta, f)
    assert_states_equal(new_merge(base, delta, f), want)
    if floor == "all":
        assert int(want.n_used) == 1  # the minimum alone survives
    if kind == "base_full" and floor == "none":
        assert bool(want.overflow) and int(want.n_used) == SHAPES[shape][0]


@pytest.mark.parametrize("w", [1, 3])
def test_a_tied_base_rows_version_is_never_read(w):
    """Where a base key equals a delta key the new merge reads the delta
    segment one BEFORE the one the old merge's search found for that base
    row; the row is the duplicate that keep-last dedup drops. Hold it with
    every delta version distinct and above every base version, so that a
    value read from the wrong segment would change the outcome."""
    c, cd = SHAPES["odd"]
    rng = np.random.default_rng(w)
    base_vals = np.concatenate([[0], np.sort(rng.choice(
        np.arange(1, TOP), size=c // 2, replace=False))])
    delta_vals = base_vals[:: 4][: cd - 3]
    base = state(base_vals, rng.integers(1, 100, size=len(base_vals)), c, w)
    delta = state(delta_vals, 1000 + 7 * np.arange(len(delta_vals)), cd, w)
    want = old_merge(base, delta, np.int32(0))
    assert_states_equal(new_merge(base, delta, np.int32(0)), want)
    assert int(want.n_used) == len(delta_vals)  # the delta covers it all


# -- the compaction tail alone ------------------------------------------------

RUNS = ("no_ties", "pairs", "long_runs", "all_equal", "all_inf", "min_run",
        "nothing_dropped")
ROOM = ("roomy", "tight", "one")


def compact_case(w, n, runs, seed=41):
    rng = np.random.default_rng([seed, n, w, RUNS.index(runs)])
    live = {"all_inf": 0, "nothing_dropped": n}.get(runs, (3 * n) // 4)
    if runs in ("no_ties", "nothing_dropped"):
        vals = np.sort(rng.choice(TOP, size=live, replace=False))
    elif runs == "pairs":
        vals = np.sort(np.repeat(rng.choice(TOP, size=live // 2 + 1,
                                            replace=False), 2)[:live])
    elif runs == "long_runs":  # runs of 1 to ~40 equal keys
        vals = np.sort(rng.choice(rng.choice(TOP, size=12, replace=False),
                                  size=live))
    elif runs == "all_equal":
        vals = np.full(live, 12345)
    elif runs == "min_run":  # a run of the minimum in front
        vals = np.sort(np.concatenate(
            [np.zeros(9, np.int64),
             rng.choice(np.arange(1, TOP), size=live - 9, replace=False)]))
    else:
        vals = np.zeros(0, np.int64)
    keys = np.full((n, w), INT32_MAX, np.int32)
    keys[:live] = encode(vals, w)
    if runs == "nothing_dropped":
        newv = (1 + np.arange(n) % 5).astype(np.int32)
    else:
        newv = np.full(n, NEG_VERSION, np.int32)
        newv[:live] = versions_for(rng, live, 1, 6)
    return keys, newv


new_compact = jax.jit(ck._dedup_compact, static_argnums=2)
old_compact = jax.jit(old_dedup_compact, static_argnums=2)


@pytest.mark.parametrize("room", ROOM)
@pytest.mark.parametrize("runs", RUNS)
@pytest.mark.parametrize("n", [257, 512])
@pytest.mark.parametrize("w", [1, 3])
def test_dedup_compact_equals_the_searching_compaction(w, n, runs, room):
    keys, newv = compact_case(w, n, runs)
    c_out = {"roomy": n - 7, "tight": n // 8, "one": 1}[room]
    want = old_compact(keys, newv, c_out, np.zeros((), np.bool_))
    got = new_compact(keys, newv, c_out, np.zeros((), np.bool_))
    for g, x in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(x))
    survivors = int(old_compact(keys, newv, n, np.zeros((), np.bool_))[2])
    assert bool(want[3]) == (survivors > c_out)
    if runs == "no_ties":
        assert bool(want[3]) == (room != "roomy")  # both sides of c_out
    if runs == "nothing_dropped":
        assert survivors == n and bool(want[3])


# -- the paint through it -----------------------------------------------------


def paint_full_keys(state, batch, accepted, commit_version, new_oldest):
    """ck._paint_tail fed W-word keys, as the kernel's full-key paint fed
    it until ROADMAP C1 (the kernel now feeds it rank rows, W = 1): the
    batch's endpoints sorted on the device, one history search for both
    the containing segment and the merge path's cross-rank."""
    c, w = state.keys.shape
    b, q, _ = batch.write_begin.shape
    e2 = b * q
    valid = (accepted[:, None] & batch.write_mask
             & lex_lt(batch.write_begin, batch.write_end))
    inf_row = jnp.full((w,), INT32_MAX, jnp.int32)
    wb = jnp.where(valid[..., None], batch.write_begin, inf_row).reshape(e2, w)
    we = jnp.where(valid[..., None], batch.write_end, inf_row).reshape(e2, w)
    new_keys = jnp.concatenate([wb, we])
    flat = valid.reshape(e2).astype(jnp.int32)
    cross_rank = searchsorted_words(state.keys, new_keys, side="right")
    new_oldv = state.versions[jnp.maximum(cross_rank - 1, 0)]
    return ck._paint_tail(
        state, *sort_keys_with_payload(
            new_keys, jnp.concatenate([flat, -flat]), new_oldv, cross_rank),
        commit_version, new_oldest)


def paint(dedup_compact, monkeypatch, st, wb, we, wm, accepted, cv, floor):
    """_paint_tail with the given compaction."""
    monkeypatch.setattr(ck, "_dedup_compact", dedup_compact)
    batch = types.SimpleNamespace(write_begin=wb, write_end=we, write_mask=wm)
    return jax.jit(lambda st, acc: paint_full_keys(
        st, batch, acc, cv, floor))(st, accepted)


@pytest.mark.parametrize("floor", FLOORS)
@pytest.mark.parametrize("overlap", ["disjoint", "nested", "same_range"])
@pytest.mark.parametrize("w", [1, 3])
def test_paint_tail_through_the_new_compaction(w, overlap, floor, monkeypatch):
    c, b, q = 300, 16, 4
    rng = np.random.default_rng([w, FLOORS[floor], len(overlap)])
    vals = np.concatenate([[0], np.sort(rng.choice(
        np.arange(1, TOP), size=c // 2, replace=False))])
    st = state(vals, versions_for(rng, len(vals), 1, 1000), c, w)
    if overlap == "disjoint":
        cuts = np.sort(rng.choice(TOP, size=2 * b * q, replace=False))
        begin, end = cuts[0::2], cuts[1::2]
    elif overlap == "nested":
        begin = rng.integers(0, TOP // 2, size=b * q)
        end = begin + rng.integers(1, TOP // 2, size=b * q)
    else:  # every txn paints the same few ranges: long runs of equal keys
        begin = np.tile(vals[[3, 9, 40, 41]], b)
        end = np.tile(vals[[5, 9, 44, 90]], b)  # one of them empty
    wb = encode(begin, w).reshape(b, q, w)
    we = encode(end, w).reshape(b, q, w)
    wm = rng.random((b, q)) < 0.8
    accepted = rng.random(b) < 0.7
    args = (st, wb, we, wm, accepted, np.int32(2000),
            np.int32(FLOORS[floor]))
    want = paint(old_dedup_compact, monkeypatch, *args)
    got = paint(ck._dedup_compact, monkeypatch, *args)
    assert_states_equal(got, want)


# -- what it lowers to --------------------------------------------------------


def while_carries(jaxpr):
    """Element counts of what every loop under `jaxpr` carries from one turn
    to the next (not the tables it only reads: those are constants of the
    loop, though StableHLO lists them among its operands). A ``fori_loop``
    of known length is a ``scan`` here and a ``while`` once lowered."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            skip = eqn.params["cond_nconsts"] + eqn.params["body_nconsts"]
            out.append([int(np.prod(v.aval.shape)) for v in eqn.invars[skip:]])
        if eqn.primitive.name == "scan":
            lo = eqn.params["num_consts"]
            out.append([int(np.prod(v.aval.shape))
                        for v in eqn.invars[lo: lo + eqn.params["num_carry"]]])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += while_carries(sub)
    return out


@pytest.mark.parametrize("merge", ["new", "old"])
def test_hist_merge_lowers_without_a_search_over_the_capacity(
        merge, monkeypatch):
    """A search on the device is a ``while`` that carries its bounds, one a
    query. The merge's three searches over the capacity (``_while.39``,
    ``_while.56``, ``_while.57`` of the chip's traces: 2.17 of 3.03 s busy;
    ledger, PR 40) are gone; the one left, the delta's rows into the base,
    carries Cd elements. Everything over the capacity is a histogram, a
    prefix sum, conditional streaming shifts and a scatter. The old body is
    run through the same check to show that the check can fail."""
    c, cd = 4096, 64
    if merge == "old":
        monkeypatch.setattr(ck, "_merge_delta", old_merge_delta)
    hist = ck.init_hist(c, 1, np.zeros(1, np.int32), cd)
    args = (hist, np.int32(2 * cd), np.int32(0))
    carries = while_carries(
        jax.make_jaxpr(lambda *a: ck._maybe_merge(*a))(*args).jaxpr)
    assert carries, "the delta's search into the base is a while"
    widest = max(max(c_) for c_ in carries)
    if merge == "old":
        assert widest >= c + cd and len(carries) == 4
        return
    assert widest <= cd and len(carries) == 1
    text = jax.jit(lambda *a: ck._maybe_merge(*a)).lower(*args).as_text()
    assert "stablehlo.scatter" in text and "stablehlo.case" in text
    assert text.count("stablehlo.while") == 1


# -- the counter --------------------------------------------------------------


def point(key):
    return KeyRange(key, key + b"\x00")


def test_hist_merges_counts_the_dispatches_that_merged_and_every_advance():
    """``hist_merges`` is the device's own count (HistState.merges), a word
    of the capacity reading: a dispatch merges exactly when its delta
    cannot take the boundaries it may paint (no floor moves here, so the
    rule's other arm, reclaimable base rows, never fires), and advance()
    merges every time."""
    batch, q = 8, 2
    cs = TPUConflictSet(capacity=1 << 10, dict_capacity=1 << 10,
                        batch_size=batch, max_read_ranges=2,
                        max_write_ranges=q, max_key_bytes=16)
    cd = cs.delta_capacity
    assert cd == 2 * batch * q + 2 and cs.hist_merges == 0
    rng = np.random.default_rng(41)
    expected, version = 0, 0

    def txns(n_writes):
        keys = rng.choice(5000, size=(batch, q), replace=False)
        return [TxnConflictInfo(
            read_version=version,
            read_ranges=[point(b"r%06d" % keys[i, 0])],
            write_ranges=[point(b"w%06d" % k) for k in keys[i, :n_writes]])
            for i in range(batch)]

    def collect_with_reading(c):
        c.enqueue_reading()
        c()
        assert c.reading() is not None

    read = 0  # what the last reading said
    for step, n_writes in enumerate([2, 2, 1, 1, 2, 1, 2, 2, 1, 2]):
        version += 10
        used = int(np.asarray(cs._hist_core.delta.n_used))
        expected += used + 2 * batch * n_writes > cd
        if step % 2:
            cs.resolve(txns(n_writes), version, 0)  # fetches no reading
        else:
            collect_with_reading(cs.resolve_async(txns(n_writes), version, 0))
            read = expected
        assert cs.hist_merges == read
    assert 3 <= expected <= 8  # some dispatches merged, some did not
    for _ in range(3):
        version += 10
        cs.advance(version, 0)
        expected += 1
    version += 10
    used = int(np.asarray(cs._hist_core.delta.n_used))
    expected += used + 2 * batch > cd
    collect_with_reading(cs.resolve_async(txns(1), version, 0))
    assert cs.hist_merges == expected == int(np.asarray(cs._hist_core.merges))
    assert cs.dict_stats["dispatches"] == 11


def test_the_reading_counts_the_base_as_the_next_merge_would_leave_it():
    """The base is frozen between merges, so it holds rows that expired
    since the last one; the next merge drops them before anything can
    overflow, and the capacity reading (headroom(), the collector's
    reading) does not count them as used. It never promises more room
    than the merge then leaves."""
    batch, q = 8, 2
    cs = TPUConflictSet(capacity=1 << 12, dict_capacity=1 << 12,
                        batch_size=batch, max_read_ranges=2,
                        max_write_ranges=q, max_key_bytes=16)
    rng = np.random.default_rng(7)
    keys = rng.permutation(5000)
    for step in range(6):  # 32 boundaries a dispatch into a delta of 34
        cs.resolve([TxnConflictInfo(
            read_version=10 * step, read_ranges=[],
            write_ranges=[point(b"w%06d" % k)
                          for k in keys[step * 16 + i * q:][:q]])
            for i in range(batch)], 10 * (step + 1), 0)
    # A read-only dispatch slides the floor past the first four batches and
    # paints nothing, so nothing merges: their rows stay in the base.
    reader = TxnConflictInfo(read_version=990, read_ranges=[point(b"r")],
                             write_ranges=[])
    c = cs.resolve_async([reader], 1000, 45)
    c.enqueue_reading()
    c()
    hc = cs._hist_core
    merges = int(np.asarray(hc.merges))
    raw = cs.capacity - int(np.asarray(hc.base.n_used)) - int(
        np.asarray(hc.delta.n_used))
    room = cs.headroom()
    assert c.reading() == (room, False)
    assert room >= raw + 100  # ~128 expired boundaries are not in the way
    cs.advance(1010, 45)  # the merge itself, at the same floor
    assert int(np.asarray(cs._hist_core.merges)) == merges + 1
    assert raw < room <= cs.headroom() + 1  # the empty delta's one row


# -- the reading with a shard axis (PR 45) ------------------------------------


def stacked_leaves(seed, shards, c=256):
    """Random window-history leaves a shard, as the mesh engine stacks them:
    a base whose versions step up and down with runs of equal and of
    expired values, counts, floors, flags."""
    rng = np.random.default_rng(seed)
    versions = np.full((shards, c), NEG_VERSION, np.int32)
    base_n = rng.integers(1, c, shards).astype(np.int32)
    for d in range(shards):
        runs = rng.integers(0, 40, base_n[d]).astype(np.int32) * 10
        runs[rng.random(base_n[d]) < 0.3] = NEG_VERSION
        versions[d, :base_n[d]] = np.repeat(
            runs[::3], 3)[:base_n[d]] if seed % 2 else runs
    return dict(
        versions=versions, base_n=base_n,
        delta_n=rng.integers(1, 64, shards).astype(np.int32),
        floor=rng.integers(0, 300, shards).astype(np.int32),
        base_over=rng.random(shards) < 0.2,
        delta_over=np.zeros(shards, bool),
        merges=rng.integers(0, 50, shards).astype(np.int32))


def reading_of(x, d=None):
    pick = (lambda a: a) if d is None else (lambda a: a[d])
    return [int(v) for v in np.asarray(ck._capacity_reading_jit(
        (pick(x["base_n"]), pick(x["delta_n"])),
        (pick(x["base_over"]), pick(x["delta_over"])), pick(x["merges"]),
        (pick(x["versions"]), pick(x["floor"]))))]


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_the_reading_with_a_shard_axis_is_the_fullest_shards(seed):
    """Stacked leaves: the slots in use are the maximum over the shards of
    the reading each shard gives alone, any overflow flag counts, and the
    merges are summed; _rows_in_use_jit, the split policy's probe, gives
    the same counts a shard."""
    x = stacked_leaves(seed, shards=4)
    alone = [reading_of(x, d) for d in range(4)]
    assert reading_of(x) == [max(r[0] for r in alone),
                             int(any(r[1] for r in alone)),
                             sum(r[2] for r in alone)]
    rows = np.asarray(ck._rows_in_use_jit(
        (x["base_n"], x["delta_n"]), (x["versions"], x["floor"])))
    assert [int(r) for r in rows] == [r[0] for r in alone]
    assert len({r[0] for r in alone}) > 1  # the shards do differ


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_the_reading_without_a_shard_axis_is_what_it_was(seed):
    """No shard axis: PR 41's rule, computed here in numpy from the same
    leaves: the base counts one slot where its versions, clamped at the
    floor, change, never more than it holds; plus the delta's rows."""
    x = stacked_leaves(seed, shards=1)
    v = x["versions"][0]
    v = np.where(v <= x["floor"][0], NEG_VERSION, v)
    steps = 1 + int((v[1:] != v[:-1]).sum())
    want = [min(int(x["base_n"][0]), steps) + int(x["delta_n"][0]),
            int(x["base_over"][0]), int(x["merges"][0])]
    assert reading_of(x, 0) == want
    # and the plain history's, which has no frozen base
    plain = ck._capacity_reading_jit(
        (x["base_n"][0],), (x["base_over"][0],), np.int32(0))
    assert [int(a) for a in np.asarray(plain)] == [
        int(x["base_n"][0]), int(x["base_over"][0]), 0]
    # the stacked plain history (one level a shard)
    many = stacked_leaves(seed, shards=4)
    plain = ck._capacity_reading_jit(
        (many["base_n"],), (many["base_over"],), np.int32(0))
    assert [int(a) for a in np.asarray(plain)] == [
        int(many["base_n"].max()), int(many["base_over"].any()), 0]
