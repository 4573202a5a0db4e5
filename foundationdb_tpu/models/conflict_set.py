"""Host-side ConflictSet API over the jitted kernel.

This is the seam the reference exposes as ``newConflictSet()`` /
``ConflictBatch`` (fdbserver/ConflictSet.h): the runtime's Resolver role
(runtime/resolver.py) talks to this class and never sees device tensors.
Responsibilities here: pad/pack byte-range batches into static-shape tensors,
chunk oversized batches (sub-batches at the same commit version are exactly
equivalent — earlier chunks' writes are painted at cv before later chunks
resolve, which reproduces in-batch ordering), give a transaction with more
conflict ranges than the padded width continuation rows (judged exactly, as
the reference's skiplist judges it: nothing is widened; see _pack and
conflict_kernel.txn_segments), and manage the absolute↔relative version
mapping with periodic device rebase.
"""

from __future__ import annotations

import ctypes
import functools
import os
import struct
import threading
from time import perf_counter as _perf_counter
from collections import deque
from typing import Callable, NamedTuple

import numpy as np

from foundationdb_tpu.core.keypack import INT32_MAX, KeyCodec
from foundationdb_tpu.core.types import KeyRange, TxnConflictInfo, Verdict
from foundationdb_tpu.models import conflict_kernel as ck
from foundationdb_tpu.obs.span import stage_timer

DEFAULT_WINDOW_VERSIONS = 5_000_000  # ~5s at 1M versions/sec, reference MVCC window
_REBASE_THRESHOLD = 1 << 30


class _Collector:
    """What ``resolve_async`` returns. Calling it is the blocking read and
    gives the verdicts. A caller that will dispatch again before it
    collects calls ``enqueue_reading()`` at once, before anything else
    may donate the state: ``reading()`` then gives (headroom, overflowed)
    of the state THIS batch left, taken on the device right behind its
    last chunk; a caller that never asked gets None and reads the engine
    (``headroom()``, ``overflowed``) as before. ``stage_s`` and
    ``version`` are the batch's own stage record (the engine's
    ``last_stage_s`` at dispatch) and commit version: a later batch may
    have been dispatched by the time this one is collected, and its
    stages must not land here nor this one's there."""

    __slots__ = ("_engine", "_pending", "_reading", "stage_s", "version")

    def __init__(self, engine, pending: list, version: int):
        self._engine = engine
        self._pending = pending
        self._reading = None
        self.stage_s = engine.last_stage_s
        self.version = version

    def __call__(self) -> list:
        return self._engine._collect(self._pending, self.stage_s,
                                     self.version)

    def enqueue_reading(self) -> None:
        self._reading = self._engine._enqueue_reading(self.version)

    def reading(self) -> "tuple[int, bool] | None":
        if self._reading is None:
            return None
        used, over, merges = (int(x) for x in np.asarray(self._reading))
        self._engine.hist_merges = merges
        return self._engine._headroom_of(used), bool(over)


def _staged(stage: str):
    """Method decorator: the call is stage `stage` of the dispatch in hand
    (obs/span.py stage_timer: seconds into ``self.last_stage_s``, a
    TraceAnnotation tagged with the commit version being resolved)."""
    def deco(fn):
        @functools.wraps(fn)
        def timed(self, *args, **kwargs):
            with stage_timer(self.last_stage_s, stage, self._last_commit):
                return fn(self, *args, **kwargs)
        return timed
    return deco


# Programs this PROCESS built, and the seconds that took: monotonic since
# count_compiles() was first called; read as window differences, so that a
# compile inside a measured window can be told from a stall.
_COMPILE_STATS = {"compiles": 0, "compile_s": 0.0}
_compile_listener_on = False


def count_compiles() -> None:
    """Register, once a process, a jax.monitoring duration listener on
    ``/jax/core/compile/backend_compile_duration`` — the event JAX records
    around every program it compiles or loads from the persistent cache
    (jax._src.dispatch.BACKEND_COMPILE_EVENT). Called by warm_up, i.e. by
    the process that owns the engine; counts every program of the
    process, not this engine's alone."""
    global _compile_listener_on
    if _compile_listener_on:
        return
    _compile_listener_on = True
    import jax.monitoring

    def on_duration(event: str, duration_secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILE_STATS["compiles"] += 1
            _COMPILE_STATS["compile_s"] += duration_secs

    jax.monitoring.register_event_duration_secs_listener(on_duration)


# ---------------------------------------------------------------------------
# Resident-dictionary host mirror
# ---------------------------------------------------------------------------
#
# The host keeps a sorted mirror of the device-resident endpoint-key
# dictionary so per-dispatch rank computation is a membership lookup plus
# arithmetic instead of a dedup+sort of the batch's endpoints, and only the
# never-before-seen keys (the DELTA) ever cross PCIe. Keys are
# compared as uint64 column pairs (the packed int32 words re-biased and
# packed big-endian two-per-word), so every comparison in the vectorized
# binary search below is a native numpy op — no structured-dtype memcmp
# dispatch on the hot path.


def _rows_to_u64(rows: np.ndarray) -> np.ndarray:
    """[n, W] packed int32 key rows -> [n, ceil(W/2)] uint64 columns whose
    lexicographic order (and equality) equals key order. The sign bias is
    one uint32 XOR (re-biasing to unsigned), then word pairs combine."""
    n, w = rows.shape
    u = np.ascontiguousarray(rows).view(np.uint32) ^ np.uint32(0x80000000)
    if w % 2:
        u = np.concatenate([u, np.zeros((n, 1), np.uint32)], axis=1)
    return (u[:, 0::2].astype(np.uint64) << np.uint64(32)) | u[:, 1::2]


def _u64_lt(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Lexicographic a < b over trailing uint64 columns (vectorized)."""
    out = np.zeros(a.shape[:-1], bool)
    eq = np.ones(a.shape[:-1], bool)
    for j in range(a.shape[-1]):
        out |= eq & (a[..., j] < b[..., j])
        eq &= a[..., j] == b[..., j]
    return out


def _insert_sorted(arr: np.ndarray, ins: np.ndarray,
                   vals: np.ndarray) -> np.ndarray:
    """``np.insert(arr, ins, vals, axis=0)`` for nondecreasing `ins`.

    np.insert copies the old rows through a boolean mask, a pass that
    costs the same whether one row arrives or a thousand. A delta of a few
    keys (a point-key cluster's steady state: ~5 never-seen keys a batch)
    is spliced in by slice copies instead, a Python step a new row and
    plain memory copies between them, which is what keeps ``dict_rank``
    from growing with whatever a bulk load left in the dictionary. On the
    build sandbox's CPU (PR 36; 54,000 and 400,000 rows of 9 int32 or 4
    uint64) the two cost the same near len(arr) / 65 new rows: 0.78 ms
    against 0.16 at 5 rows of 54,000, 0.78 against 0.94 at 1,000. The
    slice path is taken only far below that (a thousandth), so the large
    deltas of never-seen-key traffic splice exactly as they always did."""
    m = len(ins)
    if m * 1024 > len(arr):
        return np.insert(arr, ins, vals, axis=0)
    out = np.empty((len(arr) + m, *arr.shape[1:]), arr.dtype)
    lo = 0
    for j, p in enumerate(ins.tolist()):
        out[lo + j: p + j] = arr[lo:p]
        out[p + j] = vals[j]
        lo = p
    out[lo + m:] = arr[lo:]
    return out


def _u64_searchsorted(sorted2d: np.ndarray, q: np.ndarray,
                      side: str = "left") -> np.ndarray:
    """Multi-column searchsorted over the uint64 mirror columns.

    Two-level: one NATIVE np.searchsorted per side on column 0 (the first
    8 key bytes — this is the C-speed heavy lifting), then a short
    vectorized binary search on the remaining columns INSIDE each
    equal-column-0 run. Runs are tiny in practice (a key and its
    point-range end share the first 8 bytes), so the refinement costs a
    couple of light passes; the worst case degrades to the plain
    vectorized search."""
    d = sorted2d.shape[0]
    n = q.shape[0]
    if d == 0:
        return np.zeros(n, np.int64)
    col0 = sorted2d[:, 0]
    if sorted2d.shape[1] == 1:
        return np.searchsorted(col0, q[:, 0], side=side).astype(np.int64)
    lo = np.searchsorted(col0, q[:, 0], side="left").astype(np.int64)
    hi = np.searchsorted(col0, q[:, 0], side="right").astype(np.int64)
    rest = sorted2d[:, 1:]
    qrest = q[:, 1:]
    max_run = int((hi - lo).max(initial=0))
    for _ in range(int(max_run + 1).bit_length()):
        act = lo < hi
        if not act.any():
            break
        mid = (lo + hi) >> 1
        rows = rest[np.minimum(mid, d - 1)]
        go = (_u64_lt(rows, qrest) if side == "left"
              else ~_u64_lt(qrest, rows))
        lo = np.where(act & go, mid + 1, lo)
        hi = np.where(act & ~go, mid, hi)
    return lo


def _u64_unique_sorted(u: np.ndarray, rows: np.ndarray):
    """Sort+dedup a small u64 key set, carrying the int32 rows along."""
    order = np.lexsort(tuple(u[:, j] for j in reversed(range(u.shape[1]))))
    us = u[order]
    keep = np.ones(len(us), bool)
    if len(us) > 1:
        keep[1:] = (us[1:] != us[:-1]).any(axis=1)
    return us[keep], rows[order][keep]


class _RepackPlan(NamedTuple):
    """A pack that overflowed the resident dictionary, deferred to the
    dispatch thread (the repack needs EXACT device liveness — a sync the
    packing thread must not perform while windows are in flight). The
    mirror gate is held until dispatch executes the plan; the single pack
    worker therefore stalls the pipeline for exactly one repack."""

    bt: object  # the raw BatchTensors (key space)
    qu: np.ndarray  # [n, U] endpoint u64 keys, flat pack order
    is_pad: np.ndarray  # [n] all-inf rows (masked slots / +inf ends)
    new_u64: np.ndarray  # sorted-unique never-seen keys
    new_rows: np.ndarray  # their int32 rows
    dims: tuple  # (lead, b, r, q, w)
    cv: int
    cause: "str | None"  # the repacks_* counter that decided it


class _DemotePlan(NamedTuple):
    """A pack whose merged key count crossed the tiered engine's hot-tier
    watermark, deferred to the dispatch thread exactly like _RepackPlan:
    victim selection needs EXACT device liveness (a sync the packing
    thread must not perform while windows are in flight). The mirror gate
    is held until dispatch demotes and re-packs; unlike a repack, the
    device traffic is a tiny int32 rank vector, not the whole dictionary."""

    bt: object  # the raw BatchTensors (key space)
    qu: np.ndarray  # [n, U] endpoint u64 keys, flat pack order
    is_pad: np.ndarray  # [n] all-inf rows (masked slots / +inf ends)
    new_u64: np.ndarray  # sorted-unique delta keys (misses + promotions)
    new_rows: np.ndarray  # their int32 rows
    dims: tuple  # (lead, b, r, q, w)
    cv: int


_HASH_C1 = np.uint64(0x9E3779B97F4A7C15)
_HASH_C2 = np.uint64(0xFF51AFD7ED558CCD)


class _ResidentMirror:
    """Host mirror of the device-resident dictionary.

    Two coupled views: a SORTED view (u64/rows/last_used/pinned — the
    rank space the device shares) and a stable ID space probed through a
    vectorized open-addressing hash table (tab: slot -> id, linear
    probing, load factor <= 1/4). Per-dispatch membership + rank is a few
    vectorized gathers — measured ~3.5x faster than even a native
    searchsorted over the endpoint set, which is what buys the host-pack
    cut the resident design is for. Ids are append-only between resets
    (full repack / reshard rebuilds everything); ``rank_of_id`` re-scatters
    on every insert so id -> current rank stays exact as inserts shift
    the rank space."""

    # frag_due's share of the capacity, as a divisor: a quarter. Half of a
    # dictionary that is over half full is what a frag_due repack means to
    # free, and what the dictionary has to grow by before the next.
    FRAG_SHARE = 4

    def __init__(self, rows: np.ndarray, capacity: int, delta_slots: int,
                 tiered: bool = False):
        self.capacity = int(capacity)
        self.delta_slots = int(delta_slots)
        # Tiered mode (FDB_TPU_DICT_HOT_CAPACITY): the ID space is
        # promoted from "mirror" to authoritative COLD STORE. Ids of
        # demoted keys keep their tab entries, u64 rows and last-used
        # versions; only the sorted (rank-space) view shrinks. probe()
        # therefore still finds cold keys — the pack path routes those
        # hits through the normal never-seen-key delta (a PROMOTION).
        self.tiered = bool(tiered)
        self._n_ids = 0
        rows = np.asarray(rows, np.int32).copy()
        u64 = _rows_to_u64(rows)
        t = 16
        while t < 4 * self.capacity:
            t <<= 1
        self._mask = np.int64(t - 1)
        self.tab = np.full(t, -1, np.int64)
        self.u64_by_id = np.zeros((self.capacity + 1, u64.shape[1]),
                                  np.uint64)
        self.rank_of_id = np.zeros(self.capacity + 1, np.int64)
        # last-used versions live in ID space (scatter-only on the hot
        # path); used_sorted() materializes the rank-space view on the
        # rare repack/reshard paths that need it.
        self.last_used_by_id = np.zeros(self.capacity + 1, np.int64)
        self.hot_by_id = np.zeros(self.capacity + 1, bool)
        self.reset(u64, rows, np.zeros(len(rows), np.int64),
                   np.ones(len(rows), bool))
        # What frag_due decides from, as the last full repack left it
        # (repacked()): the key count it ended with, the stale rows it had
        # to keep (at the start the seed rows: pinned, never used), and
        # whether a frag_due repack has freed nothing since a repack last
        # freed a row.
        self._n_repacked = self.n
        self._stale_held = self.n
        self._frag_barren = False
        self.lock = threading.RLock()
        # Deferred-repack handshake: cleared when a pack emits a
        # _RepackPlan, set again once the dispatch thread executes it —
        # the next pack blocks at entry so its deltas are computed against
        # the post-repack mirror.
        self.gate = threading.Event()
        self.gate.set()
        self.stats = {
            "dispatches": 0,
            "endpoints": 0,
            "endpoint_hits": 0,
            "unique_keys": 0,
            "delta_new_keys": 0,
            # Dispatches whose DEVICE delta carried no key (none was new,
            # or a full repack took the new keys in with it): apply_delta's
            # lax.cond skips the merge on exactly these, so the share of
            # dispatches that pay for it is 1 - this / dispatches.
            "delta_empty_dispatches": 0,
            "evictions": 0,
            "full_repacks": 0,
            # Why a full repack was decided (the arms of _pack_resident's
            # need_repack, first that holds; their sum is full_repacks
            # when no tiered fallback fires) and the seconds spent inside
            # _repack_and_rank. Monotonic; read as window differences.
            "repacks_delta_overflow": 0,
            "repacks_dict_full": 0,
            "repacks_frag_due": 0,
            "repack_s": 0.0,
            "repack_stalls": 0,
            # Tiered-dictionary economics (zero when tiering is off):
            "demotions": 0,        # keys moved hot -> cold via _dict_evict
            "promotions": 0,       # cold keys re-entered through the delta
            "demotion_stalls": 0,  # packs deferred behind a _DemotePlan
            "demotion_bytes": 0,   # device bytes shipped by evict deltas
            "demotion_events": 0,  # _demote_now calls that evicted > 0
        }

    @property
    def n(self) -> int:
        return len(self.u64)

    @property
    def cold_n(self) -> int:
        """Keys resident only in the host cold tier (0 when untired —
        every id is then in the sorted hot view)."""
        return self._n_ids - self.n

    def _hash(self, u64: np.ndarray) -> np.ndarray:
        h = u64[:, 0] * _HASH_C1
        for j in range(1, u64.shape[1]):
            h = (h ^ u64[:, j]) * _HASH_C2
        return ((h ^ (h >> np.uint64(33))) & np.uint64(self._mask)).astype(
            np.int64
        )

    def reset(self, u64, rows, last_used, pinned) -> None:
        """Rebuild the sorted view from a fresh sorted key set (repack and
        reshard path; the delta path uses incremental insert_new).

        Untired: the ID space rebuilds too (ids == sorted positions).
        Tiered: the ID space is the cold store and SURVIVES — existing
        keys keep their stable ids, keys leaving the hot view demote
        instead of vanishing, genuinely new keys allocate fresh ids — so
        a full repack or scoped reshard never forgets the cold tier."""
        n = len(u64)
        if self.tiered and self._n_ids:
            ids = self.probe(u64)
            alloc = np.flatnonzero(ids < 0)
            self._ensure_ids(self._n_ids + len(alloc))
            fresh = self._n_ids + np.arange(len(alloc), dtype=np.int64)
            ids[alloc] = fresh
            self.u64_by_id[fresh] = u64[alloc]
            self._n_ids += len(alloc)
            self.u64, self.rows = u64, rows
            self.pinned = pinned
            self.hot_by_id[: self._n_ids] = False
            self.hot_by_id[ids] = True
            self.last_used_by_id[ids] = last_used
            self.id_at = ids
            self.rank_of_id[ids] = np.arange(n)
            self._tab_insert(fresh)
            return
        self.u64, self.rows = u64, rows
        self.pinned = pinned
        self._n_ids = n
        self.u64_by_id[:n] = u64
        self.last_used_by_id[:n] = last_used  # ids == sorted pos at reset
        self.id_at = np.arange(n, dtype=np.int64)  # sorted pos -> id
        self.rank_of_id[:n] = np.arange(n)
        self.hot_by_id[:] = False
        self.hot_by_id[:n] = True
        self.tab[:] = -1
        self._tab_insert(np.arange(n, dtype=np.int64))

    def _ensure_ids(self, need: int) -> None:
        """Grow the ID-space arrays (and rehash the probe table when its
        <=1/4 load bound would break) so the cold tier scales with the key
        UNIVERSE while the sorted hot view stays at hot capacity."""
        cur = len(self.u64_by_id)
        if need > cur:
            new = cur
            while new < need:
                new <<= 1
            grow = new - cur
            self.u64_by_id = np.concatenate(
                [self.u64_by_id,
                 np.zeros((grow, self.u64_by_id.shape[1]), np.uint64)]
            )
            self.rank_of_id = np.concatenate(
                [self.rank_of_id, np.zeros(grow, np.int64)]
            )
            self.last_used_by_id = np.concatenate(
                [self.last_used_by_id, np.zeros(grow, np.int64)]
            )
            self.hot_by_id = np.concatenate(
                [self.hot_by_id, np.zeros(grow, bool)]
            )
        if need * 4 > self._mask + 1:
            t = int(self._mask + 1)
            while need * 4 > t:
                t <<= 1
            self._mask = np.int64(t - 1)
            self.tab = np.full(t, -1, np.int64)
            self._tab_insert(np.arange(self._n_ids, dtype=np.int64))

    def demote(self, ranks: np.ndarray) -> np.ndarray:
        """Drop sorted-view rows at the given rank positions (the host
        half of the _dict_evict delta). Their ids stay in the cold store —
        tab entry, u64 row and last-used version intact — so a later
        probe() still finds them and promotion re-enters them through the
        normal delta with the SAME stable id. Returns the demoted ids."""
        ids = self.id_at[ranks]
        self.u64 = np.delete(self.u64, ranks, axis=0)
        self.rows = np.delete(self.rows, ranks, axis=0)
        self.pinned = np.delete(self.pinned, ranks)
        self.id_at = np.delete(self.id_at, ranks)
        self.rank_of_id[self.id_at] = np.arange(len(self.id_at))
        self.hot_by_id[ids] = False
        self.stats["demotions"] += len(ids)
        return ids

    def probe(self, qu: np.ndarray, active: "np.ndarray | None" = None):
        """ids int64 [n] (-1 = absent) for each query key row."""
        n = len(qu)
        ids = np.full(n, -1, np.int64)
        # Guard on the ID space, not the sorted view: under tiering the
        # hot view can be empty while cold ids remain probe-able (untired
        # the two counts are always equal).
        if n == 0 or self._n_ids == 0:
            return ids
        idxs = (np.flatnonzero(active) if active is not None
                else np.arange(n, dtype=np.int64))
        h = self._hash(qu[idxs])
        q = qu[idxs]
        step = np.int64(0)
        while len(idxs):
            slot = (h + step) & self._mask
            cand = self.tab[slot]
            hit = cand >= 0
            match = np.zeros(len(idxs), bool)
            if hit.any():
                rows = self.u64_by_id[cand[hit]]
                qh = q[hit]
                eq = rows[:, 0] == qh[:, 0]
                for j in range(1, rows.shape[1]):
                    eq &= rows[:, j] == qh[:, j]
                match[hit] = eq
            ids[idxs[match]] = cand[match]
            # Empty slot = definitive miss (no deletes outside reset).
            cont = hit & ~match
            idxs, h, q = idxs[cont], h[cont], q[cont]
            step += 1
            if step > self._mask:  # full-table bound (unreachable: load<=1/4)
                break
        return ids

    def touch(self, ids: np.ndarray, cv: int) -> None:
        if ids.size:
            self.last_used_by_id[ids] = cv

    def used_sorted(self) -> np.ndarray:
        """Rank-space view of the last-used versions (repack/reshard)."""
        return self.last_used_by_id[self.id_at]

    def insert_new(self, new_u64, new_rows, cv: int,
                   ids: "np.ndarray | None" = None):
        """Incremental sorted insert of delta keys; returns (their ids,
        ``ins``): ins[j] is the count of hot keys below new key j BEFORE
        the insert — the splice point here, and the device merge's
        ``cross`` rank (ck.ResidentBatch.delta_cross): the hot view is the
        device's dictionary row for row, so the kernel takes it as shipped
        and searches nothing.

        ``ids`` (tiered promotion path): per-row existing cold id, or -1
        for a genuinely new key. Cold keys re-enter the sorted view with
        their stable id (tab/u64/last-used rows already present); only
        the -1 rows allocate. Untired callers omit it — every delta key
        is then never-seen and allocates append-only, exactly as before."""
        m = len(new_u64)
        ins = _u64_searchsorted(self.u64, new_u64, "left")
        self.u64 = _insert_sorted(self.u64, ins, new_u64)
        self.rows = _insert_sorted(self.rows, ins, new_rows)
        self.pinned = _insert_sorted(self.pinned, ins, np.zeros(m, bool))
        if ids is None:
            new_ids = self._n_ids + np.arange(m, dtype=np.int64)
            self.u64_by_id[new_ids] = new_u64
            self.last_used_by_id[new_ids] = cv
            self._n_ids += m
            self.id_at = _insert_sorted(self.id_at, ins, new_ids)
            self.rank_of_id[self.id_at] = np.arange(len(self.id_at))
            self.hot_by_id[new_ids] = True
            self._tab_insert(new_ids)
            return new_ids, ins
        alloc = np.flatnonzero(ids < 0)
        self._ensure_ids(self._n_ids + len(alloc))
        new_ids = np.asarray(ids, np.int64).copy()
        fresh = self._n_ids + np.arange(len(alloc), dtype=np.int64)
        new_ids[alloc] = fresh
        self.u64_by_id[fresh] = new_u64[alloc]
        self.last_used_by_id[new_ids] = cv
        self._n_ids += len(alloc)
        self.id_at = _insert_sorted(self.id_at, ins, new_ids)
        self.rank_of_id[self.id_at] = np.arange(len(self.id_at))
        self.hot_by_id[new_ids] = True
        self.stats["promotions"] += m - len(alloc)
        self._tab_insert(fresh)
        return new_ids, ins

    def _tab_insert(self, ids: np.ndarray) -> None:
        """Vectorized linear-probing insert: same-batch slot races resolve
        by scatter-then-gather-back (losers advance with the occupied)."""
        if not len(ids):
            return
        h = self._hash(self.u64_by_id[ids])
        idxs = np.arange(len(ids), dtype=np.int64)
        step = np.int64(0)
        while len(idxs):
            slot = (h[idxs] + step) & self._mask
            empty = np.flatnonzero(self.tab[slot] < 0)
            if len(empty):
                self.tab[slot[empty]] = ids[idxs[empty]]
                won = self.tab[slot[empty]] == ids[idxs[empty]]
                done = np.zeros(len(idxs), bool)
                done[empty[won]] = True
                idxs = idxs[~done]
            step += 1
            if step > self._mask:
                raise RuntimeError("resident hash table full")

    def frag_due(self, floor_version: int) -> bool:
        """Opportunistic-repack trigger: fire only when a repack would free
        rows worth its cost, counted without a device sync. A full repack
        keeps {device-live} ∪ {pinned} ∪ {the dispatch's keys} ∪ {keys used
        at or after the MVCC floor} and drops every other key
        (TPUConflictSet._repack_and_rank), so what it can free is the stale
        keys, less those the last repack found device-live or pinned and
        had to keep (floor advanced, history not yet merged: the mirror
        cannot see when the device lets go of them). Fires when the
        dictionary is over half full, over half of it is reclaimable by
        that count, and it has grown by a quarter of its capacity
        (1 / FRAG_SHARE) since the last full repack.

        Invariant: a frag_due repack never runs twice without the
        dictionary having grown by a quarter of its capacity in between,
        and never frees 0 rows twice in a row: one that freed nothing
        shuts the trigger until a repack (a forced one: dict_full,
        delta_overflow) has freed a row again.

        Tiered engines reclaim stale keys through DEMOTION deltas instead
        (stale == the demotion victim set), so the trigger is off there."""
        grown = self.n - self._n_repacked
        if (self.tiered or self._frag_barren
                or self.n <= self.capacity // 2
                or grown < self.capacity // self.FRAG_SHARE):
            return False
        stale = int(
            (self.last_used_by_id[: self._n_ids] < floor_version).sum()
        )
        return 2 * (stale - self._stale_held) > self.n

    def repacked(self, stale_held: int, freed: int, for_frag: bool) -> None:
        """A full repack has rebuilt the sorted view (reset): record what
        frag_due counts from. ``stale_held`` is the stale rows it kept
        (device-live, pinned), ``freed`` the rows it dropped."""
        self._n_repacked = self.n
        self._stale_held = stale_held
        self._frag_barren = (for_frag or self._frag_barren) and freed == 0


class PreparedWindow(NamedTuple):
    """A host-packed dispatch window awaiting device dispatch.

    The pack half (``pack_wire_window``) is pure host work — the C wire
    pass, padding, and the rank pack against the mirror — so a scheduler
    can run it on a worker thread for window N+1 while the device still
    executes window N (sched/packing.py). The
    dispatch half (``dispatch_window``) threads device state and must run
    on the dispatching thread, in commit-version order."""

    batch: object  # device-format batch tensors, k-leading axis
    cvs_rel: np.ndarray
    olds_rel: np.ndarray
    count: int
    rebase_delta: int  # deferred device rebase; applied before dispatch


class _SpecPending(NamedTuple):
    """One speculatively dispatched window awaiting reconcile.

    ``snapshot`` is a fresh device copy of the engine state taken RIGHT
    BEFORE this window's dispatch (the resolve entry points donate their
    state argument, so the live state never double-buffers — the
    snapshot is the explicit, spec_depth-bounded HBM cost of
    speculation). Rolling a mis-speculated window back is a host pointer
    swap to this snapshot followed by a paint-only re-advance with the
    confirmed accept mask."""

    seq: int
    snapshot: object  # device state BEFORE dispatch (rollback target)
    batch: object  # device-format batch (ResidentBatch)
    cvs_rel: np.ndarray
    olds_rel: np.ndarray
    count: int
    verdicts: object  # device verdicts int8 [k, B] (still in flight)
    levels: object  # device wave levels int32 [k, B] or None


class TPUConflictSet:
    """Drop-in conflict engine: resolve(txns, commit_version) → verdicts."""

    def __init__(
        self,
        capacity: int = 1 << 16,
        batch_size: int = 512,
        max_read_ranges: int = 8,
        max_write_ranges: int = 8,
        max_key_bytes: int = 32,
        window_versions: int = DEFAULT_WINDOW_VERSIONS,
        delta_capacity: int | None = None,
        wave_commit: bool | None = None,
        dict_capacity: int | None = None,
        dict_delta_slots: int | None = None,
        dict_hot_capacity: int | None = None,
        dict_demote_batch: int | None = None,
        spec_resolve: bool | None = None,
        spec_depth: int = 2,
    ):
        self.codec = KeyCodec(max_key_bytes)
        # Speculative pipelined resolve (FDB_TPU_SPEC_RESOLVE default):
        # dispatches run against the OPTIMISTICALLY advanced state while
        # earlier windows' verdicts are still unconfirmed by the upper
        # layer; a bounded reconcile ring (spec_depth in-flight windows,
        # one device-state snapshot each) confirms or rolls back + repairs.
        # Same per-engine override shape as wave_commit.
        self.spec = (ck._SPEC_RESOLVE if spec_resolve is None
                     else bool(spec_resolve))
        self.spec_depth = max(1, int(spec_depth))
        self._spec_ring: deque[_SpecPending] = deque()
        self._spec_seq = 0
        self._spec_done: dict[int, tuple] = {}
        self._spec_stats = {
            "spec_dispatched": 0,  # windows dispatched speculatively
            "spec_confirmed": 0,   # reconciled with zero rollback
            "spec_repaired": 0,    # reconciled through rollback + repair
            "spec_flipped": 0,     # younger-window verdicts changed by repair
            "chain_rolls": 0,      # optimistic chain rolled to reconciled state
        }
        # Upper-layer confirmation hook: called at reconcile time as
        # hook(seq, verdicts[k, count]) -> bool[k, count] confirmation mask
        # (False = this txn's speculative outcome is revoked — tlog
        # failure, ratekeeper revoke, chaos injection) or None = confirm
        # all. Default None = every window confirms (the production fast
        # path; revocation is the exception speculation bets against).
        self.spec_confirm_hook: Callable | None = None
        self.dict_capacity = int(
            dict_capacity
            or int(os.environ.get("FDB_TPU_DICT_CAPACITY", "0"))
            or max(2 * capacity,
                   capacity + 4 * batch_size * (max_read_ranges
                                                + max_write_ranges))
        )
        self.dict_delta_slots = int(
            dict_delta_slots
            or int(os.environ.get("FDB_TPU_DICT_DELTA", "0"))
            or min(max(self.dict_capacity // 2, 1),
                   max(1024, 2 * batch_size * (max_read_ranges
                                               + max_write_ranges)))
        )
        # Two-tier dictionary (FDB_TPU_DICT_HOT_CAPACITY > 0): the device
        # dictionary becomes the HOT tier at
        # this capacity and the mirror's ID space the authoritative host
        # COLD store. Crossing the hot watermark demotes rank-contiguous
        # victim batches through _dict_evict (the inverse of the insert
        # delta) instead of full-repacking, so capacity follows the hot
        # set, not the key universe. 0/None = untired (bit-identical to
        # the pre-tiering engine).
        hot = int(
            dict_hot_capacity
            if dict_hot_capacity is not None
            else int(os.environ.get("FDB_TPU_DICT_HOT_CAPACITY", "0") or 0)
        )
        self.tiered = bool(hot > 0)
        if self.tiered:
            self.dict_capacity = hot
            self.dict_delta_slots = min(
                self.dict_delta_slots, max(1, hot // 2)
            )
            # Static evict-delta width (jit shape): one batch per
            # _evict_res_jit call, looped when the victim set is larger.
            self._demote_slots = int(
                dict_demote_batch
                or int(os.environ.get("FDB_TPU_DICT_DEMOTE_BATCH", "0") or 0)
                or self.dict_delta_slots
            )
            # Demotion fires when the post-merge key count would leave
            # less than one delta's headroom in the hot tier.
            self._demote_watermark = max(
                1, self.dict_capacity - self.dict_delta_slots
            )
        else:
            self._demote_slots = 0
            self._demote_watermark = 0
        # Wave-commit mode (reorder-don't-abort; conflict_kernel phase 2b):
        # None = the FDB_TPU_WAVE_COMMIT env default. Both modes' entry
        # points are distinct compiled programs, so engines of either mode
        # coexist in one process (the import-once rule only pins the env
        # DEFAULT). NOTE: a wave engine reorders txns against the FULL
        # conflict graph of its window. Single-resolver roles see it
        # whole; the mesh ShardedConflictSet OR-reduces per-shard clipped
        # graphs on-device; role-level multi-resolver deployments run the
        # two-phase global protocol (resolve_edges/resolve_apply below —
        # the commit proxy OR-reduces the shards' edge bitsets and every
        # shard levels the identical global graph).
        self.wave_commit = ck._WAVE_COMMIT if wave_commit is None else bool(
            wave_commit
        )
        self.capacity = capacity
        self.batch_size = batch_size
        self.max_read_ranges = max_read_ranges
        self.max_write_ranges = max_write_ranges
        self.window_versions = window_versions
        # Window-history delta sizing: must absorb one batch's worst-case
        # paint (the in-jit merge empties it just-in-time before a batch
        # that wouldn't fit).
        self.delta_capacity = delta_capacity or min(
            capacity, 2 * batch_size * max_write_ranges + 2
        )
        self.base_version: int | None = None
        self.oldest_version: int = 0  # absolute; advances monotonically
        self._last_commit: int = 0
        # Exact conflicting read ranges of the LAST resolve() call, by txn
        # index — populated only when some txn asked
        # (report_conflicting_keys) so the hot path pays nothing. Same
        # surface as the oracle's (reference: conflictingKRIndices); the
        # runtime Resolver reads it for the repair subsystem's reports.
        self.last_conflicting: dict[int, list[KeyRange]] = {}
        # Wave levels of the LAST resolve() call, by txn index (wave
        # engines only; None otherwise): >= 0 committed at that wave,
        # conflict_kernel.LEVEL_CYCLE aborted on a true cycle,
        # LEVEL_NONE every other non-commit. Chunked resolves offset
        # later chunks' waves past earlier ones (chunks serialize in
        # order), so the list is one coherent schedule for the call.
        self.last_wave: list[int] | None = None
        # Exact reordered count of the last resolve (wave engines only):
        # txns committed past their chunk's FIRST wave — the published
        # cross-chunk offsets deliberately excluded (see _collect_waves).
        self.last_reordered: int | None = None
        # Window-path analogue (dispatch_window collectors): int32
        # [k, count] levels, one independent wave schedule per scanned
        # batch (batches already serialize by commit version).
        self.last_wave_window: np.ndarray | None = None
        # Stage seconds of the dispatch in hand (obs/span.py ENGINE_STAGES;
        # filled through stage_timer, chunks accumulate). The resolver's
        # span sink hands in a fresh dict before a batch and reads it
        # after; with no reader it is a handful of floats that keep
        # summing. Never enters kernel state.
        self.last_stage_s: dict[str, float] = {}
        # Packs that waited for the device (_device_live_ranks: a full
        # repack, a tiered demotion). A role that keeps a batch in flight
        # reads the difference over a dispatch to know its pipeline
        # drained there.
        self.pack_syncs = 0
        # The window history's merges since boot (ck.HistState.merges; the
        # mesh engine's shards summed), as of the last capacity reading
        # collected: a word of the reading a role fetches with every
        # batch's verdicts (a collector's reading(), or headroom() for a
        # batch collected at once), so no device read of its own.
        # hist_merges / dispatches says how often a dispatch's paint did
        # not fit the delta; advance() merges every time.
        self.hist_merges = 0
        # Admission subsystem (attach_admission_filter): a RecentWritesFilter
        # fed from each dispatch's ACCEPTED write sets using the endpoint
        # u64 columns the resident pack already computed — no re-hash, no
        # extra host→device key bytes (the filter's jax banks persist on
        # device; the update operand is the write-fingerprint row the
        # dispatch shipped anyway).
        self.admission_filter = None
        self._adm_stash = None  # (write fps [b, q], valid [b, q]) per pack
        # Role-level global wave protocol (core/wavemesh): resolve_edges
        # stashes the packed chunks here until resolve_apply consumes the
        # combined global graph. None between windows; the mesh-sharded
        # subclass leaves the entry points unset (it exchanges in-jit).
        self._wave_pending = None
        self._wave_edges_fn = None
        self._wave_apply_fn = None
        self._init_engine()

    def attach_admission_filter(self, f) -> None:
        """Attach a RecentWritesFilter: every resolve feeds the accepted
        write-set fingerprints (they ARE the mirror's u64 key columns)."""
        self.admission_filter = f

    def _init_engine(self) -> None:
        """Build device state + entry points. Subclasses (the mesh-sharded
        engine) override this; all host-side logic is shared. The endpoint
        dictionary and the rank-space window history PERSIST on the device
        (ck.ResState); the packer emits rank batches + key deltas against
        the host mirror."""
        self._mirror = _ResidentMirror(
            self.codec.min_key[None, :], self.dict_capacity,
            self.dict_delta_slots, tiered=self.tiered,
        )
        self.state = ck.init_res(
            self._mirror.rows, self.dict_capacity, self.capacity,
            self.delta_capacity,
        )
        self._rebase_fn = ck._rebase_res_jit
        self._advance_hist_fn = ck._advance_hist_res_jit
        self._repack_fn = ck._repack_res_jit
        self._evict_fn = ck._evict_res_jit
        wave = self.wave_commit
        self._resolve_fn = (
            ck._resolve_res_wave_jit if wave else ck._resolve_res_jit)
        self._resolve_report_fn = (
            ck._resolve_report_res_wave_jit if wave
            else ck._resolve_report_res_jit)
        self._resolve_many_fn = (
            ck._resolve_many_res_wave_jit if wave
            else ck._resolve_many_res_jit)
        if wave:
            # Two-phase entry points for the role-level global wave
            # protocol (resolve_edges/resolve_apply).
            self._wave_edges_fn = ck._wave_edges_res_jit
            self._wave_apply_fn = ck._wave_apply_res_jit
        # Paint-only re-advance for the speculative reconcile path (no
        # _wave variant: a forced accept mask has no levels to compute —
        # wave engines paint with levels >= 0).
        self._paint_many_fn = ck._paint_many_res_jit

    # -- resident-dictionary packing -----------------------------------------

    def _flat_endpoints(self, bt: ck.BatchTensors):
        """All endpoint key rows of a (possibly [k]-leading) batch, flat in
        (read_begin, read_end, write_begin, write_end) section order."""
        rb = np.asarray(bt.read_begin)
        lead = rb.shape[:-3]
        b, r, w = rb.shape[-3:]
        q = np.asarray(bt.write_begin).shape[-2]
        flat = np.concatenate([
            rb.reshape(-1, w),
            np.asarray(bt.read_end).reshape(-1, w),
            np.asarray(bt.write_begin).reshape(-1, w),
            np.asarray(bt.write_end).reshape(-1, w),
        ])
        return flat, (lead, b, r, q, w)

    def _ranks_to_batch(self, bt: ck.BatchTensors, ranks: np.ndarray,
                        dims, delta=None) -> ck.ResidentBatch:
        """Reassemble flat endpoint ranks + a key delta into the device
        ResidentBatch. ``delta`` is (new key rows, their cross ranks as
        _ResidentMirror.insert_new returned them), or None for an EMPTY
        delta (a full repack, a warm-up). Both are padded to the engine's
        static slot count: the rows with +inf, the ranks with
        dict_capacity + 1, past every row of the device's dictionary, which
        is how the kernel tells padding from a key."""
        lead, b, r, q, w = dims
        nl = int(np.prod(lead)) if lead else 1
        n_r, n_q = nl * b * r, nl * b * q
        delta_keys = np.full((self.dict_delta_slots, w), INT32_MAX, np.int32)
        delta_cross = np.full(
            self.dict_delta_slots, self.dict_capacity + 1, np.int32
        )
        if delta is not None:
            rows, cross = delta
            delta_keys[: len(rows)] = rows
            delta_cross[: len(cross)] = cross
        wb = ranks[2 * n_r : 2 * n_r + n_q].reshape(*lead, b, q)
        we = ranks[2 * n_r + n_q :].reshape(*lead, b, q)
        # The paint permutation, precomputed here (kernel RankBatch
        # docstring: rejected writes merge as delta-0 no-ops, so the sort
        # order is acceptance-independent and the device paint is pure
        # gathers). Introsort, per scan step: order within equal-rank
        # ties is irrelevant (the coverage cumsum at a tie run's last row
        # is order-independent and keep-last dedup erases the rest), so
        # the stable kind's extra pass buys nothing.
        paint = np.concatenate(
            [wb.reshape(*lead, b * q), we.reshape(*lead, b * q)], axis=-1
        )
        paint_src = np.argsort(paint, axis=-1).astype(np.int32)
        return ck.ResidentBatch(
            delta_keys=delta_keys,
            delta_cross=delta_cross,
            ranks=ck.RankBatch(
                read_begin=ranks[:n_r].reshape(*lead, b, r),
                read_end=ranks[n_r : 2 * n_r].reshape(*lead, b, r),
                read_mask=np.asarray(bt.read_mask),
                write_begin=wb,
                write_end=we,
                write_mask=np.asarray(bt.write_mask),
                read_version=np.asarray(bt.read_version),
                txn_mask=np.asarray(bt.txn_mask),
                paint_src=paint_src,
                cont=bt.cont,
            ),
        )

    def _note_write_fps(self, qu: np.ndarray, is_pad: np.ndarray,
                        dims) -> None:
        """Stash the pack's write-begin fingerprints for the admission
        filter feed (_collect records the ACCEPTED rows once verdicts
        land). The fingerprint IS admission.filter.u64_cols_fingerprint
        over the endpoint u64 columns — one shared definition, because
        the no-re-hash feed contract depends on record and probe staying
        bit-identical — so the feed costs a vectorized mix over rows
        already computed, never a key re-hash. Window-path packs ([k]-leading) skip the stash: the
        runtime role feed goes through Resolver.admission_filter there."""
        if self.admission_filter is None:
            return
        lead, b, r, q, _w = dims
        if lead:
            self._adm_stash = None
            return
        from foundationdb_tpu.admission.filter import u64_cols_fingerprint

        n_r, n_q = b * r, b * q
        sect = slice(2 * n_r, 2 * n_r + n_q)
        fps = u64_cols_fingerprint(qu[sect])
        self._adm_stash = (fps.reshape(b, q), (~is_pad[sect]).reshape(b, q))

    @_staged("dict_rank")
    def _pack_resident(self, bt: ck.BatchTensors, defer_repack: bool = False):
        """Rank-space pack against the resident mirror: classify every
        endpoint as hit (already resident) or miss, emit the sorted-unique
        miss set as the dispatch's dictionary DELTA, and rewrite endpoints
        as ranks into the POST-merge dictionary — pure host arithmetic,
        no np.unique over the full endpoint set and no dictionary ship.

        Overflow (delta too large / dictionary full) or fragmentation
        forces a FULL REPACK, which needs exact device liveness: inline on
        the dispatching thread, or — on the threaded window path
        (``defer_repack``) — deferred to dispatch_window via _RepackPlan
        with the mirror gate held so later packs wait for the new mirror.

        Stage ``dict_rank`` (obs/span.py): the whole of this minus any
        inline repack or demotion, which are ``dict_repack``."""
        mir = self._mirror
        mir.gate.wait()
        flat, dims = self._flat_endpoints(bt)
        qu = _rows_to_u64(flat)
        # All-inf pad rows map bijectively to one u64 row — comparing the
        # (half-width) u64 columns beats a W-word reduce on the hot path.
        pad = _rows_to_u64(np.full((1, dims[-1]), INT32_MAX, np.int32))[0]
        is_pad = qu[:, 0] == pad[0]
        for j in range(1, qu.shape[1]):
            is_pad &= qu[:, j] == pad[j]
        ids = mir.probe(qu, ~is_pad)
        found = ids >= 0
        if self.tiered:
            # Cold-tier hits (probe found a demoted id) re-enter through
            # the SAME never-seen-key delta: a promotion is just a delta
            # row whose id already exists. Only hot hits skip the delta.
            hot_hit = np.zeros(len(ids), bool)
            f = np.flatnonzero(found)
            hot_hit[f] = mir.hot_by_id[ids[f]]
            miss = ~hot_hit & ~is_pad
        else:
            hot_hit = found
            miss = ~found & ~is_pad
        mi = np.flatnonzero(miss)
        if mi.size:
            new_u64, new_rows = _u64_unique_sorted(qu[mi], flat[mi])
        else:
            new_u64 = np.zeros((0, qu.shape[1]), np.uint64)
            new_rows = np.zeros((0, dims[-1]), np.int32)
        m = len(new_u64)
        cv = self._last_commit
        cause = (
            "repacks_delta_overflow" if m > self.dict_delta_slots
            else "repacks_dict_full"
            if not self.tiered and mir.n + m > mir.capacity
            else "repacks_frag_due" if mir.frag_due(self.oldest_version)
            else None
        )
        plan = _RepackPlan(bt, qu, is_pad, new_u64, new_rows, dims, cv, cause)
        if cause is not None:
            mir.stats[cause] += 1
            if defer_repack:
                mir.gate.clear()
                mir.stats["repack_stalls"] += 1
                return plan
            return self._repack_and_rank(plan, inside="dict_rank")
        if self.tiered and mir.n + m > self._demote_watermark:
            if defer_repack:
                # Same deferral contract as _RepackPlan: victim selection
                # needs the exact-liveness device sync, so the packing
                # thread hands the window to dispatch with the gate held.
                mir.gate.clear()
                mir.stats["demotion_stalls"] += 1
                return _DemotePlan(bt, qu, is_pad, new_u64, new_rows, dims, cv)
            with stage_timer(self.last_stage_s, "dict_repack", cv,
                             inside="dict_rank"):
                self._demote_now(m, protect=(qu, is_pad))
            if mir.n + m > mir.capacity:
                # Demotion could not free enough room (victims all
                # pinned, device-live or recent): the honest full-repack
                # fallback — the thrash pathology obs/doctor flags.
                return self._repack_and_rank(plan, inside="dict_rank")
        delta = None
        with mir.lock:
            mir.touch(ids[hot_hit], cv)
            if m:
                pos = _u64_searchsorted(new_u64, qu[mi], "left")
                if self.tiered:
                    # Every miss is in the new set: its index there maps
                    # it to its existing cold id (promotion) or -1 (new).
                    row_ids = np.full(m, -1, np.int64)
                    row_ids[pos] = ids[mi]
                    new_ids, cross = mir.insert_new(new_u64, new_rows, cv,
                                                    ids=row_ids)
                else:
                    # Every miss is in the new set: its index there is its
                    # id.
                    new_ids, cross = mir.insert_new(new_u64, new_rows, cv)
                ids[mi] = new_ids[pos]
                delta = (new_rows, cross)
            # Post-merge rank = current sorted position of the id.
            ranks = mir.rank_of_id[np.maximum(ids, 0)].astype(np.int32)
            ranks[is_pad | (ids < 0)] = INT32_MAX
            st = mir.stats
            st["dispatches"] += 1
            st["endpoints"] += int((~is_pad).sum())
            st["endpoint_hits"] += int(hot_hit.sum())
            fid = ids[hot_hit]
            uniq_found = (
                int(np.bincount(fid, minlength=1).astype(bool).sum())
                if fid.size else 0
            )
            st["unique_keys"] += m + uniq_found
            st["delta_new_keys"] += m
            st["delta_empty_dispatches"] += int(m == 0)
        self._note_write_fps(qu, is_pad, dims)
        return self._ranks_to_batch(bt, ranks, dims, delta)

    def _device_live_ranks(self) -> np.ndarray:
        """Exact dictionary liveness: every rank the device history still
        references (device sync — the repack-only cost). Sorted unique."""
        self.pack_syncs += 1
        hist = self.state.hist
        if isinstance(hist, ck.HistState):
            arrays = [hist.base.keys, hist.delta.keys]
        else:
            arrays = [hist.keys]
        ranks = np.concatenate(
            [np.asarray(a)[..., 0].reshape(-1) for a in arrays]
        )
        live = np.unique(ranks[ranks != INT32_MAX])
        return live[(live >= 0) & (live < self._mirror.n)]

    def _repack_and_rank(self, plan: _RepackPlan,
                         inside: "str | None" = None) -> ck.ResidentBatch:
        """Full dictionary repack: rebuild the dictionary from {live
        history ranks} ∪ {pinned} ∪ {this dispatch's keys} ∪ {keys last
        used at or after the MVCC floor}, ship it whole, and remap every
        device-held rank. What is kept is what is useful, not what fits:
        a key that is stale on the host and that the device history does
        not reference is dropped, and comes back, if ever, as a delta
        row. Raises ValueError when the live set itself does not fit. The
        rare fallback the per-delta path buys its way out of; also the
        cold-start path. What decides it: delta_overflow and dict_full by
        force, frag_due by _ResidentMirror.frag_due's counts, which this
        repack refreshes (never twice without the dictionary having grown
        by a quarter of its capacity, never 0 rows freed twice in a row).

        Stage ``dict_repack`` (carved out of ``inside`` when called from
        within another stage); its seconds also sum into the mirror's
        ``repack_s``."""
        with stage_timer(self.last_stage_s, "dict_repack", plan.cv,
                         inside=inside) as timed:
            out = self._repack_and_rank_now(plan)
        self._mirror.stats["repack_s"] += timed.seconds
        return out

    def _repack_and_rank_now(self, plan: _RepackPlan) -> ck.ResidentBatch:
        mir = self._mirror
        with mir.lock:
            try:
                live = self._device_live_ranks()
                keep = np.zeros(mir.n, bool)
                keep[live] = True
                keep |= mir.pinned
                pos = _u64_searchsorted(mir.u64, plan.qu, "left")
                cand = np.minimum(pos, max(mir.n - 1, 0))
                found = (
                    (pos < mir.n)
                    & (mir.u64[cand] == plan.qu).all(axis=1)
                    & ~plan.is_pad
                )
                keep[pos[found]] = True  # this dispatch's keys stay
                mir.touch(mir.id_at[pos[found]], plan.cv)
                m = len(plan.new_u64)
                must = int(keep.sum())
                if must + m + 1 > mir.capacity + 1:
                    raise ValueError(
                        f"resident dictionary cannot fit {must} live/pinned"
                        f" + {m} new keys in capacity {mir.capacity};"
                        " raise dict_capacity / FDB_TPU_DICT_CAPACITY"
                    )
                # Of the rest, only a key used at or after the MVCC floor
                # stays: a stale one that nothing references comes back, if
                # it ever does, as an ordinary delta row. Should more be in
                # use than fits under the delta headroom, the newest stay.
                used_sorted = mir.used_sorted()
                floor = self.oldest_version
                stale_held = int((used_sorted[keep] < floor).sum())
                fresh = np.flatnonzero(~keep & (used_sorted >= floor))
                room = max(
                    mir.capacity - self.dict_delta_slots - m - must, 0
                )
                if fresh.size > room:
                    by_age = np.argsort(used_sorted[fresh], kind="stable")
                    fresh = fresh[by_age[fresh.size - room:]]
                keep[fresh] = True
                evicted = mir.n - int(keep.sum())

                kept_u64 = mir.u64[keep]
                kept_rows = mir.rows[keep]
                kept_used = used_sorted[keep]
                kept_pin = mir.pinned[keep]
                ins = _u64_searchsorted(kept_u64, plan.new_u64, "left")
                fin_u64 = np.insert(kept_u64, ins, plan.new_u64, axis=0)
                fin_rows = np.insert(kept_rows, ins, plan.new_rows, axis=0)
                fin_used = np.insert(kept_used, ins, plan.cv)
                fin_pin = np.insert(kept_pin, ins, False)
                n_new = len(fin_u64)

                # remap: exact new rank for every kept old rank; dropped
                # ranks get their insertion point (provably dead — never
                # gathered by the device).
                remap = np.zeros(mir.capacity + 1, np.int32)
                remap[: mir.n] = _u64_searchsorted(
                    fin_u64, mir.u64, "left"
                ).astype(np.int32)
                dict_dev = np.full(
                    (mir.capacity + 1, fin_rows.shape[1]), INT32_MAX, np.int32
                )
                dict_dev[:n_new] = fin_rows
                self.state = self._repack_fn(
                    self.state, dict_dev, np.int32(n_new), remap
                )
                mir.reset(fin_u64, fin_rows, fin_used, fin_pin)
                mir.repacked(stale_held, evicted,
                             plan.cause == "repacks_frag_due")
                st = mir.stats
                st["full_repacks"] += 1
                st["evictions"] += evicted
                st["dispatches"] += 1
                st["endpoints"] += int((~plan.is_pad).sum())
                st["endpoint_hits"] += int(found.sum())
                st["unique_keys"] += m + int(np.unique(pos[found]).size)
                st["delta_new_keys"] += m
                st["delta_empty_dispatches"] += 1

                # Ranks against the rebuilt mirror; the delta already rode
                # in with the repack, so the device delta is empty.
                ranks = _u64_searchsorted(fin_u64, plan.qu, "left").astype(
                    np.int32
                )
                ranks[plan.is_pad] = INT32_MAX
            finally:
                mir.gate.set()
        self._note_write_fps(plan.qu, plan.is_pad, plan.dims)
        return self._ranks_to_batch(plan.bt, ranks, plan.dims)

    def _demote_now(self, incoming: int, protect=None) -> int:
        """Demote cold hot-tier keys to the host cold store (dispatch
        thread only — selection needs the exact-liveness device sync).

        Victim policy, in exclusion order: pinned min/bound keys never
        move; ranks the device history still references (exact
        _device_live_ranks) stay — evicting one would skew every younger
        rank through the shift table; keys used inside the in-flight MVCC
        window (last_used >= oldest_version) stay; the current dispatch's
        keys (``protect`` = its probed u64 set) stay; and when an
        admission filter is attached, keys its recency banks report
        maybe-written since the floor stay. Survivors demote
        oldest-last-used first, shipped as static-width _evict_res_jit
        rank deltas (a few KiB) — never a full repack. Returns the count
        actually demoted (0 = nothing safely evictable)."""
        mir = self._mirror
        with mir.lock:
            used = mir.used_sorted()
            cand = ~mir.pinned & (used < self.oldest_version)
            cand[self._device_live_ranks()] = False
            if protect is not None:
                qu, is_pad = protect
                pids = mir.probe(qu, ~is_pad)
                pf = pids[pids >= 0]
                hot = pf[mir.hot_by_id[pf]]
                cand[mir.rank_of_id[hot]] = False
            if self.admission_filter is not None:
                idx = np.flatnonzero(cand)
                if idx.size:
                    from foundationdb_tpu.admission.filter import (
                        u64_cols_fingerprint,
                    )
                    recent = np.asarray(
                        self.admission_filter.probe_u64(
                            u64_cols_fingerprint(mir.u64[idx]),
                            self.oldest_version,
                        )
                    )
                    cand[idx[recent]] = False
            idx = np.flatnonzero(cand)
            if not idx.size:
                return 0
            # Free past the watermark plus half a batch of hysteresis so
            # the next few windows' deltas fit without demoting again.
            over = mir.n + incoming - self._demote_watermark
            want = min(idx.size,
                       max(over, 0) + max(1, self._demote_slots // 2))
            victims = idx[np.argsort(used[idx], kind="stable")[:want]]
            order = np.sort(victims)
            done = 0
            while done < len(order):
                # Chunks ascend, so every previously evicted rank sits
                # below this chunk: the device-rank adjustment is exactly
                # the count already gone.
                chunk = order[done : done + self._demote_slots] - done
                ev = np.full(self._demote_slots, INT32_MAX, np.int32)
                ev[: len(chunk)] = chunk.astype(np.int32)
                self.state = self._evict_fn(self.state, ev)
                mir.stats["demotion_bytes"] += 4 * self._demote_slots
                done += len(chunk)
            mir.demote(order)
            mir.stats["demotion_events"] += 1
            return len(order)

    def _demote_and_rank(self, plan: _DemotePlan) -> ck.ResidentBatch:
        """Execute a deferred demotion on the dispatch thread (every
        earlier window has dispatched, so liveness is exact — the same
        ordering argument as the deferred _RepackPlan), reopen the gate,
        then re-pack the stalled window inline: the inline path
        re-derives hits/promotions against the post-demotion mirror and
        itself escalates to a full repack if demotion could not free
        enough room."""
        try:
            with stage_timer(self.last_stage_s, "dict_repack", plan.cv):
                self._demote_now(len(plan.new_u64),
                                 protect=(plan.qu, plan.is_pad))
        finally:
            self._mirror.gate.set()
        return self._pack_resident(plan.bt)

    @property
    def dict_stats(self) -> dict:
        """Dictionary-economics counters: unique keys/dispatch, delta hit
        rate, evictions, forced full repacks."""
        s = dict(self._mirror.stats, **_COMPILE_STATS)
        d = max(1, s["dispatches"])
        e = max(1, s["endpoints"])
        s.update(
            resident_keys=self._mirror.n,
            keys_widened=self.codec.keys_widened,
            dict_capacity=self._mirror.capacity,
            delta_slots=self.dict_delta_slots,
            unique_keys_per_dispatch=round(s["unique_keys"] / d, 1),
            delta_hit_rate=round(s["endpoint_hits"] / e, 4),
            # Tier economics (inert zeros when tiering is off):
            tiered=self.tiered,
            dict_hot_occupancy=round(
                self._mirror.n / max(1, self._mirror.capacity), 4
            ),
            cold_tier_keys=self._mirror.cold_n,
            demotion_bytes_per_dispatch=round(s["demotion_bytes"] / d, 1),
            # What ONE full repack ships host->device (the packed dict
            # rows + the rank-shift table) — the per-event counterfactual
            # the demotion delta replaces. The A/B multiplies this by
            # demotion_events to price the no-evict design.
            full_repack_ship_bytes=(self._mirror.capacity + 1) * 4
            * (self._mirror.rows.shape[1] + 1),
        )
        return s

    # -- public API ---------------------------------------------------------

    def resolve(
        self,
        txns: list[TxnConflictInfo],
        commit_version: int,
        oldest_version: int | None = None,
    ) -> list[Verdict]:
        return self.resolve_async(txns, commit_version, oldest_version)()

    def resolve_async(
        self,
        txns: list[TxnConflictInfo],
        commit_version: int,
        oldest_version: int | None = None,
    ) -> "_Collector":
        """Dispatch every chunk to the device immediately and return a
        collector (``_Collector``): calling it is the one blocking read,
        the verdicts. The resolver role (runtime/resolver.py
        ``_dispatch_entry``) holds one batch's collector while it packs
        and enqueues the NEXT batch, and collects one behind; the device
        orders dispatches by the state they thread, so the batch enqueued
        meanwhile resolves against exactly this one's painted history.

        The state this batch leaves is donated to that next dispatch, so
        what the role's capacity fail-safe needs of it is taken before:
        the collector's ``enqueue_reading()``, called right behind the
        last chunk, has a tiny program reduce the history's ``n_used`` /
        ``overflow`` to two scalars that are outputs, not state
        (``ck._capacity_reading_jit``), and ``reading()`` returns them
        as (headroom, overflowed) with no further wait.

        When some txn set report_conflicting_keys (and the engine compiled
        a report entry point), the kernel's loser-range mask rides along
        and the collector populates ``last_conflicting`` — exact
        conflicting read ranges per txn index, the same surface the oracle
        provides."""
        can_report = getattr(self, "_resolve_report_fn", None) is not None
        self._spec_drain_serial()
        self._begin_resolve(commit_version, oldest_version)
        cv = np.int32(self._rel(commit_version))
        oldest = np.int32(self._rel(self.oldest_version))
        pending: list[tuple] = []
        for lo, hi in self._chunks(txns):
            chunk = txns[lo:hi]
            # Per CHUNK: only chunks that actually contain a reporting txn
            # pay the report program + host-side range bookkeeping.
            if can_report and any(t.report_conflicting_keys for t in chunk):
                batch, reads = self._pack(chunk, collect_reads=True)
                # Pack BEFORE reading self.state: a resident-dictionary
                # repack inside the packer replaces (and donates) it.
                dev = self._pack_resident(_for_kernel(batch, self.wave_commit))
                with stage_timer(self.last_stage_s, "engine_enqueue",
                                 commit_version):
                    out = self._resolve_report_fn(self.state, dev, cv,
                                                  oldest)
                verdicts, levels, losers, self.state = (
                    out if self.wave_commit else (out[0], None, *out[1:])
                )
                flags = [t.report_conflicting_keys for t in chunk]
            else:
                batch = self._pack(chunk)
                # may repack: order matters
                dev = self._pack_resident(_for_kernel(batch, self.wave_commit))
                with stage_timer(self.last_stage_s, "engine_enqueue",
                                 commit_version):
                    out = self._resolve_fn(self.state, dev, cv, oldest)
                verdicts, levels, self.state = (
                    out if self.wave_commit else (out[0], None, out[1])
                )
                losers = reads = flags = None
            # The copy starts when the dispatch ends, not when the
            # collector asks: its read is then a wait, not a round trip.
            verdicts.copy_to_host_async()
            pending.append(
                (verdicts, *_rows_and_heads(batch, len(chunk)), losers,
                 reads, flags, levels, self._take_adm(commit_version))
            )
        return _Collector(self, pending, commit_version)

    def _take_adm(self, commit_version: int):
        """Claim the last pack's admission write-fingerprint stash, BOUND
        to its resolve's commit version (None when no filter is attached /
        window-path pack). The version rides in the pending tuple — NOT
        instance state — because deferred collectors pipeline: a later
        dispatch must not relabel an earlier dispatch's write versions."""
        stash, self._adm_stash = self._adm_stash, None
        return None if stash is None else (stash, commit_version)

    def resolve_wire(
        self,
        wire: bytes | np.ndarray,
        commit_version: int,
        oldest_version: int | None = None,
        count: int | None = None,
    ) -> list[Verdict]:
        return self.resolve_wire_async(wire, commit_version, oldest_version, count)()

    def resolve_wire_async(
        self,
        wire: bytes | np.ndarray,
        commit_version: int,
        oldest_version: int | None = None,
        count: int | None = None,
        as_array: bool = False,
    ) -> Callable[[], list[Verdict]]:
        """The production hot path: a flat serialized resolver batch (see
        native/keypack.cpp for the wire format — the analogue of the
        reference's ResolveTransactionBatchRequest bytes) is packed into
        device tensors by one C pass, never touching per-txn Python objects."""
        buf = np.frombuffer(wire, dtype=np.uint8) if isinstance(wire, (bytes, bytearray)) else wire
        lib = _keypack_lib()
        # Structurally validate the WHOLE buffer before any dispatch: a chunk
        # failing mid-stream would leave earlier chunks' writes painted into
        # device history with no verdicts delivered (phantom conflicts
        # forever). kp_count_txns walks every record's bounds in one C pass.
        counted = int(lib.kp_count_txns(_u8(buf), buf.size, 0))
        if counted < 0 or (count is not None and count > counted):
            raise ValueError("malformed resolver wire batch")
        if count is None:
            count = counted
        self._spec_drain_serial()
        self._begin_resolve(commit_version, oldest_version)
        cv = np.int32(self._rel(commit_version))
        oldest = np.int32(self._rel(self.oldest_version))
        pending: list[tuple] = []
        offset, remaining = 0, count
        while remaining > 0:
            # Fewer than asked for where wide transactions fill the rows.
            batch, offset, n = self._pack_wire(
                buf, offset, min(remaining, self.batch_size))
            # may repack: order matters
            dev = self._pack_resident(_for_kernel(batch, self.wave_commit))
            with stage_timer(self.last_stage_s, "engine_enqueue",
                             commit_version):
                out = self._resolve_fn(self.state, dev, cv, oldest)
            verdicts, levels, self.state = (
                out if self.wave_commit else (out[0], None, out[1])
            )
            pending.append((verdicts, *_rows_and_heads(batch, n), None,
                            None, None, levels,
                            self._take_adm(commit_version)))
            remaining -= n
        if as_array:

            def collect_array():
                self._collect_waves(pending)
                self._feed_admission(pending)
                return np.concatenate(
                    [_per_txn(v, n, heads) for v, n, heads, *_r in pending]
                )

            return collect_array
        return _Collector(self, pending, commit_version)

    def resolve_wire_window(
        self,
        wire: bytes | np.ndarray,
        commit_versions,
        count: int,
    ) -> np.ndarray:
        return self.resolve_wire_window_async(wire, commit_versions, count)()

    def resolve_wire_window_async(
        self,
        wire: bytes | np.ndarray,
        commit_versions,
        count: int,
    ) -> Callable[[], np.ndarray]:
        """Resolve a WINDOW of k consecutive batches in one device dispatch.

        ``wire`` holds k·count txns; txns [i·count, (i+1)·count) resolve at
        ``commit_versions[i]`` (strictly increasing). One lax.scan program
        (conflict_kernel.resolve_many) replaces k dispatches — the host-side
        analogue of the reference proxy batching many commits per resolver
        RPC, here amortizing per-dispatch latency instead of network round
        trips. Returns a collector yielding verdicts int8 [k, count].

        Callers should keep k fixed across calls (each distinct k compiles
        its own program). The pack/dispatch halves are separately callable
        (``pack_wire_window`` / ``dispatch_window``) so a scheduler can
        double-buffer host packing against device execution.
        """
        return self.dispatch_window(
            self.pack_wire_window(wire, commit_versions, count)
        )

    def pack_wire_window(
        self,
        wire: bytes | np.ndarray,
        commit_versions,
        count: int,
    ) -> PreparedWindow:
        """Host half of the window path: validate, advance version
        bookkeeping, and pack wire bytes into device-format tensors. Pure
        host work (the device rebase, if one fell due, is DEFERRED into the
        PreparedWindow), so it may run on a packing thread concurrently
        with ``dispatch_window`` of the PREVIOUS window — never concurrently
        with another pack (packs are commit-version ordered).

        One row a transaction: the scan takes `count` transactions a
        step at fixed rows, so a transaction with more ranges than a row
        has slots is refused here (ValueError), never widened; send such
        batches through resolve / resolve_wire."""
        buf = (
            np.frombuffer(wire, dtype=np.uint8)
            if isinstance(wire, (bytes, bytearray))
            else wire
        )
        k = len(commit_versions)
        if count > self.batch_size:
            raise ValueError("window path resolves one kernel batch per version")
        lib = _keypack_lib()
        counted = int(lib.kp_count_txns(_u8(buf), buf.size, 0))
        if counted < k * count:
            raise ValueError("malformed resolver wire batch")

        # A raise below must leave the host bookkeeping untouched: with a
        # deferred rebase, base_version would otherwise run ahead of the
        # never-rebased device state and silently skew every later
        # window's relative versions. Restoring the snapshot makes a
        # failed pack fully transactional (host-only — thread-safe on the
        # packing thread).
        snap = (self.base_version, self.oldest_version, self._last_commit)
        try:
            rebase_delta = 0
            oldest_abs = np.empty(k, np.int64)
            for i, cv in enumerate(commit_versions):
                rebase_delta += self._begin_resolve(
                    int(cv), None, defer_rebase=True
                )
                oldest_abs[i] = self.oldest_version
            # base_version is final after all _begin_resolve rebases —
            # convert now. A rebase mid-window can lift base above floors
            # snapshotted earlier; clamp those to 0 (everything below base
            # is already expired on device, so a zero floor is exact — the
            # kernel takes max(state.oldest, new_oldest), never regresses).
            cvs_rel = np.asarray(
                [self._rel(int(cv)) for cv in commit_versions], np.int32
            )
            olds_rel = np.asarray(
                [max(0, int(v) - self.base_version) for v in oldest_abs],
                np.int32,
            )

            batches = self._empty_batch(k)
            offset = 0
            for i in range(k):
                offset = _wire_offset(lib.kp_pack_batch(
                    _u8(buf), buf.size, offset, count,
                    self.batch_size, self.max_read_ranges,
                    self.max_write_ranges,
                    self.codec.n_words, self.base_version,
                    _i32(batches.read_begin[i]), _i32(batches.read_end[i]),
                    _u8(batches.read_mask[i]),
                    _i32(batches.write_begin[i]), _i32(batches.write_end[i]),
                    _u8(batches.write_mask[i]),
                    _i32(batches.read_version[i]), _u8(batches.txn_mask[i]),
                    None, None,
                ))
            # The deferred-repack packer variant: a resident-dictionary
            # overflow on the packing thread becomes a _RepackPlan
            # executed by dispatch_window (which may sync device
            # state), not an inline repack here.
            dev_batch = self._pack_resident(batches, defer_repack=True)
        except BaseException:
            self.base_version, self.oldest_version, self._last_commit = snap
            raise
        return PreparedWindow(
            batch=dev_batch,
            cvs_rel=cvs_rel,
            olds_rel=olds_rel,
            count=count,
            rebase_delta=rebase_delta,
        )

    def dispatch_window(self, prepared: PreparedWindow) -> Callable[[], np.ndarray]:
        """Device half of the window path: thread state through the scan
        program. Must run on the dispatching thread, in the same order the
        windows were packed.

        Speculative engines route through the reconcile ring: the dispatch
        happens immediately against the optimistically advanced state, and
        the returned collector reconciles (in FIFO order) before
        materializing verdicts — callers like the bench loop and
        PipelinedWindowRunner see the same collector contract either way."""
        if self.spec:
            seq = self.spec_dispatch_window(prepared)

            def collect_spec() -> np.ndarray:
                while seq not in self._spec_done:
                    self.reconcile_window()
                verdicts, levels = self._spec_done.pop(seq)
                if self.wave_commit:
                    self.last_wave_window = levels
                return verdicts

            return collect_spec
        if prepared.rebase_delta:
            self.state = self._rebase_fn(
                self.state, np.int32(min(prepared.rebase_delta, 2**31 - 1))
            )
        batch = prepared.batch
        if isinstance(batch, _RepackPlan):
            # Deferred resident repack: runs here because every earlier
            # window has dispatched, so the device liveness sync is exact
            # and the rank remap lands between window N-1 and N — the same
            # position it holds in the mirror's history.
            batch = self._repack_and_rank(batch)
        elif isinstance(batch, _DemotePlan):
            # Deferred tiered demotion: same exactness argument, but the
            # device traffic is an evict rank vector, not a dictionary.
            batch = self._demote_and_rank(batch)
        with stage_timer(self.last_stage_s, "engine_enqueue",
                         self._last_commit):
            out = self._resolve_many_fn(
                self.state, batch, prepared.cvs_rel, prepared.olds_rel
            )
        verdicts, levels, self.state = (
            out if self.wave_commit else (out[0], None, out[1])
        )
        if not self.wave_commit:
            return lambda: np.asarray(verdicts)[:, : prepared.count]

        def collect():
            # Waves are PER BATCH on the window path (batches already
            # serialize by commit version); publish int32 [k, count].
            self.last_wave_window = np.asarray(levels)[:, : prepared.count]
            return np.asarray(verdicts)[:, : prepared.count]

        return collect

    # -- speculative pipelined resolve (FDB_TPU_SPEC_RESOLVE=1) ---------------
    #
    # The resolve programs above paint accepted writes in the SAME device
    # program that decides them, so by the time window N's verdicts are
    # materialized on the host — let alone confirmed durable by the upper
    # layer (tlog push, ratekeeper) — the device state has already
    # advanced optimistically. Serial mode serializes anyway: it waits
    # for N's collector before dispatching N+1. Speculative mode
    # dispatches N+1 immediately and keeps a bounded FIFO ring of
    # unconfirmed windows; when N's confirmation lands (or the ring
    # fills), reconcile either confirms (the overwhelmingly common case —
    # drop N's snapshot, done) or rolls the state back to N's snapshot,
    # re-paints N with only the confirmed accepts, and repairs every
    # younger in-flight window against the corrected history: each is
    # re-resolved (only genuinely-conflicted txns flip; the windows' ranks
    # live in per-window coordinate systems, so nothing can prove a
    # younger window clean without resolving it). Serializability is
    # therefore preserved by construction; the A/B harness additionally replays
    # both arms through a fresh serial engine and compares verdict bytes.

    def spec_dispatch_window(self, prepared: PreparedWindow) -> int:
        """Dispatch a packed window speculatively; returns its reconcile
        sequence id. Must run on the dispatching thread, in pack order
        (same contract as dispatch_window)."""
        if not self.spec:
            raise ValueError("speculative resolve is off for this engine "
                             "(FDB_TPU_SPEC_RESOLVE=1 / spec_resolve=True)")
        while len(self._spec_ring) >= self.spec_depth:
            self.reconcile_window()
        if prepared.rebase_delta:
            # Pending snapshots are in pre-rebase version coordinates —
            # a rebase under them would corrupt every rollback target.
            # Rebases are ~once per 2^30 versions; draining first is free.
            self.reconcile_all()
            self.state = self._rebase_fn(
                self.state, np.int32(min(prepared.rebase_delta, 2**31 - 1))
            )
        batch = prepared.batch
        if isinstance(batch, _RepackPlan):
            # A resident-dictionary repack rebuilds the rank space from
            # exact device liveness — not a rollback-able operation, and
            # the liveness sync must not see unconfirmed writes. Drain.
            self.reconcile_all()
            batch = self._repack_and_rank(batch)
        elif isinstance(batch, _DemotePlan):
            # Demotion shares the repack's constraints: the liveness sync
            # must not see unconfirmed speculative paints, and evicting a
            # rank is not rollback-able (snapshots hold pre-evict ranks).
            self.reconcile_all()
            batch = self._demote_and_rank(batch)
        snap = ck._snapshot_jit(self.state)
        out = self._resolve_many_fn(
            self.state, batch, prepared.cvs_rel, prepared.olds_rel
        )
        verdicts, levels, self.state = (
            out if self.wave_commit else (out[0], None, out[1])
        )
        seq = self._spec_seq
        self._spec_seq += 1
        self._spec_ring.append(_SpecPending(
            seq=seq, snapshot=snap, batch=batch,
            cvs_rel=prepared.cvs_rel, olds_rel=prepared.olds_rel,
            count=prepared.count, verdicts=verdicts, levels=levels,
        ))
        self._spec_stats["spec_dispatched"] += 1
        return seq

    def _spec_accept_mask(self, batch, verdicts, levels) -> np.ndarray:
        """bool [k, B]: which txns this dispatch ACCEPTED (i.e. painted).
        Wave engines: committed at some wave (levels >= 0 — padding is
        excluded by construction). Plain engines: verdict COMMITTED ∧
        txn_mask (padded slots get verdict 0 from assemble_verdicts and
        MUST be masked out)."""
        if levels is not None:
            return np.asarray(levels) >= 0
        return (np.asarray(verdicts) == 0) & np.asarray(batch.ranks.txn_mask)

    def reconcile_window(self, confirmed: np.ndarray | None = None) -> np.ndarray:
        """Reconcile the OLDEST in-flight window against its upper-layer
        confirmation; returns its verdicts int8 [k, count] (also stashed
        for the window's dispatch collector).

        ``confirmed`` is a bool [k, count] mask (False = the upper layer
        revoked this txn's speculative outcome); None consults
        ``spec_confirm_hook``, and a None hook confirms everything. The
        window's own verdicts are returned UNCHANGED — an upper-layer
        revocation is an upper-layer abort, not a resolver verdict; what
        reconcile repairs is the HISTORY (revoked writes un-painted) and
        every younger window that speculated on it."""
        p = self._spec_ring.popleft()
        verdicts_np = np.asarray(p.verdicts)[:, : p.count]
        levels_np = (None if p.levels is None
                     else np.asarray(p.levels)[:, : p.count])
        spec_acc = self._spec_accept_mask(p.batch, p.verdicts, p.levels)
        k, b = spec_acc.shape
        if confirmed is None and self.spec_confirm_hook is not None:
            confirmed = self.spec_confirm_hook(p.seq, verdicts_np)
        if confirmed is None:
            rejected = np.zeros((k, b), bool)
        else:
            conf = np.zeros((k, b), bool)
            conf[:, : p.count] = np.asarray(confirmed, bool)[:, : p.count]
            rejected = spec_acc & ~conf
        if not rejected.any():
            self._spec_stats["spec_confirmed"] += 1
            self._spec_done[p.seq] = (verdicts_np, levels_np)
            return verdicts_np  # snapshot drops here — state already right

        # -- mis-speculation: rollback + repair --------------------------
        self._spec_stats["spec_repaired"] += 1
        self._spec_stats["chain_rolls"] += 1
        # 1) Roll the live state back to before this window (pointer swap
        #    to the snapshot; it becomes the live state and is donated by
        #    the paint below, so no extra buffer lingers).
        self.state = p.snapshot
        # 2) Re-advance with ONLY the confirmed accepts: a paint-only pass
        #    with a host-forced mask — the same merge/GC/paint pipeline,
        #    minus the verdict decision the upper layer overrode.
        self.state = self._paint_many_fn(
            self.state, p.batch, spec_acc & ~rejected,
            p.cvs_rel, p.olds_rel,
        )
        # 3) Repair every younger in-flight window against the corrected
        #    history, in dispatch order: re-resolve it (only
        #    genuinely-conflicted txns flip).
        younger = list(self._spec_ring)
        self._spec_ring.clear()
        for y in younger:
            snap = ck._snapshot_jit(self.state)
            out = self._resolve_many_fn(
                self.state, y.batch, y.cvs_rel, y.olds_rel
            )
            nv, nl, self.state = (
                out if self.wave_commit else (out[0], None, out[1])
            )
            old_acc = self._spec_accept_mask(y.batch, y.verdicts, y.levels)
            new_acc = self._spec_accept_mask(y.batch, nv, nl)
            self._spec_stats["spec_flipped"] += int(
                (old_acc != new_acc)[:, : y.count].sum()
            )
            self._spec_ring.append(
                y._replace(snapshot=snap, verdicts=nv, levels=nl))
        self._spec_done[p.seq] = (verdicts_np, levels_np)
        return verdicts_np

    def reconcile_all(self) -> None:
        """Drain the in-flight ring (confirmations via spec_confirm_hook).
        Serial entry points and non-rollback-able device ops (rebase,
        resident repack) call this before touching state."""
        while self._spec_ring:
            self.reconcile_window()

    def _spec_drain_serial(self) -> None:
        """Guard for serial-path entry points on a speculative engine:
        in-flight windows must confirm/repair before state is read or
        advanced outside the ring."""
        if self._spec_ring:
            self.reconcile_all()

    def spec_metrics(self) -> dict:
        """Counters for the obs plane (resolver.get_metrics mirrors these;
        ratekeeper clamps speculation depth on the repair rate)."""
        out = dict(self._spec_stats)
        out["spec_depth"] = len(self._spec_ring)
        return out

    def spec_resolve_async(self, txns, commit_version: int,
                           oldest_version: int | None = None):
        """Object-path speculative dispatch (the resolver role's seam):
        one chunk lifted to a k=1 window through the same ring. Returns a
        collector yielding list[Verdict], or None when this batch can't
        speculate (oversized → chunking serializes anyway; a reporting txn
        needs the report program; a transaction wider than a row needs
        continuation rows, which the scan program and the ring's accept
        masks do not carry) — the caller falls back to the serial
        path after reconcile_all().

        Admission-filter feeding is skipped under speculation (the filter
        is advisory recency state; feeding optimistic accepts could
        poison it on revocation)."""
        if (not self.spec or len(txns) > self.batch_size
                or any(t.report_conflicting_keys for t in txns)
                or self._txn_row_counts(txns) is not None):
            return None
        while len(self._spec_ring) >= self.spec_depth:
            self.reconcile_window()
        delta = self._begin_resolve(commit_version, oldest_version,
                                    defer_rebase=True)
        if delta:
            self.reconcile_all()
            self.state = self._rebase_fn(
                self.state, np.int32(min(delta, 2**31 - 1))
            )
        cv_rel = np.asarray([self._rel(commit_version)], np.int32)
        old_rel = np.asarray([self._rel(self.oldest_version)], np.int32)
        batch = self._pack(txns)
        self._adm_stash = None
        dev = self._pack_resident(batch, defer_repack=True)
        if isinstance(dev, _RepackPlan):
            self.reconcile_all()
            dev = self._repack_and_rank(dev)
        elif isinstance(dev, _DemotePlan):
            self.reconcile_all()
            dev = self._demote_and_rank(dev)
        # k=1 lift: the scan axis goes on the ranks; the key delta is
        # per-window (merged once) exactly as the window packer emits.
        dev = dev._replace(ranks=type(dev.ranks)(*(
            None if f is None else np.asarray(f)[None]
            for f in dev.ranks)))
        snap = ck._snapshot_jit(self.state)
        out = self._resolve_many_fn(self.state, dev, cv_rel, old_rel)
        verdicts, levels, self.state = (
            out if self.wave_commit else (out[0], None, out[1])
        )
        seq = self._spec_seq
        self._spec_seq += 1
        self._spec_ring.append(_SpecPending(
            seq=seq, snapshot=snap, batch=dev, cvs_rel=cv_rel,
            olds_rel=old_rel, count=len(txns), verdicts=verdicts,
            levels=levels,
        ))
        self._spec_stats["spec_dispatched"] += 1

        def collect() -> list[Verdict]:
            while seq not in self._spec_done:
                self.reconcile_window()
            v, lv = self._spec_done.pop(seq)
            if self.wave_commit and lv is not None:
                row = lv[0]
                self.last_wave = [int(x) for x in row]
                self.last_reordered = int((row > 0).sum())
            return [Verdict(int(x)) for x in v[0]]

        return collect

    # -- role-level global wave protocol (core/wavemesh) ----------------------

    @property
    def wave_global_capable(self) -> bool:
        """Does this engine implement the two-phase global wave protocol
        (resolve_edges/resolve_apply)? True for single-chip wave-commit
        engines; the mesh-sharded subclass exchanges edges on-device
        inside one program and is a self-contained single resolver from
        the role's perspective (it reports False — a deployment sharding
        ABOVE a mesh engine would need edges of edges)."""
        return self.wave_commit and self._wave_edges_fn is not None

    def resolve_edges(
        self,
        txns: list[TxnConflictInfo],
        commit_version: int,
        oldest_version: int | None = None,
    ):
        """Phase 1 of the global wave protocol: gate this shard's CLIPPED
        view of the window (TOO_OLD + history conflicts) and build its
        clipped predecessor bitsets, WITHOUT painting. The packed device
        batches stay stashed until resolve_apply consumes the combined
        graph — one pack serves both phases. Returns a wavemesh.WaveEdges
        payload (per-chunk packed uint32 matrices) for the commit proxy's
        OR-reduce, indexed by TRANSACTION whatever rows this shard's clip
        of a wide transaction takes here."""
        from foundationdb_tpu.core.wavemesh import WaveEdges

        if not self.wave_global_capable:
            raise ValueError(
                "resolve_edges requires a wave-commit engine with the "
                "two-phase entry points (wave_commit=True)"
            )
        if self._wave_pending is not None:
            raise ValueError(
                "resolve_edges with an apply outstanding: the previous "
                "window's resolve_apply must land first (version chain)"
            )
        rows = self.txn_rows(txns)[0]
        if rows > self.batch_size:
            # The protocol exchanges ONE schedule domain per window. The
            # single-engine path chunks oversized windows and serializes
            # them THROUGH the history (chunk k+1's gate sees chunk k's
            # paints — cross-chunk read-write pairs abort); a one-shot
            # edge exchange gates every chunk against the pre-window
            # history and would silently commit those pairs. The commit
            # proxy keeps wave batches within one engine chunk of
            # transactions; a client's wide ones (continuation rows) can
            # still pass it in rows, and the resolver role answers such a
            # window through the fail-safe before it comes here.
            raise ValueError(
                f"global wave window of {len(txns)} txns takes {rows} "
                f"rows; the engine chunk holds {self.batch_size}: one "
                "exchange carries one schedule domain"
            )
        self._spec_drain_serial()
        self._begin_resolve(commit_version, oldest_version)
        cv = np.int32(self._rel(commit_version))
        oldest = np.int32(self._rel(self.oldest_version))
        # The guard above pins the one-window-one-chunk invariant, so the
        # payload is exactly one chunk (or none for an empty window).
        n = len(txns)
        if not n:
            self._wave_pending = ([], commit_version)
            return WaveEdges(
                count=0, too_old=np.zeros(0, bool),
                hist_conflict=np.zeros(0, bool), chunks=[],
            )
        batch = self._pack(txns)
        dev = self._pack_resident(batch)
        too_old, hist_c, p, self.state = self._wave_edges_fn(
            self.state, dev, oldest
        )
        # The exchange goes by TRANSACTION: every shard is sent every
        # transaction of the window, clipped, and lays a wide one out in
        # rows of its own. A transaction's gate is the OR over its rows
        # and its edges are its head row's (conflict_kernel._edge_pred).
        rows, heads = _rows_and_heads(batch, n)
        too_old, hist_c, p = (np.asarray(x) for x in (too_old, hist_c, p))
        if heads is not None:
            too_old = np.logical_or.reduceat(too_old[:rows], heads)
            hist_c = np.logical_or.reduceat(hist_c[:rows], heads)
            p = _heads_to_txns(p, heads)
        self._wave_pending = (
            [(dev, n, rows, heads, cv, oldest,
              self._take_adm(commit_version))],
            commit_version,
        )
        return WaveEdges(
            count=n,
            too_old=too_old[:n],
            hist_conflict=hist_c[:n],
            chunks=[(n, p)],
        )

    def resolve_abandon(self) -> None:
        """Drop a pending resolve_edges without painting (another shard's
        capacity fail-safe rejected the whole window). Nothing reached
        device history in phase 1, so dropping the stash IS the
        paint-nothing fail-safe contract; version bookkeeping stays
        advanced (harmless — the device floor catches up on the next
        dispatch's max())."""
        self._wave_pending = None

    def resolve_apply(self, graph) -> list[Verdict]:
        """Phase 2: level the combined GLOBAL graph on-device (identical
        inputs on every shard → identical schedule on every shard), paint
        this shard's accepted writes, and publish last_wave /
        last_reordered exactly like a single-shard wave resolve. The
        conflicting-keys report degrades to the resolver-side
        conservative superset on this path (last_conflicting stays
        empty)."""
        if self._wave_pending is None:
            raise ValueError("resolve_apply without a pending resolve_edges")
        pend, commit_version = self._wave_pending
        self._wave_pending = None
        if len(graph.chunks) != len(pend):
            raise ValueError(
                f"global graph has {len(graph.chunks)} chunks; this shard "
                f"packed {len(pend)}"
            )
        gi = 0
        level_parts: list[np.ndarray] = []
        feed: list[tuple] = []
        for (dev, n, rows, heads, cv, oldest, adm), (nc, pred) in zip(
                pend, graph.chunks):
            if nc != n:
                raise ValueError(
                    f"global graph chunk of {nc} txns vs local pack of {n}"
                )
            cand = np.zeros(self.batch_size, bool)
            cand[:n] = graph.cand[gi : gi + n]
            levels, self.state = self._wave_apply_fn(
                self.state, dev.ranks, cand,
                np.ascontiguousarray(pred, np.uint32),
                cv, oldest,
            )
            lv = np.asarray(levels)[:n]  # by transaction, like the graph
            level_parts.append(lv)
            if adm is not None:
                # The write fingerprints were stashed by row.
                feed.append((lv if heads is None else np.repeat(
                    lv, np.diff(np.append(heads, rows))), adm))
            gi += n
        # Stitch the coherent window schedule (same chunk-offset rule as
        # _collect_waves) + the attribution counters.
        waves: list[int] = []
        offset = 0
        reordered = 0
        for lv in level_parts:
            reordered += int((lv > 0).sum())
            waves.extend(int(x) + offset if x >= 0 else int(x) for x in lv)
            if len(lv) and int(lv.max()) >= 0:
                offset += int(lv.max()) + 1
        self.last_wave = waves
        self.last_reordered = reordered
        self.last_conflicting = {}
        # Admission feed (engine-attached filters): accepted writes at
        # this window's commit version, judged on the GLOBAL schedule.
        if self.admission_filter is not None:
            for lv, ((fps, valid), adm_cv) in feed:
                sel = valid[: len(lv)] & (lv >= 0)[:, None]
                if sel.any():
                    self.admission_filter.record_u64(
                        fps[: len(lv)][sel], adm_cv
                    )
                else:
                    self.admission_filter.advance(adm_cv)
        from foundationdb_tpu.core.wavemesh import verdicts_from_schedule

        return verdicts_from_schedule(graph, waves)

    def _collect_waves(self, pending: list[tuple]) -> None:
        """Publish ``last_wave`` from the pending chunks' level tensors.

        Chunks of one resolve call serialize in submission order (earlier
        chunks' writes are painted before later chunks resolve), so chunk
        i+1's wave 0 serializes after ALL of chunk i's waves: offset each
        chunk's committed levels past the previous chunk's maximum to make
        the list one coherent schedule for the whole call."""
        if not self.wave_commit:
            return
        waves: list[int] = []
        offset = 0
        reordered = 0
        for _v, n, heads, _losers, _reads, _flags, levels, _adm in pending:
            lv = _per_txn(levels, n, heads)
            # Reordered = committed past its CHUNK's first wave (raw
            # level > 0). The chunk offsets below exist only to make the
            # published schedule coherent across chunks — a later chunk's
            # wave-0 txn committed in plain arrival order and must not
            # count as reordered.
            reordered += int((lv > 0).sum())
            waves.extend(int(x) + offset if x >= 0 else int(x) for x in lv)
            if len(lv) and int(lv.max()) >= 0:
                offset += int(lv.max()) + 1
        self.last_wave = waves
        self.last_reordered = reordered

    def _feed_admission(self, pending: list[tuple]) -> None:
        """Record ACCEPTED write fingerprints into the attached admission
        filter at this resolve's commit version (no-op when detached).
        Runs at collect time — verdicts are already materialized, so the
        mask costs one vectorized compare per chunk."""
        if self.admission_filter is None:
            return
        for verdicts, n, _h, _l, _r, _f, _lv, adm in pending:
            if adm is None:
                continue
            (fps, valid), cv = adm
            # Row by row: a transaction's rows share its verdict.
            v = np.asarray(verdicts)[:n]
            sel = valid[:n] & (v == Verdict.COMMITTED)[:, None]
            if sel.any():
                self.admission_filter.record_u64(fps[:n][sel], cv)
            else:
                self.admission_filter.advance(cv)

    def _collect(self, pending: list[tuple], rec: "dict | None",
                 version: int) -> list[Verdict]:
        """``rec`` / ``version``: the stage record and the commit version
        of the batch being collected, bound at its dispatch: by now
        ``last_stage_s`` / ``_last_commit`` may be a later batch's."""
        with stage_timer(rec, "verdict_wait", version):
            # The blocking read: chunks execute in order, so the last
            # chunk's verdicts are ready when every chunk's are.
            if pending:
                np.asarray(pending[-1][0])
        with stage_timer(rec, "resolve_post", version):
            return self._decode(pending)

    def _decode(self, pending: list[tuple]) -> list[Verdict]:
        out: list[Verdict] = []
        self.last_conflicting = {}
        self._collect_waves(pending)
        self._feed_admission(pending)
        gi = 0
        r = self.max_read_ranges
        for verdicts, n, heads, losers, reads, flags, _lv, _adm in pending:
            v = _per_txn(verdicts, n, heads)
            if losers is not None:
                m = np.asarray(losers)[:n]
                if m.dtype != np.bool_:
                    # uint32 bitset rows (ck.pack_loser_mask): bit c = read
                    # slot c lost — unpack to the bool [n, R] layout.
                    m = (
                        (m[:, None] >> np.arange(r, dtype=np.uint32)) & 1
                    ).astype(bool)
                # A transaction's read c sits in slot c counted from its
                # head row, continuation rows included.
                m = m.reshape(-1)
                first = (np.arange(len(v)) if heads is None else heads) * r
                for j in np.flatnonzero(v == Verdict.CONFLICT):
                    if flags[j]:
                        mine = reads[j]
                        lost = m[first[j]: first[j] + len(mine)]
                        cols = [mine[c] for c in np.flatnonzero(lost)]
                        # Exactly the read ranges that lost. Empty mask
                        # (shouldn't happen for a real conflict) degrades
                        # to the full read set.
                        self.last_conflicting[gi + j] = cols or list(mine)
            out.extend(Verdict(int(x)) for x in v)
            gi += len(v)
        return out

    def _begin_resolve(
        self,
        commit_version: int,
        oldest_version: int | None,
        defer_rebase: bool = False,
    ) -> int:
        """Advance host-side version bookkeeping for one dispatch. Returns
        the version delta of a rebase that fell due: 0 normally, applied to
        device state immediately — unless ``defer_rebase``, in which case
        the caller must apply it before the next device op (the packing
        thread may not touch device state)."""
        if commit_version <= self._last_commit:
            raise ValueError(
                f"commit versions must advance: {commit_version} <= {self._last_commit}"
            )
        if self.base_version is None:
            self.base_version = max(0, commit_version - self.window_versions)
        if oldest_version is not None:
            self.oldest_version = max(self.oldest_version, oldest_version)
        self.oldest_version = max(
            self.oldest_version, commit_version - self.window_versions
        )
        delta = self._maybe_rebase(commit_version, defer=defer_rebase)
        self._last_commit = commit_version
        return delta

    @property
    def _hist_core(self) -> ck.HistState:
        """The window history proper, out of the ResState."""
        return self.state.hist

    def _reading(self):
        """The capacity reading of the state as it stands, enqueued:
        int32 [3] on the device (boundary slots in use, overflowed, the
        window history's merges since boot): ck._capacity_reading_jit."""
        st = self._hist_core
        return ck._capacity_reading_jit(
            (st.base.n_used, st.delta.n_used),
            (st.base.overflow, st.delta.overflow), st.merges,
            (st.base.versions, st.delta.oldest))

    def _enqueue_reading(self, commit_version: int = 0):
        """Enqueue the capacity reading of the state as the last dispatch
        leaves it, and start its copy to the host. One more enqueue of
        the dispatch: stage ``engine_enqueue``."""
        with stage_timer(self.last_stage_s, "engine_enqueue",
                         commit_version):
            out = self._reading()
            out.copy_to_host_async()
        return out

    @property
    def overflowed(self) -> bool:
        st = self._hist_core
        return bool(
            np.asarray(st.base.overflow).any()
            or np.asarray(st.delta.overflow).any()
        )

    def headroom(self) -> int:
        """Free boundary slots in the tightest shard (device sync).

        The host-side back-pressure signal: a painted write range adds at
        most 2 boundaries, so a batch of n txns can grow the history by at
        most ``2 * n * max_write_ranges`` slots — if headroom is below that,
        resolving the batch could overflow (truncate history → missed
        conflicts). The runtime Resolver checks this before every batch and
        fail-safes instead (see runtime/resolver.py). The reference's
        SkipList never loses history inside the MVCC window; this check is
        how the fixed-capacity engine earns the same guarantee.

        A merge keeps at most base+delta live
        boundaries (the base counted as that merge's GC would leave it:
        rows that expired since the last merge use no capacity,
        ck._capacity_reading_jit), and the just-in-time merge empties the
        delta before a dispatch that wouldn't fit — so admission needs
        room in the merged base AND a delta that can absorb one whole
        DISPATCH. The delta is
        built to (``delta_capacity`` defaults to a full dispatch's worst
        case), and a batch of more rows than ``batch_size`` — 512 wide
        transactions, say — goes through it a dispatch at a time; only a
        delta configured smaller than that caps what a batch may bring.
        """
        used, _over, merges = (int(x) for x in np.asarray(self._reading()))
        # A batch collected at once is read here, not through its
        # collector's reading(): the count of merges rides along as there.
        self.hist_merges = merges
        return self._headroom_of(used)

    def _headroom_of(self, used: int) -> int:
        """headroom() given the boundary slots in use."""
        free = self.capacity - used
        if self.delta_capacity < min(
                self.capacity, self.worst_case_growth(self.batch_size)):
            return min(free, self.delta_capacity)
        return free

    def worst_case_growth(self, n_rows: int) -> int:
        """Upper bound on boundary-slot growth from resolving n_rows
        padded rows (txn_rows: one a transaction unless it is wide)."""
        return 2 * n_rows * self.max_write_ranges

    def clear_overflow(self) -> None:
        """Reset the sticky device overflow flag (after the host has
        reacted — see Resolver's unsafe-window handling)."""
        hc = self._hist_core
        self.state = self.state._replace(hist=hc._replace(
            base=hc.base._replace(overflow=hc.base.overflow & False),
            delta=hc.delta._replace(overflow=hc.delta.overflow & False),
        ))

    def advance(self, commit_version: int, oldest_version: int | None = None) -> None:
        """GC-only dispatch: move the version chain and MVCC floor forward
        without painting any writes. Expired segments compact out, so
        headroom recovers as the window slides — this is what lets the
        Resolver's fail-safe mode drain and exit. It forces a merge (the
        lazy base would otherwise hold expired segments until the next
        organic merge)."""
        self._spec_drain_serial()
        self._begin_resolve(commit_version, oldest_version)
        if self.admission_filter is not None:
            self.admission_filter.advance(commit_version)  # age the banks
        cv = np.int32(self._rel(commit_version))
        oldest = np.int32(self._rel(self.oldest_version))
        _, self.state = self._advance_hist_fn(self.state, cv, oldest)

    def warm_up(self) -> dict[str, float]:
        """Compile the entry points a serving resolver dispatches, before
        the first request: resolve, the conflicting-keys report, the
        GC-only advance, the version rebase, the capacity reading and the
        full dictionary repack, each run once at this engine's shapes on
        an all-masked batch at relative version 0 — which paints nothing,
        moves no floor and remaps every rank to itself, so the state that
        comes out equals the state that went in. Host version bookkeeping
        is not touched. Returns seconds per entry point (compile plus one
        execution; set-up information, not a measurement).

        Not covered, and compiled on first use: the scan-window program
        (each window depth is its own program; its callers warm the depths
        they use), the two-phase wave-exchange entry points and the
        tiered evict."""
        import jax

        count_compiles()
        zero = np.int32(0)
        bt = self._empty_batch()
        # Assembled directly, not through _pack_resident: a warm-up is
        # no dispatch, and the mirror's counters should not say so.
        flat, dims = self._flat_endpoints(bt)
        empty = self._ranks_to_batch(
            bt, np.full(len(flat), INT32_MAX, np.int32), dims)
        steps: dict[str, Callable] = {
            "resolve": lambda: self._resolve_fn(
                self.state, empty, zero, zero)[-1],
        }
        if getattr(self, "_resolve_report_fn", None) is not None:
            steps["resolve_report"] = lambda: self._resolve_report_fn(
                self.state, empty, zero, zero)[-1]
        steps["advance"] = lambda: self._advance_hist_fn(
            self.state, zero, zero)[-1]
        steps["rebase"] = lambda: self._rebase_fn(self.state, zero)
        # Reads the state, returns none: the state goes through untouched.
        steps["reading"] = lambda: (
            jax.block_until_ready(self._enqueue_reading()), self.state)[1]
        mir = self._mirror
        dict_dev = np.full((mir.capacity + 1, mir.rows.shape[1]),
                           INT32_MAX, np.int32)
        dict_dev[: mir.n] = mir.rows
        identity = np.arange(mir.capacity + 1, dtype=np.int32)
        steps["repack"] = lambda: self._repack_fn(
            self.state, dict_dev, np.int32(mir.n), identity)
        merges = np.asarray(self._hist_core.merges)
        seconds: dict[str, float] = {}
        for name, step in steps.items():
            t0 = _perf_counter()
            self.state = jax.block_until_ready(step())
            seconds[name] = round(_perf_counter() - t0, 3)
        # The advance step merged, and a warm-up is none of the count's.
        self.state = self.state._replace(hist=self._hist_core._replace(
            merges=self._device_merges(merges)))
        return seconds

    def _device_merges(self, merges: np.ndarray):
        """A merge count back on the device, placed as the engine's
        set-up placed it (here init_hist's: on no named device): an array
        placed otherwise is another argument type to every compiled entry
        point, and the first batch would compile them again."""
        import jax

        return jax.numpy.asarray(merges)

    def device_info(self) -> dict:
        """Where this engine's state lives, read off the arrays
        themselves: platform, device kind and how many devices hold it."""
        import jax

        from foundationdb_tpu.utils import describe_devices

        return describe_devices(sorted(
            {d for leaf in jax.tree.leaves(self.state)
             for d in leaf.devices()},
            key=lambda d: d.id))

    # -- internals ----------------------------------------------------------

    def _rel(self, v: int) -> int:
        assert self.base_version is not None
        rel = v - self.base_version
        if rel < 0:
            raise ValueError(f"version {v} below base {self.base_version}")
        return rel

    def _rel_read(self, v: int) -> int:
        """Read versions may legitimately predate the base (ancient readers):
        clamp to -1, which is strictly below every window floor → TOO_OLD for
        readers, irrelevant for blind writers."""
        assert self.base_version is not None
        return max(-1, v - self.base_version)

    def _maybe_rebase(self, commit_version: int, defer: bool = False) -> int:
        assert self.base_version is not None
        if commit_version - self.base_version < _REBASE_THRESHOLD:
            return 0
        delta = self.oldest_version - self.base_version
        if delta <= 0:
            return 0
        # Device versions < delta are all expired; the kernel clamps them to
        # the sentinel, so saturating the device delta at int32 max is exact
        # even for astronomically large jumps.
        if not defer:
            self.state = self._rebase_fn(self.state, np.int32(min(delta, 2**31 - 1)))
        self.base_version += delta
        return delta

    def _empty_batch(self, k: int | None = None) -> ck.BatchTensors:
        """Padded all-masked-out batch tensors (shared by both packers so
        the wire and object paths can never diverge on layout). k adds a
        leading window axis for the scan path."""
        lead = () if k is None else (k,)
        b = self.batch_size
        r, q = self.max_read_ranges, self.max_write_ranges
        w = self.codec.width
        return ck.BatchTensors(
            read_begin=np.full((*lead, b, r, w), INT32_MAX, np.int32),
            read_end=np.full((*lead, b, r, w), INT32_MAX, np.int32),
            read_mask=np.zeros((*lead, b, r), bool),
            write_begin=np.full((*lead, b, q, w), INT32_MAX, np.int32),
            write_end=np.full((*lead, b, q, w), INT32_MAX, np.int32),
            write_mask=np.zeros((*lead, b, q), bool),
            read_version=np.zeros((*lead, b), np.int32),
            txn_mask=np.zeros((*lead, b), bool),
        )

    def _txn_row_counts(self, txns) -> "list[int] | None":
        """Rows each transaction takes in the padded batch, or None when
        every one takes a single row (the common case, found by lengths
        alone). A transaction with more non-empty reads than
        ``max_read_ranges`` or writes than ``max_write_ranges`` runs on
        into continuation rows: ceil(n / slots) of them, never fewer
        ranges."""
        r, q = self.max_read_ranges, self.max_write_ranges
        if all(len(t.read_ranges) <= r and len(t.write_ranges) <= q
               for t in txns):
            return None
        rows = []
        for t in txns:
            nr, nq = len(t.read_ranges), len(t.write_ranges)
            if nr > r:
                nr = sum(1 for x in t.read_ranges if not x.empty)
            if nq > q:
                nq = sum(1 for x in t.write_ranges if not x.empty)
            rows.append(max(1, -(-nr // r), -(-nq // q)))
        return rows if max(rows) > 1 else None

    def txn_rows(self, txns) -> tuple[int, int]:
        """(padded rows `txns` take, how many of them take more than one):
        what the history's worst-case growth and the resolver's counters
        go by. (len(txns), 0) for a batch with no wide transaction."""
        rows = self._txn_row_counts(txns)
        if rows is None:
            return len(txns), 0
        return sum(rows), sum(1 for k in rows if k > 1)

    def _chunks(self, txns) -> list[tuple[int, int]]:
        """[lo, hi) slices of `txns`, one a dispatch, in order: as many
        whole transactions as fit `batch_size` rows. A transaction is
        never split across two dispatches. Stage ``host_pack``; over a
        batch that holds a wide transaction also ``wide_layout``, which
        is that much of it."""
        b = self.batch_size
        with stage_timer(self.last_stage_s, "host_pack", self._last_commit):
            rows = self._txn_row_counts(txns)
            if rows is None:
                return [(i, min(i + b, len(txns)))
                        for i in range(0, len(txns), b)]
            with stage_timer(self.last_stage_s, "wide_layout",
                             self._last_commit):
                out, lo, used = [], 0, 0
                for i, k in enumerate(rows):
                    if k > b:
                        raise ValueError(
                            f"transaction {i} needs {k} rows of "
                            f"{self.max_read_ranges} read / "
                            f"{self.max_write_ranges} write ranges; one "
                            f"dispatch holds {b}")
                    if used + k > b:
                        out.append((lo, i))
                        lo, used = i, 0
                    used += k
                out.append((lo, len(txns)))
                return out

    @_staged("host_pack")
    def _pack_wire(
        self, buf: np.ndarray, offset: int, count: int
    ) -> tuple[ck.BatchTensors, int, int]:
        """One C pass: wire bytes [offset..] → padded batch tensors, the
        same layout as _pack bit for bit. Takes up to `count`
        transactions, fewer where wide ones fill the rows first. Returns
        (batch, offset past the last one taken, how many were taken).
        Stage ``host_pack``, like _pack."""
        bt = self._empty_batch()
        cont = np.zeros(self.batch_size, bool)
        used = np.zeros(2, np.int32)
        new_off = _wire_offset(_keypack_lib().kp_pack_batch(
            _u8(buf), buf.size, offset, count,
            self.batch_size, self.max_read_ranges, self.max_write_ranges,
            self.codec.n_words, self.base_version,
            _i32(bt.read_begin), _i32(bt.read_end), _u8(bt.read_mask),
            _i32(bt.write_begin), _i32(bt.write_end), _u8(bt.write_mask),
            _i32(bt.read_version), _u8(bt.txn_mask), _u8(cont), _i32(used),
        ))
        if used[1] > used[0]:
            bt = bt._replace(cont=cont)
        return bt, new_off, int(used[0])

    @_staged("host_pack")
    def _pack(self, txns: list[TxnConflictInfo], collect_reads: bool = False):
        """Keys -> row tensors, for transactions that fit ``batch_size``
        rows together (_chunks). A transaction's non-empty ranges fill
        slots in the order given; one with more than a row holds runs on
        into continuation rows right after its first — same read version,
        ``txn_mask`` set, marked in ``cont`` — so its range c sits in slot
        c counted from its first row. Every range is judged as it was
        sent: none is widened, merged or dropped. ``cont`` stays None, and
        the tensors are what they always were, for a batch with no wide
        transaction. native/keypack.cpp kp_pack_batch is the same layout
        on the wire path.

        Stage ``host_pack`` (obs/span.py): its wall seconds ACCUMULATE in
        ``last_stage_s`` across the chunks of a capacity-chunked resolve;
        the reader — the resolver's span sink — hands in a fresh record
        per dispatched batch, so the sum is per batch."""
        bt = self._empty_batch()
        r, q = self.max_read_ranges, self.max_write_ranges
        w = self.codec.width

        # One vectorized pack per endpoint kind across the whole batch (the
        # per-txn Python work is just index bookkeeping).
        r_slots, r_pairs = [], []
        w_slots, w_pairs = [], []
        reads_per_txn: list[list[KeyRange]] = []
        versions: list[int] = []  # one a ROW
        cont_rows: list[int] = []
        row = 0
        for t in txns:
            reads = [x for x in t.read_ranges if not x.empty]
            writes = [x for x in t.write_ranges if not x.empty]
            if collect_reads:
                # Kept in slot order: the report path maps the kernel's
                # loser-mask columns back to these ranges.
                reads_per_txn.append(reads)
            for c, x in enumerate(reads, row * r):
                r_slots.append(c)
                r_pairs.append((x.begin, x.end))
            for c, x in enumerate(writes, row * q):
                w_slots.append(c)
                w_pairs.append((x.begin, x.end))
            rv = self._rel_read(t.read_version)
            if len(reads) <= r and len(writes) <= q:
                versions.append(rv)
                row += 1
            else:
                k = max(-(-len(reads) // r), -(-len(writes) // q))
                versions.extend([rv] * k)
                cont_rows.extend(range(row + 1, row + k))
                row += k
        if row > self.batch_size:
            raise ValueError(
                f"{len(txns)} transactions need {row} rows; one dispatch "
                f"holds {self.batch_size}")
        bt.txn_mask[:row] = True
        bt.read_version[:row] = versions
        if r_pairs:
            rb, re_ = self.codec.pack_ranges(r_pairs)
            bt.read_begin.reshape(-1, w)[r_slots] = rb
            bt.read_end.reshape(-1, w)[r_slots] = re_
            bt.read_mask.reshape(-1)[r_slots] = True
        if w_pairs:
            wb, we = self.codec.pack_ranges(w_pairs)
            bt.write_begin.reshape(-1, w)[w_slots] = wb
            bt.write_end.reshape(-1, w)[w_slots] = we
            bt.write_mask.reshape(-1)[w_slots] = True
        if cont_rows:
            cont = np.zeros(self.batch_size, bool)
            cont[cont_rows] = True
            bt = bt._replace(cont=cont)
        if collect_reads:
            return bt, reads_per_txn
        return bt


def _for_kernel(bt: ck.BatchTensors, wave: bool) -> ck.BatchTensors:
    """`bt` as the kernel takes it: without ``cont`` where the row ->
    transaction reduce has nothing to decide.

    A transaction's rows stand or fall together, and only a read can make
    one fall. Where no wide transaction of the batch has a read (a bulk
    load's 100 sets a transaction), every row is accepted on its own
    account, never TOO_OLD, and painted exactly as the whole would be: the
    batch runs the program a batch of narrow transactions runs, and the
    ``cont`` variant is neither compiled nor loaded for it. The host still
    maps verdicts by head row (_rows_and_heads reads the layout's own
    ``cont``). Reads fill slots from the head row on, so a wide
    transaction reads something exactly when its head row's first slot is
    taken. The wave schedule gives every row a level of its own, so it
    keeps the reduce."""
    cont = bt.cont
    if cont is None or wave:
        return bt
    wide_heads = np.flatnonzero(cont[1:] & ~cont[:-1])
    if bt.read_mask[wide_heads, 0].any():
        return bt
    return bt._replace(cont=None)


def _rows_and_heads(bt: ck.BatchTensors, n_txns: int):
    """(rows the batch fills, the head row of each transaction) — the
    second None where rows and transactions are the same index."""
    if bt.cont is None:
        return n_txns, None
    heads = np.flatnonzero(bt.txn_mask & ~bt.cont)
    return int(np.count_nonzero(bt.txn_mask)), heads


def _heads_to_txns(p: np.ndarray, heads: np.ndarray) -> np.ndarray:
    """A packed predecessor matrix indexed by head ROW (uint32 [BP, BP/32],
    bit i of row j as ops/bitset.pack_bits_u32 lays it) -> the same
    relation indexed by TRANSACTION: transaction t is row heads[t]."""
    bp = p.shape[0]
    dense = np.unpackbits(np.ascontiguousarray(p, np.uint32).view(np.uint8),
                          axis=1, bitorder="little")
    out = np.zeros((bp, bp), np.uint8)
    out[:len(heads), :len(heads)] = dense[np.ix_(heads, heads)]
    return np.packbits(out, axis=1, bitorder="little").view(np.uint32)


def _per_txn(rows, n: int, heads) -> np.ndarray:
    """A per-row device result (verdicts, wave levels), one a transaction:
    its head row's."""
    out = np.asarray(rows)[:n]
    return out if heads is None else out[heads]


def _wire_offset(ret: int) -> int:
    """kp_pack_batch's return, or the ValueError it stands for."""
    if ret == -2:
        raise ValueError(
            "a transaction has more ranges than a row has slots, and this "
            "path takes one row a transaction (the scan-window path); "
            "resolve / resolve_wire take it")
    if ret < 0:
        raise ValueError("malformed resolver wire batch")
    return int(ret)


def encode_resolve_batch(txns: list[TxnConflictInfo]) -> bytes:
    """Serialize txns to the resolver wire format (native/keypack.cpp).

    The sim runtime and tests use this to exercise the production path; a
    real deployment's proxies would emit these bytes directly as their RPC
    payload (the analogue of serializing ResolveTransactionBatchRequest)."""
    out = bytearray()
    for t in txns:
        reads = list(t.read_ranges)
        writes = list(t.write_ranges)
        out += struct.pack("<qii", t.read_version, len(reads), len(writes))
        for rng in reads + writes:
            out += struct.pack("<ii", len(rng.begin), len(rng.end))
            out += rng.begin
            out += rng.end
    return bytes(out)


_KP_LIB = None


def _keypack_lib():
    global _KP_LIB
    if _KP_LIB is None:
        from foundationdb_tpu.native import load_library

        lib = load_library("keypack")
        u8p = ctypes.POINTER(ctypes.c_uint8)
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64 = ctypes.c_int64
        lib.kp_pack_batch.restype = i64
        lib.kp_pack_batch.argtypes = [
            u8p, i64, i64, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, i64,
            i32p, i32p, u8p, i32p, i32p, u8p, i32p, u8p, u8p, i32p,
        ]
        lib.kp_count_txns.restype = i64
        lib.kp_count_txns.argtypes = [u8p, i64, i64]
        _KP_LIB = lib
    return _KP_LIB


def _u8(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _i32(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
