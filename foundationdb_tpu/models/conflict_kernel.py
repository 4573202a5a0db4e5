"""The jitted MVCC conflict-resolution kernel.

This is the TPU-native replacement for the reference resolver's skiplist
engine (fdbserver/SkipList.cpp + ConflictSet.h: ConflictBatch::addTransaction /
detectConflicts / combineWriteConflictRanges). Same observable semantics,
completely different shape:

- The write history is a *step function over the keyspace*: sorted boundary
  keys ``K[C, W]`` with per-segment last-write version ``V[C]``. This is
  exact, not approximate, because the reference hands out ONE commit version
  per resolve batch (masterserver → CommitProxy getVersion), so every write
  of a batch lands at the same version.
- ONE design (ROADMAP C1): the endpoint-key dictionary and the history live
  on the device across dispatches (ResState); the host ships each dispatch's
  never-seen keys with their ranks and every endpoint as a rank
  (ResidentBatch), so the history is a width-1 step function over ranks; it
  is kept in two levels (HistState: a frozen base with its prebuilt RMQ
  table, a small delta every batch probes and paints, merged on demand).
- A batch resolve is one ``jit``ted call of dense ops: binary-search every
  read endpoint into K, sparse-table range-max for "newest write version
  overlapping this read", a rank-space pairwise overlap matrix for intra-batch
  read-vs-earlier-write conflicts, and a wave-relaxation loop (rounds over
  bit-packed [G, G/32] tiles) that reproduces the reference's sequential
  acceptance order without a sequential scan.
- Accepted writes are painted into the step function with a merge-path
  interleave + coverage prefix-sum, then boundaries made redundant (equal
  adjacent versions, expired segments) are compacted out — the analogue of
  the reference skiplist's insert + version-window GC.

Phases carry ``jax.named_scope`` names, so a profiler trace of the REAL
program says which phase an operation belongs to (its ``op_name`` path):
``dict_insert`` / ``dict_evict`` / ``dict_remap`` (resident dictionary
upkeep: apply_delta, apply_evict, apply_dict_remap), ``hist_merge``
(_maybe_merge, advance_hist), ``history_probe`` (too-old mask + reads vs
history), ``endpoint_ranks``, ``accept`` (block scan or wave schedule),
``paint_compact`` and ``verdicts``. The names sit on the shared helpers,
so every entry point (one batch / a scanned window, report, wave, the
mesh's shard step) speaks one vocabulary. Metadata only: the compiled
program does not change.

Everything is static-shape; hosts pad batches (see conflict_set.TPUConflictSet).
Versions on device are int32, relative to a host-held base (the MVCC window
is ~5-7M versions, far inside int32; the host rebases periodically).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from foundationdb_tpu.core.keypack import INT32_MAX
from foundationdb_tpu.core.types import (
    WAVE_LEVEL_CYCLE as LEVEL_CYCLE,
    WAVE_LEVEL_NONE as LEVEL_NONE,
    env_choice as _env_choice,
)
from foundationdb_tpu.ops.bitset import (
    or_matvec_u32,
    pack_bits_u32,
    unpack_bits_u32,
)
from foundationdb_tpu.ops.lex import searchsorted_words, searchsorted_words_fp
from foundationdb_tpu.ops.rmq import range_max, sparse_table

NEG_VERSION = -(2**31) + 1


# Wave-commit mode: "0" (default — sequential-order acceptance, conflicts
# abort) | "1" (reorder-don't-abort: the same conflict graph schedules
# txns into dependency-ordered commit waves; only true cycles abort —
# see _wave_commit_accept). Selects the ENGINE DEFAULT only: both modes'
# entry points are separate jitted programs, so hosts can construct
# engines of either mode in one process (TPUConflictSet(wave_commit=...)).
# Read once at import — flipping it mid-process would silently split jit
# caches.
_WAVE_COMMIT = _env_choice("FDB_TPU_WAVE_COMMIT", "0", ("0", "1")) == "1"

# Speculative pipelined resolve: "0" (default — windows resolve strictly
# in order, the A/B baseline) | "1" (window N+1 dispatches against window
# N's PENDING write sets: N's accepted-so-far writes are painted as if
# committed while N's verdicts are still in flight / unconfirmed by the
# upper layer; a host-side reconcile ring confirms or repairs when the
# verdicts land — see conflict_set.TPUConflictSet.spec_dispatch_window).
# Same import-once rule as the flag above.
_SPEC_RESOLVE = _env_choice("FDB_TPU_SPEC_RESOLVE", "0", ("0", "1")) == "1"

# Verdict encoding (core.types.Verdict values, as device int8).
V_COMMITTED = 0
V_CONFLICT = 1
V_TOO_OLD = 2


class ConflictState(NamedTuple):
    """Device-resident write history (the step function)."""

    keys: jax.Array  # int32 [C, W] sorted; keys[0] = packed b""; tail = +inf
    versions: jax.Array  # int32 [C]; versions[i] covers [keys[i], keys[i+1]); tail NEG
    n_used: jax.Array  # int32 scalar — live boundary count
    oldest: jax.Array  # int32 scalar — oldest resolvable (relative) version
    overflow: jax.Array  # bool scalar — capacity exceeded; host must react


class BatchTensors(NamedTuple):
    """One padded resolver batch (host-packed, see conflict_set.BatchPacker)."""

    read_begin: jax.Array  # int32 [B, R, W]
    read_end: jax.Array  # int32 [B, R, W]
    read_mask: jax.Array  # bool [B, R]
    write_begin: jax.Array  # int32 [B, Q, W]
    write_end: jax.Array  # int32 [B, Q, W]
    write_mask: jax.Array  # bool [B, Q]
    read_version: jax.Array  # int32 [B] (relative)
    txn_mask: jax.Array  # bool [B]
    # bool [B], or None when every transaction of the batch fits one row:
    # row i continues the transaction of row i - 1 (see txn_segments).
    cont: jax.Array | None = None


def init_state(capacity: int, width: int, min_key) -> ConflictState:
    """min_key: the codec's packed b"" (KeyCodec.min_key) — boundary 0."""
    keys = jnp.full((capacity, width), INT32_MAX, dtype=jnp.int32)
    keys = keys.at[0].set(jnp.asarray(min_key, dtype=jnp.int32))
    versions = jnp.full((capacity,), NEG_VERSION, dtype=jnp.int32)
    return ConflictState(
        keys=keys,
        versions=versions,
        n_used=jnp.int32(1),
        oldest=jnp.int32(0),
        overflow=jnp.zeros((), jnp.bool_),
    )


def _read_vs_accepted_writes(
    rb: jax.Array,
    re_: jax.Array,
    read_live: jax.Array,
    wb: jax.Array,
    we: jax.Array,
    write_live: jax.Array,
    accepted: jax.Array,
) -> jax.Array:
    """bool [B, R]: read range slot overlaps SOME accepted txn's write
    range (rank space). The intra-batch half of the loser-range report:
    all of a batch's accepted writes land at the same commit version, so
    a rejected txn repairing at that version must re-read every one of
    its ranges an accepted peer wrote — earlier OR later in batch order
    (the report is for re-reading a snapshot, not for blame assignment).
    A txn's own writes never qualify (it was rejected, so it is not in
    `accepted`)."""
    b, q = wb.shape
    aw = (write_live & accepted[:, None]).reshape(b * q)
    wbf = wb.reshape(b * q)
    wef = we.reshape(b * q)
    hit = (
        (rb[:, :, None] < wef[None, None, :])
        & (wbf[None, None, :] < re_[:, :, None])
        & aw[None, None, :]
    )
    return read_live & jnp.any(hit, axis=2)


# ---------------------------------------------------------------------------
# Phase 2: intra-batch conflict graph + wave acceptance
# ---------------------------------------------------------------------------


# Above this many (read-slot × write-slot) pairs the unrolled overlap form
# is replaced by one vectorized 4D reduce (compile time / program size
# cap). 128 keeps tpcc's 12x8 on the unrolled path: inside the block
# scan each term is a fused [G, B] compare with no 4D intermediate,
# while the vectorized form materializes [G, R, B, Q] per block.
_OVERLAP_UNROLL_LIMIT = 128


def _overlap_rows(
    rows_rb: jax.Array,
    rows_re: jax.Array,
    rows_live: jax.Array,
    wb: jax.Array,
    we: jax.Array,
    write_live: jax.Array,
) -> jax.Array:
    """M rows [N, B] for a slice of reader txns vs ALL writer txns.

    rows_*: [N, R] rank-space read intervals; wb/we/write_live: [B, Q].
    One fused [N, B] elementwise term per (read-slot, write-slot) pair —
    no 4D intermediate, no serialized map: XLA fuses the R·Q compares into
    a single memory-bound pass over the output matrix.

    Program size grows as R·Q under the unrolled form, so large range
    limits (e.g. tpcc's 12×8) switch to a single vectorized 4D reduce:
    one [N, R, B, Q] compare + any-reduce, constant program size at the
    cost of a fusible 4D intermediate."""
    n, r = rows_rb.shape
    b, q = wb.shape
    if r * q > _OVERLAP_UNROLL_LIMIT:
        t = (rows_rb[:, :, None, None] < we[None, None, :, :]) & (
            wb[None, None, :, :] < rows_re[:, :, None, None]
        )
        live = rows_live[:, :, None, None] & write_live[None, None, :, :]
        return jnp.any(t & live, axis=(1, 3))
    m = jnp.zeros((n, b), jnp.bool_)
    for i in range(r):
        rbi = rows_rb[:, i, None]
        rei = rows_re[:, i, None]
        livei = rows_live[:, i, None]
        for j in range(q):
            t = (rbi < we[None, :, j]) & (wb[None, :, j] < rei)
            m = m | (t & livei & write_live[None, :, j])
    return m


# Block size for the block-sequential acceptance scan. Within a block the
# wave relaxation runs on a [G, G] tile (0.5 MB at G=512 — VMEM-resident);
# cross-block influence is a single [G, B] matvec per block. This bounds
# the data-dependent round count by G per block AND shrinks each round's
# traffic from [B, B] (134 MB at B=8192) to [G, G], which matters on
# high-conflict workloads (mako Zipf-0.99, 95% conflicts) where acceptance
# chains are deep and the full-matrix wave paid 268 MB per round.
_ACCEPT_BLOCK = 512


def _block_scan_accept(base, xs_rows, make_rows):
    """Shared block-scan body for both acceptance entry points.

    Exact sequential-order acceptance (equivalent to _wave_accept and to
    the reference's sequential ConflictBatch order): process blocks of G
    txns in order (lax.scan); a block's candidates are first demoted by
    accepted writers in EARLIER blocks (one [G, B] @ [B] matvec against
    the accepted-so-far vector — later blocks contribute zeros), then the
    within-block order is resolved by the [G, G] wave. All predecessors
    of a block outside it are fully determined when the block runs, so
    the result is exact.

    xs_rows: pytree whose leaves have leading axis nblk; make_rows maps
    one slice of it to that block's [G, B] overlap rows.

    Packed-mask form (block size a multiple of 32; a smaller batch keeps
    bool rows and the bf16 matvec): the [G, B] rows are uint32-packed the
    moment they are built and never touched as bool again — the cross-block demotion matvec becomes a
    bitwise AND + any-reduce against the packed accepted vector (1/8 the
    row bytes, no bool→bf16 conversion, no MXU round trip), the accepted
    carry itself is a [B/32] bitset, and the within-block tile handed to
    the wave accept is the packed [G, G/32] diagonal slice.
    """
    b = base.shape[0]
    g = min(_ACCEPT_BLOCK, b)
    nblk = b // g
    packed = g % 32 == 0

    def body(acc, xs):
        rows_x, base_k, k = xs
        rows_k = make_rows(rows_x)  # [G, B]
        if packed:
            rp = pack_bits_u32(rows_k)  # [G, B/32]
            prior_hit = or_matvec_u32(rp, acc)
            sub = jax.lax.dynamic_slice(
                rp, (jnp.int32(0), k * (g // 32)), (g, g // 32)
            )
            acc_k = _wave_accept_packed(base_k & ~prior_hit, sub)
            acc = jax.lax.dynamic_update_slice(
                acc, pack_bits_u32(acc_k), (k * (g // 32),)
            )
        else:
            prior_hit = (
                jax.lax.dot(
                    rows_k.astype(jnp.bfloat16),
                    acc.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32,
                )
                > 0.0
            )
            sub = jax.lax.dynamic_slice(rows_k, (jnp.int32(0), k * g), (g, g))
            acc_k = _wave_accept(base_k & ~prior_hit, sub)
            acc = jax.lax.dynamic_update_slice(acc, acc_k, (k * g,))
        return acc, None

    acc, _ = jax.lax.scan(
        body,
        jnp.zeros((b // 32,), jnp.uint32) if packed else jnp.zeros_like(base),
        (
            xs_rows,
            base.reshape(nblk, g),
            jnp.arange(nblk, dtype=jnp.int32),
        ),
    )
    return unpack_bits_u32(acc, b) if packed else acc


def _block_accept(base: jax.Array, m: jax.Array) -> jax.Array:
    """Block-scan acceptance over a materialized [B, B] overlap matrix."""
    b = base.shape[0]
    g = min(_ACCEPT_BLOCK, b)
    if b % g:
        return _wave_accept(base, m)
    return _block_scan_accept(
        base, m.reshape(b // g, g, b), lambda rows_k: rows_k
    )


def _block_accept_fused(
    base: jax.Array,
    rb: jax.Array,
    re_: jax.Array,
    read_live: jax.Array,
    wb: jax.Array,
    we: jax.Array,
    write_live: jax.Array,
) -> jax.Array:
    """_block_accept with the overlap rows computed in-scan from rank
    intervals: the [B, B] matrix is never materialized — each block builds
    its own [G, B] slice from the [B, R]/[B, Q] rank vectors (a few KB),
    saving the ~200 MB/batch of matrix write+read at B=8192."""
    b = base.shape[0]
    g = min(_ACCEPT_BLOCK, b)
    if b % g:
        m = _overlap_rows(rb, re_, read_live, wb, we, write_live)
        return _wave_accept(base, m)
    nblk = b // g
    r = rb.shape[1]
    return _block_scan_accept(
        base,
        (
            rb.reshape(nblk, g, r),
            re_.reshape(nblk, g, r),
            read_live.reshape(nblk, g, r),
        ),
        lambda x: _overlap_rows(x[0], x[1], x[2], wb, we, write_live),
    )


# ---------------------------------------------------------------------------
# Transactions wider than a row (BatchTensors.cont)
# ---------------------------------------------------------------------------
#
# The batch's static row holds R read and Q write ranges. A transaction with
# more takes CONTINUATION rows right after its first (its head): the same
# read version and mask, its further ranges in slot order; the host marks
# them in ``cont`` (conflict_set._pack / native/keypack.cpp). Rows and
# transactions then stop being the same index, and the reference's rule is
# about transactions: one is accepted, and its writes painted, only if NONE
# of its rows conflicts, in batch order, with the accepted writes of earlier
# transactions of the batch counted. Everything below reduces rows to
# transactions around the acceptance designs, which stay as they are: a
# transaction is a candidate when every one of its rows is (txn_candidates);
# the row-by-row overlap matrix is folded onto the head rows
# (txn_fold_overlap: head(t) meets head(u) when any row of t meets any row
# of u, and a continuation row meets nothing), so the block scan, the wave
# and the wave-commit schedule see one row a transaction; the heads' answers
# go back to every row (txn_spread), which is what the paint, the verdicts
# and the loser report read. A batch with ``cont`` None never comes here:
# it traces to the program it always had.


class TxnSegments(NamedTuple):
    head: jax.Array  # bool [B] — the row starts a transaction
    start: jax.Array  # int32 [B] — the head row of the row's transaction
    end: jax.Array  # int32 [B] — the last row of the row's transaction


def txn_segments(cont: jax.Array) -> TxnSegments:
    b = cont.shape[0]
    idx = jnp.arange(b, dtype=jnp.int32)
    head = ~cont
    start = jax.lax.cummax(jnp.where(head, idx, 0))
    last = jnp.concatenate([head[1:], jnp.ones((1,), jnp.bool_)])
    end = jax.lax.cummin(jnp.where(last, idx, b - 1), reverse=True)
    return TxnSegments(head, start, end)


def _seg_any(m: jax.Array, seg: TxnSegments, axis: int) -> jax.Array:
    """OR of `m` over each transaction's rows along `axis`, at every row of
    the transaction: an inclusive prefix count, read at the two ends."""
    c = jnp.cumsum(m.astype(jnp.int32), axis=axis)
    at_start = jnp.take(c, seg.start, axis=axis) - jnp.take(
        m, seg.start, axis=axis).astype(jnp.int32)
    return jnp.take(c, seg.end, axis=axis) > at_start


def txn_candidates(base: jax.Array, seg: TxnSegments) -> jax.Array:
    """bool [B]: set on a head row whose transaction has every row in
    `base`; a continuation row is never a candidate of its own."""
    return seg.head & ~_seg_any(~base, seg, 0)


def txn_spread(v: jax.Array, seg: TxnSegments) -> jax.Array:
    """The head row's value on every row of its transaction."""
    return v[seg.start]


def txn_fold_overlap(m: jax.Array, seg: TxnSegments) -> jax.Array:
    """[B, B] row-by-row overlaps -> the same relation between head rows:
    entry (head(t), head(u)) is set when any row of t meets any row of u;
    rows and columns of continuation rows are cleared. The diagonal block
    of a transaction (its reads against its own writes) lands on its
    head's diagonal, which every acceptance design ignores."""
    m = _seg_any(m, seg, 1) & seg.head[None, :]
    return _seg_any(m, seg, 0) & seg.head[:, None]


def _wave_accept(base: jax.Array, m: jax.Array) -> jax.Array:
    """Reproduce sequential in-order acceptance with O(depth) matvec rounds.

    base[i]: txn i would commit absent intra-batch conflicts. Edge j→i exists
    when j < i and M[i, j] (j's writes overlap i's reads). Sequential rule:
    accept i iff base[i] and no ACCEPTED j<i with an edge. Rounds: a txn is
    rejected as soon as an accepted conflicting predecessor is known; it is
    accepted once all its predecessors are determined and none of the
    accepted ones conflict. Each round determines at least the lowest
    undetermined txn, and in practice conflict chains are shallow (hot-key
    workloads determine in 2-3 rounds).
    """
    b = base.shape[0]
    tri = jnp.tril(jnp.ones((b, b), jnp.bool_), k=-1)
    # bf16 edges: the matvec rides the MXU; accumulation is forced to f32 so
    # row sums up to B stay exact (we only test > 0 anyway).
    p = (m & tri).astype(jnp.bfloat16)  # [B, B]

    def mv(vec):
        return (
            jax.lax.dot(p, vec.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
            > 0.0
        )

    def cond(carry):
        det, _, i = carry
        # Formal bound: each round determines at least the lowest
        # undetermined txn (all its predecessors are determined), so B
        # rounds always suffice — the cap makes the worst case explicit.
        return ~jnp.all(det) & (i < b)

    def step(carry):
        det, acc, i = carry
        hit_acc = mv(acc)
        pending = mv(~det)
        newly_rej = ~det & hit_acc
        newly_acc = ~det & base & ~hit_acc & ~pending
        det = det | newly_rej | newly_acc | (~det & ~base)
        acc = acc | newly_acc
        return det, acc, i + 1

    det0 = ~base  # non-candidates are determined (not accepted) immediately
    acc0 = jnp.zeros_like(base)
    _, acc, _ = jax.lax.while_loop(cond, step, (det0, acc0, jnp.int32(0)))
    return acc


def _wave_accept_packed(base: jax.Array, p: jax.Array) -> jax.Array:
    """_wave_accept over a uint32-packed [G, G/32] predecessor bitset.

    Same relaxation rounds and round count; each round's two matvecs are
    bitwise AND + any-reduce against the packed tile — 1/16 the operand
    bytes of the bf16 MXU tile (bit vs 2-byte lane) and no bool↔bf16
    conversions. ``p`` is the raw packed block tile; the strict-lower
    triangle mask is applied here (packed, so it too is 1/8 the bytes)."""
    g = base.shape[0]
    p = p & pack_bits_u32(jnp.tril(jnp.ones((g, g), jnp.bool_), k=-1))

    def mv(vec):
        return or_matvec_u32(p, pack_bits_u32(vec))

    def cond(carry):
        det, _, i = carry
        return ~jnp.all(det) & (i < g)

    def step(carry):
        det, acc, i = carry
        hit_acc = mv(acc)
        pending = mv(~det)
        newly_rej = ~det & hit_acc
        newly_acc = ~det & base & ~hit_acc & ~pending
        det = det | newly_rej | newly_acc | (~det & ~base)
        acc = acc | newly_acc
        return det, acc, i + 1

    det0 = ~base
    acc0 = jnp.zeros_like(base)
    _, acc, _ = jax.lax.while_loop(cond, step, (det0, acc0, jnp.int32(0)))
    return acc


# ---------------------------------------------------------------------------
# Phase 2b: wave commit (FDB_TPU_WAVE_COMMIT=1) — reorder, don't abort
# ---------------------------------------------------------------------------
#
# Sequential acceptance treats batch order as serialization order and
# aborts every txn whose reads overlap an accepted EARLIER txn's writes —
# throwing away the conflict graph it just materialized. Wave commit
# spends it instead (FAFO, arXiv:2507.10757): the constraint "i must
# serialize BEFORE j" exists exactly when reads(i) ∩ writes(j) ≠ ∅ (i
# must not observe j's write), which is the untriangled overlap matrix.
# Topologically leveling that digraph yields commit WAVES: wave 0 txns
# see only pre-batch state, wave k txns serialize after waves < k, and
# every write-after-read chain commits in dependency order instead of
# losing all but its luckiest link. Only txns on TRUE CYCLES (mutual
# read-write entanglement — e.g. two RMWs of one key) are unschedulable;
# they abort, one exactly-on-a-cycle victim at a time, and the repair
# subsystem mops them up.
#
# Serializability: the realized order is (wave, batch index). A committed
# txn j's reads overlap no historical write past its read version (the
# history gate is unchanged) and no committed peer write EXCEPT those of
# txns at strictly LATER waves — which serialize after j, so j's
# pre-batch snapshot is exactly what the order prescribes. All writes
# still land at the batch commit version: visible read versions are
# always batch versions (GRV hands out committed batch versions, never
# intra-batch points), so a single-version paint is byte-equivalent for
# every future conflict test while the proxy applies same-version
# mutations in wave order.

#: Wave-level encoding (int32 [B], alongside the verdicts):
#:   >= 0  committed at this wave (serialization order = (level, index))
#:   -1    not committed for non-cycle reasons (history conflict,
#:         TOO_OLD, masked slot)
#:   -2    aborted on a true cycle (the repair engine's residue)
#: Canonical values live in core.types (imported at the top) so the
#: oracle and the runtime share them without importing device code.


def _pred_matrix_packed(base, rb, re_, read_live, wb, we, write_live,
                        seg: "TxnSegments | None" = None):
    """uint32 [BP, BP/32] packed predecessor bitsets over rank intervals:
    bit i of row j ⇔ reads(i) ∩ writes(j) ≠ ∅ (txn i must serialize
    before txn j), diagonal cleared, restricted to candidate txns. With
    `seg` (a batch with continuation rows) the matrix is built whole and
    folded onto the head rows before it is packed.

    Built [G, B]-blockwise with the same _overlap_rows primitive as the
    acceptance scan (writes of the block's txns as rows, everyone's reads
    as columns — overlap is symmetric, so the transpose falls out of the
    argument order) and packed the moment each block materializes. Inputs
    are padded to a multiple of 32 (BP) by the caller."""
    bp = base.shape[0]
    g = min(_ACCEPT_BLOCK, bp)
    q = wb.shape[1]
    if seg is not None:
        p = pack_bits_u32(txn_fold_overlap(
            _overlap_rows(wb, we, write_live, rb, re_, read_live), seg))
    elif bp % g == 0 and bp > g:
        nblk = bp // g
        p = jax.lax.map(
            lambda x: pack_bits_u32(
                _overlap_rows(x[0], x[1], x[2], rb, re_, read_live)
            ),
            (
                wb.reshape(nblk, g, q),
                we.reshape(nblk, g, q),
                write_live.reshape(nblk, g, q),
            ),
        ).reshape(bp, bp // 32)
    else:
        p = pack_bits_u32(
            _overlap_rows(wb, we, write_live, rb, re_, read_live)
        )
    idx = jnp.arange(bp, dtype=jnp.int32)
    diag = jnp.where(
        (idx[:, None] >> 5) == jnp.arange(bp // 32, dtype=jnp.int32)[None, :],
        (jnp.uint32(1) << (idx & 31).astype(jnp.uint32))[:, None],
        jnp.uint32(0),
    )
    return p & ~diag & pack_bits_u32(base)[None, :]


def _min_pred(p, undetp, j):
    """Lowest-index undetermined predecessor of txn j (packed row scan).
    Only called on stuck txns, whose undetermined predecessor set is
    non-empty by construction."""
    row = p[j] & undetp
    w = jnp.argmax(row != 0).astype(jnp.int32)
    lanes = jnp.arange(32, dtype=jnp.uint32)
    bit = jnp.argmax(((row[w] >> lanes) & 1) != 0).astype(jnp.int32)
    return w * 32 + bit


def _cycle_victim(p, undet, undetp):
    """Deterministic exactly-on-a-cycle victim of a stalled schedule.

    At a stall every undetermined txn has an undetermined predecessor, so
    the min-predecessor walk is total on the stuck set and — being a
    deterministic functional graph — terminates on exactly one cycle.
    Walk BP steps from the lowest stuck txn (guaranteed to have entered
    the cycle: entry distance < |stuck| <= BP), then walk BP more
    tracking the minimum index visited — at least one full loop of the
    cycle, so the result is the cycle's minimum-index member regardless
    of where the first walk landed. The host oracle replays the identical
    rule with n steps; both step counts exceed every entry distance and
    cycle length, so the victims agree byte-for-byte."""
    bp = undet.shape[0]
    j0 = jnp.argmax(undet).astype(jnp.int32)
    j = jax.lax.fori_loop(0, bp, lambda _, j: _min_pred(p, undetp, j), j0)

    def track(_, carry):
        j, m = carry
        j = _min_pred(p, undetp, j)
        return j, jnp.minimum(m, j)

    _, victim = jax.lax.fori_loop(0, bp, track, (j, j))
    return victim


@jax.named_scope("accept")
def wave_pred_matrix(
    base: jax.Array, ranks: tuple[jax.Array, ...],
    cont: "jax.Array | None" = None,
) -> jax.Array:
    """uint32 [BP, BP/32] packed predecessor bitsets over (possibly
    shard-clipped) rank intervals, padded to BP = ceil32(B). The
    shard-exchange operand: shards partition the keyspace, so the OR of
    per-shard clipped matrices IS the global matrix (an edge's overlap
    region lands in exactly the shards that witness it) — the mesh
    engine all_gathers and OR-reduces these, and the role-level
    resolve_edges payload carries them to the commit proxy."""
    rb, re_, read_live, wb, we, write_live = ranks
    b = base.shape[0]
    bp = ((b + 31) // 32) * 32
    if bp != b:
        pad = bp - b
        base = jnp.pad(base, (0, pad))
        rb = jnp.pad(rb, ((0, pad), (0, 0)))
        re_ = jnp.pad(re_, ((0, pad), (0, 0)))
        read_live = jnp.pad(read_live, ((0, pad), (0, 0)))
        wb = jnp.pad(wb, ((0, pad), (0, 0)))
        we = jnp.pad(we, ((0, pad), (0, 0)))
        write_live = jnp.pad(write_live, ((0, pad), (0, 0)))
        if cont is not None:
            cont = jnp.pad(cont, (0, pad))
    # `base` of a batch with continuation rows is txn_candidates' (heads
    # only), so the candidate mask clears every continuation column.
    seg = None if cont is None else txn_segments(cont)
    return _pred_matrix_packed(base, rb, re_, read_live, wb, we, write_live,
                               seg)


def wave_occupied_tiles(p: jax.Array) -> jax.Array:
    """int32 scalar: non-zero 32x32-bit tiles of a packed predecessor
    matrix (32 rows x 1 uint32 word). The realized-graph density signal
    behind the mesh exchange-cost model: a tile-scoped exchange ships
    only occupied tiles, so its bytes scale with the conflict graph the
    workload actually produced, not with BP² (bench.py roofline
    ``exchange_bytes_per_batch``)."""
    bp, w = p.shape
    t = p.reshape(bp // 32, 32, w)
    return jnp.sum(jnp.any(t != 0, axis=1).astype(jnp.int32))


def _wave_level_packed(base: jax.Array, p: jax.Array) -> jax.Array:
    """level int32 [BP] from a packed predecessor matrix: the wave-commit
    fixed point. ``base`` is the padded candidate mask; ``p`` the packed
    [BP, BP/32] graph (global or single-shard — the rule is graph-
    agnostic).

    Fixed point over the packed predecessor bitsets (same operand shape
    and AND/any-reduce rounds as _wave_accept_packed): each iteration
    either levels every txn with no undetermined predecessor into the
    next wave, or — when the remaining subgraph has no source, i.e. every
    stuck txn sits on or behind a cycle — aborts the one _cycle_victim
    and continues, so txns merely DOWNSTREAM of a cycle are re-examined
    once the cycle is broken and still commit. Every iteration determines
    at least one txn, bounding the loop by the candidate count (the
    saturation cap makes the worst case explicit, exactly like the wave
    accept's round cap). Deterministic in the graph alone, so every mesh
    shard running it on the same OR-reduced matrix reports the identical
    schedule (core/wavemesh.level_wave_graph is the host replay)."""
    bp = base.shape[0]
    idx = jnp.arange(bp, dtype=jnp.int32)

    def cond(carry):
        undet, _level, _wave, it = carry
        return jnp.any(undet) & (it < bp + 1)

    def step(carry):
        undet, level, wave, it = carry
        undetp = pack_bits_u32(undet)
        blocked = or_matvec_u32(p, undetp)
        ready = undet & ~blocked
        has_ready = jnp.any(ready)
        victim = jax.lax.cond(
            has_ready,
            lambda: jnp.int32(bp),  # out-of-range: no abort this round
            lambda: _cycle_victim(p, undet, undetp),
        )
        vmask = idx == victim
        level = jnp.where(
            has_ready & ready,
            wave,
            jnp.where(vmask, jnp.int32(LEVEL_CYCLE), level),
        )
        undet = undet & ~jnp.where(has_ready, ready, vmask)
        return undet, level, wave + has_ready.astype(jnp.int32), it + 1

    _, level, _, _ = jax.lax.while_loop(
        cond,
        step,
        (
            base,
            jnp.full((bp,), LEVEL_NONE, jnp.int32),
            jnp.int32(0),
            jnp.int32(0),
        ),
    )
    return level


@jax.named_scope("accept")
def wave_level_from_graph(
    cand: jax.Array, p: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """(accepted bool [B], level int32 [B]) from a GLOBAL predecessor
    matrix + global candidate mask. Columns are re-masked to candidates
    here: a shard's clipped matrix carries edges from txns that are
    candidates in its local view but history-gated on another shard, and
    those edges must not constrain the schedule."""
    b = cand.shape[0]
    bp = p.shape[0]
    candp = jnp.pad(cand, (0, bp - b)) if bp != b else cand
    p = p & pack_bits_u32(candp)[None, :]
    level = _wave_level_packed(candp, p)[:b]
    return level >= 0, level


def _wave_commit_accept(
    base: jax.Array, ranks: tuple[jax.Array, ...],
    cont: "jax.Array | None" = None,
) -> tuple[jax.Array, jax.Array]:
    """(accepted bool [B], level int32 [B]): schedule candidate txns into
    dependency-ordered commit waves; abort only true-cycle members. The
    single-shard composition of wave_pred_matrix + _wave_level_packed."""
    b = base.shape[0]
    p = wave_pred_matrix(base, ranks, cont)
    bp = p.shape[0]
    basep = jnp.pad(base, (0, bp - b)) if bp != b else base
    level = _wave_level_packed(basep, p)[:b]
    return level >= 0, level


# ---------------------------------------------------------------------------
# Phase 3: paint accepted writes into the step function + compact
# ---------------------------------------------------------------------------


def _paint_tail(
    state: ConflictState,
    snew: jax.Array,
    sdelta_new: jax.Array,
    soldv_new: jax.Array,
    scross: jax.Array,
    commit_version: jax.Array,
    new_oldest: jax.Array,
) -> ConflictState:
    """Shared merge-path + coverage + compact tail of the paint pass.

    Inputs are the SORTED new endpoints (snew [n2, W] keys, coverage
    deltas, pre-paint segment versions, cross-ranks into the history), as
    _paint_and_compact_res gathers them; nothing here assumes W = 1."""
    c, w = state.keys.shape
    n2 = snew.shape[0]
    n = c + n2

    # Merge-path by rank arithmetic, one search and three gathers a slot
    # (the construction _merge_delta had until PR 41; here n is the delta's
    # 16,386 slots, not the history's capacity, and ROADMAP A5 is its item).
    # pos_n[j] = output slot of sorted-new[j] = j + its cross-rank in the
    # history ('right' side puts history entries before equal new entries —
    # a collision-free permutation of [0, n) even with duplicate keys).
    # Each output slot then derives its source by rank arithmetic: slot i
    # holds new[k] iff pos_n[k] == i, else history[i - #new_slots_before_i].
    pos_n = jnp.arange(n2, dtype=jnp.int32) + scross
    idx = jnp.arange(n, dtype=jnp.int32)
    cnt_le = jnp.searchsorted(pos_n, idx, side="right").astype(jnp.int32)
    k_new = jnp.maximum(cnt_le - 1, 0)
    from_new = (cnt_le > 0) & (pos_n[k_new] == idx)
    hist_idx = jnp.clip(idx - cnt_le, 0, c - 1)  # exact for non-new slots

    skeys = jnp.where(from_new[:, None], snew[k_new], state.keys[hist_idx])
    sdelta = jnp.where(from_new, sdelta_new[k_new], 0)
    soldv = jnp.where(from_new, soldv_new[k_new], state.versions[hist_idx])

    covered = jnp.cumsum(sdelta) > 0
    is_inf = jnp.all(skeys == INT32_MAX, axis=-1)
    newv = jnp.where(covered, commit_version, soldv)
    # GC: segments at/below the window floor can never conflict again.
    newv = jnp.where((newv <= new_oldest) | is_inf, NEG_VERSION, newv)

    fkeys, fv, n_used, overflow = _dedup_compact(skeys, newv, c, state.overflow)
    return ConflictState(
        keys=fkeys,
        versions=fv,
        n_used=n_used,
        oldest=new_oldest,
        overflow=overflow,
    )


def _stream_shift(cols, shift, k: int, up: bool):
    """One streaming pass of a staged move: every row whose remaining
    ``shift`` has the binary digit ``k`` moves k slots (``up``: to the
    higher index), its columns and its shift riding together; a slot left
    behind reads shift 0, so it stays put and whatever arrives overwrites
    it. The rows' shifts are nondecreasing along the array, so rows keep
    their order after every digit and no two ever meet: upwards the digits
    run high to low (holes open between the rows, _merge_delta), downwards
    low to high (the same states in reverse: holes close, _dedup_compact).
    ``cols`` are arrays with the rows on axis 0."""

    def moved(x):
        pad = jnp.zeros((k,) + x.shape[1:], x.dtype)
        if up:
            return jnp.concatenate([pad, x[:-k]])
        return jnp.concatenate([x[k:], pad])

    from_k = moved(shift)
    arrives = (from_k & k) != 0
    leaves = (shift & k) != 0
    cols = tuple(
        jnp.where(arrives.reshape((-1,) + (1,) * (x.ndim - 1)), moved(x), x)
        for x in cols
    )
    return cols, jnp.where(arrives, from_k, jnp.where(leaves, 0, shift))


def _dedup_compact(skeys, newv, c_out, prior_overflow):
    """Shared compaction tail of every step-function rewrite (paint and
    the window-history merge): dedup equal keys, drop boundaries that no
    longer change the step function, compact survivors to the front.

    skeys [n, W] sorted (ties allowed), newv [n] already GC'd (expired and
    padding rows hold the sentinel). Returns (keys, versions, n_used,
    overflow) at capacity c_out.

    Nothing searches and nothing gathers once per row: the version of the
    previous kept boundary is a forward fill by doubling (one streaming
    pass a binary digit of the longest run of equal keys: one pass for a
    merge, whose runs are a base row and the delta row equal to it), and
    the survivors move DOWN by the count of dropped rows before them in
    streaming shifts (_stream_shift), a pass skipped when no survivor's
    count has that digit.

    What the chip charges (TPU v5 lite, n = 532,482 rows of one key word;
    my chip run, PR 41, PERF.md section 6's table): a GATHERED row 7.9 ns
    whatever it is gathered from (4.2 ms a gather, and a binary search is
    14-20 of them: the three searches this function and _merge_delta made
    over the capacity were 250 ms of a 316 ms merge); a STREAMING shift
    pass over keys, versions and shift 0.05 ms, a forward-fill pass 0.02
    ms, a prefix sum under 0.05 ms; a SCATTER of 8,194 sorted, unique
    updates 0.22 ms (27 ns an update: scatters do serialize, and 8,194 of
    them still cost a twentieth of one gather over the history). At the
    paint's size (n = 16,386) this body takes 0.45 ms where the searching
    one took 1.4 ms, so one construction serves both callers."""
    n = skeys.shape[0]
    is_inf = jnp.all(skeys == INT32_MAX, axis=-1)
    # Dedup equal keys: keep the LAST occurrence (it carries the full
    # coverage sum and the consistent old version).
    neq_next = jnp.any(skeys[:-1] != skeys[1:], axis=-1)
    keep1 = jnp.concatenate([neq_next, jnp.ones((1,), jnp.bool_)])
    # Drop boundaries whose version equals the previous KEPT boundary's —
    # they no longer change the step function (this is what erases interior
    # boundaries of freshly painted ranges and expired segments). prev_v[i]
    # is the version of the last keep1 row before i (none: a value no
    # version takes): after the pass of digit k a row knows it if that row
    # lies within 2k rows behind it. A +inf row is never kept, whatever it
    # reads, so the padding's long run of equal keys costs no pass.
    no_prev = NEG_VERSION - 1
    prev_v = jnp.concatenate([jnp.full((1,), no_prev, newv.dtype), newv[:-1]])
    known = jnp.concatenate([jnp.ones((1,), jnp.bool_), (keep1 | is_inf)[:-1]])

    def fill(pv, ok, k):
        pv_k = jnp.concatenate([jnp.full((k,), no_prev, pv.dtype), pv[:-k]])
        ok_k = jnp.concatenate([jnp.ones((k,), jnp.bool_), ok[:-k]])
        return jnp.where(ok, pv, pv_k), ok | ok_k

    for b in range((n - 1).bit_length()):
        prev_v, known = jax.lax.cond(
            jnp.all(known), lambda pv, ok: (pv, ok),
            functools.partial(fill, k=1 << b), prev_v, known,
        )
    keep = keep1 & (newv != prev_v) & ~is_inf

    # The keyspace minimum must always remain a boundary. Force its run's
    # LAST row (the keep-last dedup representative): forcing the first
    # would duplicate the boundary whenever a batch paints endpoints
    # equal to the minimum (e.g. shard-clamped delta-0 entries at lo).
    first_live = jnp.argmax(~is_inf)  # index of smallest real key (= min key)
    is_min = jnp.all(skeys == skeys[first_live], axis=-1) & ~is_inf
    min_last = n - 1 - jnp.argmax(is_min[::-1])
    keep = keep.at[min_last].set(True)

    # Compact survivors to the front: the j-th kept row moves down by the
    # dropped rows before it, a count that never falls from one survivor
    # to the next. A dropped row's count reads 0: it stays where it is
    # until a survivor lands on it, or ends behind the last survivor.
    keep_cum = jnp.cumsum(keep.astype(jnp.int32))  # [n], non-decreasing
    n_used = keep_cum[-1]
    down = jnp.where(keep, jnp.arange(1, n + 1, dtype=jnp.int32) - keep_cum, 0)
    digits = jnp.bitwise_or.reduce(down)
    cols = (skeys, newv)
    for b in range((n - 1).bit_length()):
        k = 1 << b
        cols, down = jax.lax.cond(
            (digits & k) != 0,
            functools.partial(_stream_shift, k=k, up=False),
            lambda cols, down: (cols, down), cols, down,
        )
    live_out = jnp.arange(c_out, dtype=jnp.int32) < n_used
    fkeys = jnp.where(live_out[:, None], cols[0][:c_out], INT32_MAX)
    fv = jnp.where(live_out, cols[1][:c_out], NEG_VERSION)
    overflow = prior_overflow | (n_used > c_out)
    return fkeys, fv, jnp.minimum(n_used, c_out), overflow


# ---------------------------------------------------------------------------
# Verdicts, the loser report and the acceptance dispatch
# ---------------------------------------------------------------------------


@jax.named_scope("verdicts")
def assemble_verdicts(
    too_old: jax.Array, txn_mask: jax.Array, accepted: jax.Array
) -> jax.Array:
    return jnp.where(
        too_old,
        jnp.int8(V_TOO_OLD),
        jnp.where(txn_mask & ~accepted, jnp.int8(V_CONFLICT), jnp.int8(V_COMMITTED)),
    )


@jax.named_scope("verdicts")
def loser_range_mask(
    hist_mask: jax.Array,
    ranks: tuple[jax.Array, ...],
    accepted: jax.Array,
    verdicts: jax.Array,
) -> jax.Array:
    """bool [B, R]: which read range slots of each CONFLICT txn lost —
    history conflicts exactly, plus overlaps with accepted peers' writes
    (whose mutations land at this batch's commit version). Surfaced to the
    host so the resolver's conflicting-keys report (and the client repair
    engine behind it) re-reads only these, not the whole read set."""
    rb, re_, read_live, wb, we, write_live = ranks
    intra = _read_vs_accepted_writes(
        rb, re_, read_live, wb, we, write_live, accepted
    )
    return (hist_mask | intra) & (verdicts == V_CONFLICT)[:, None]


@jax.named_scope("accept")
def _accept_or_schedule(base, ranks, wave: bool, cont=None):
    """Shared acceptance dispatch: sequential-order block scan (wave=False)
    or the wave-commit schedule (wave=True — levels ride along). `cont`
    (a batch with continuation rows, else None) reduces rows to
    transactions around either: scope ``accept/txn_rows``."""
    if cont is None:
        if wave:
            return _wave_commit_accept(base, ranks)
        return _block_accept_fused(base, *ranks), None
    with jax.named_scope("txn_rows"):
        seg = txn_segments(cont)
        cand = txn_candidates(base, seg)
    if wave:
        accepted, levels = _wave_commit_accept(cand, ranks, cont)
    else:
        with jax.named_scope("txn_rows"):
            m = txn_fold_overlap(_overlap_rows(*ranks), seg)
        accepted, levels = _block_accept(cand, m), None
    with jax.named_scope("txn_rows"):
        return txn_spread(accepted, seg), (
            None if levels is None else txn_spread(levels, seg))


def rebase(state: ConflictState, delta: jax.Array) -> ConflictState:
    """Shift all relative versions down by delta (host rebases its offset).

    Versions below delta are expired by construction (host only rebases to
    the window floor) — clamp them to the sentinel instead of underflowing;
    this also makes a saturated delta (huge version jump) behave correctly.
    """
    v = jnp.where(state.versions < delta, NEG_VERSION, state.versions - delta)
    return state._replace(
        versions=v, oldest=jnp.maximum(state.oldest - delta, 0)
    )


# ---------------------------------------------------------------------------
# Window history: two-level base + delta
# ---------------------------------------------------------------------------
#
# VERDICT r4 item 2: one flat step function rebuilds sparse_table(versions)
# — O(C·log C) HBM traffic at C=262k — inside EVERY batch of a scanned
# window. The two-level design amortizes it:
#
# - `base`: the bulk history, FROZEN between merges, with its sparse table
#   carried alongside (built once per merge, not per batch).
# - `delta`: a small step function (capacity Cd ~ one batch's worst-case
#   paint) holding only the writes since the last merge. Per-batch work —
#   the delta RMQ build and the paint — touches Cd elements, not C.
# - History query = max(base range-max via the PREBUILT table, delta
#   range-max via a per-batch table over Cd).
# - When the next batch's worst-case paint wouldn't fit the delta, the
#   delta is folded into the base (pointwise-max merge of two step
#   functions over their union boundary set) and the base table rebuilt,
#   all inside the same compiled program (lax.cond). A merge searches the
#   delta's Cd rows into the base and nothing else; over the base it makes
#   a constant number of STREAMING passes (a histogram, prefix sums, one
#   shift pass a binary digit, one scatter of Cd rows: _merge_delta,
#   _dedup_compact). On the chip, Cd = 8,194 holding 6,500 boundaries:
#   4.4 / 4.9 / 5.6 ms with the table's rebuild at C = 1<<17 / 1<<18 /
#   1<<19, of which the search is 2.5 (my chip run, PR 41; until then
#   three searches and a dozen gathers a BASE row: 75 / 162 / 316 ms).
#   HistState.merges counts them; the engine serves it as `hist_merges`.
#
# Freezing base between merges is sound: base versions only become STALE
# (≤ the advancing floor), and the conflict test `newest > read_version`
# with read_version ≥ floor (non-TOO_OLD txns) is unaffected by stale
# segments; expired segments are GC'd at the next merge.


class HistState(NamedTuple):
    """Two-level device history: frozen base + its RMQ table + live delta."""

    base: ConflictState
    base_st: jax.Array  # sparse table over base.versions [L, C]
    delta: ConflictState  # capacity Cd; oldest = the LIVE window floor
    merges: jax.Array  # int32 — merges since boot (_maybe_merge, advance_hist)


def init_hist(capacity: int, width: int, min_key,
              delta_capacity: int) -> HistState:
    base = init_state(capacity, width, min_key)
    return HistState(
        base=base,
        base_st=sparse_table(base.versions),
        delta=init_state(delta_capacity, width, min_key),
        merges=jnp.int32(0),
    )


def _reset_delta(delta: ConflictState, floor: jax.Array) -> ConflictState:
    """Empty delta after a merge; keys[0] (the keyspace minimum boundary)
    is invariant under paint, so reuse it. Overflow stays sticky (host
    clears after reacting)."""
    keys = jnp.full_like(delta.keys, INT32_MAX).at[0].set(delta.keys[0])
    return ConflictState(
        keys=keys,
        versions=jnp.full_like(delta.versions, NEG_VERSION),
        n_used=jnp.int32(1),
        oldest=floor,
        overflow=delta.overflow,
    )


def _merge_delta(base: ConflictState, delta: ConflictState,
                 floor: jax.Array) -> ConflictState:
    """Fold the delta into the base: pointwise max of the two step
    functions over the union boundary set, then GC (≤ floor) + compact.
    Max is exact because delta writes postdate every base write they
    cover.

    Costs what the delta costs plus a constant number of streaming passes
    over the base, as _dict_insert does next door. ONE search is left, the
    delta's Cd rows into the base (``cross_d``); everything per base row
    comes out of it by a histogram and a prefix sum:

    - delta row j lands at slot j + cross_d[j] ('right' puts a base row
      before an equal delta row, so keep-last dedup keeps the delta's);
      base row r lands at r + (delta rows with cross_d <= r), the running
      count of a histogram of cross_d, by _stream_shift, one pass a binary
      digit of that count; the delta's rows fill the holes by one sorted,
      unique scatter.
    - The delta's version over base row r is that of the last delta row
      before it: the running sum of the delta's version STEPS added into
      the same bins (int32 sums telescope exactly, wrap or not), started
      at the delta's first version. For a base row whose key EQUALS a delta
      key this is the delta segment one before the one that covers it;
      that base row is the duplicate keep-last dedup drops, so its version
      is never read (_dedup_compact: not keep1, and prev_v passes over it).
    - The base's version under delta row j is one gather of Cd rows."""
    c = base.keys.shape[0]
    cd = delta.keys.shape[0]
    # The fingerprint search (both operands are step-function key arrays).
    cross_d = searchsorted_words_fp(
        base.keys, delta.keys, side="right")  # [Cd]
    pos_d = jnp.arange(cd, dtype=jnp.int32) + cross_d

    def gc(v, keys):
        is_inf = jnp.all(keys == INT32_MAX, axis=-1)
        return jnp.where((v <= floor) | is_inf, NEG_VERSION, v)

    dv = delta.versions
    v_d = gc(jnp.maximum(base.versions[jnp.maximum(cross_d - 1, 0)], dv),
             delta.keys)

    def running(x):
        """Over base row r, the sum of x[j] for the delta rows j before
        it (cross_d[j] <= r). A +inf delta row's cross_d is C: outside
        the bins, behind every base row, and it lands at the very top."""
        return jnp.cumsum(jnp.zeros((c,), jnp.int32).at[cross_d].add(
            x, mode="drop", indices_are_sorted=True))

    up = running(1)
    dv_b = dv[0] + running(dv - jnp.concatenate([dv[:1], dv[:-1]]))
    v_b = gc(jnp.maximum(base.versions, dv_b), base.keys)

    def grown(x):
        return jnp.concatenate([x, jnp.zeros((cd,) + x.shape[1:], x.dtype)])

    cols, up_n = (grown(base.keys), grown(v_b)), grown(up)
    m = up[-1]
    for b in reversed(range(cd.bit_length())):
        k = 1 << b
        cols, up_n = jax.lax.cond(
            m >= k,
            functools.partial(_stream_shift, k=k, up=True),
            lambda cols, up_n: (cols, up_n), cols, up_n,
        )
    scatter = dict(mode="drop", indices_are_sorted=True, unique_indices=True)
    skeys = cols[0].at[pos_d].set(delta.keys, **scatter)
    v = cols[1].at[pos_d].set(v_d, **scatter)

    fkeys, fv, n_used, overflow = _dedup_compact(
        skeys, v, c, base.overflow | delta.overflow
    )
    return ConflictState(
        keys=fkeys, versions=fv, n_used=n_used, oldest=floor,
        overflow=overflow,
    )


@jax.named_scope("hist_merge")
def _maybe_merge(hist: HistState, demand: jax.Array,
                 floor: jax.Array) -> HistState:
    """Fold delta into base when `demand` more boundary slots wouldn't
    fit, OR when enough base segments have expired that the merge's GC
    reclaims meaningful capacity (the frozen base never GCs on its own —
    without this, headroom would stay pinned after the MVCC floor slides
    past old history, starving the resolver fail-safe's release check).
    The sparse-table rebuild rides inside the taken branch only."""
    base, _, delta, _ = hist
    cd = delta.keys.shape[0]
    c = base.keys.shape[0]

    reclaimable = jnp.sum(
        ((base.versions <= floor) & (base.versions > NEG_VERSION))
        .astype(jnp.int32)
    )

    def do_merge(h):
        nb = _merge_delta(h.base, h.delta, floor)
        return HistState(nb, sparse_table(nb.versions),
                         _reset_delta(h.delta, floor), h.merges + 1)

    need = (delta.n_used + demand > cd) | (reclaimable >= max(c // 8, 1))
    return jax.lax.cond(need, do_merge, lambda h: h, hist)


@jax.named_scope("hist_merge")
def advance_hist(hist: HistState, commit_version: jax.Array,
                 new_oldest: jax.Array) -> HistState:
    """GC-only step for the hist engine: advance the floor AND force a
    merge so expired base segments compact out — this is what lets the
    resolver fail-safe drain (headroom must recover as the window slides;
    the lazy base would otherwise hold expired segments until the next
    organic merge)."""
    floor = jnp.maximum(hist.delta.oldest, new_oldest)
    nb = _merge_delta(hist.base, hist.delta, floor)
    return HistState(nb, sparse_table(nb.versions),
                     _reset_delta(hist.delta, floor), hist.merges + 1)


# ---------------------------------------------------------------------------
# Rank-space batch helpers: the host packer emits every endpoint as an
# int32 rank (RankBatch below), so emptiness and liveness are scalar
# compares and the loser report leaves the device as a bitset.
# ---------------------------------------------------------------------------


@jax.named_scope("history_probe")
def too_old_mask_packed(
    state: ConflictState, pb: RankBatch, new_oldest: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """(floor, too_old[B]) in rank space (emptiness is a scalar int32
    compare). The window floor advances BEFORE resolution (reference:
    Resolver sets ConflictSet::oldestVersion from the request, then detects
    conflicts) and never regresses — a caller passing a regressed
    new_oldest must not reopen a window whose writes were GC'd. Write-only
    transactions are never too old."""
    has_reads = jnp.any(pb.read_mask & (pb.read_begin < pb.read_end), axis=1)
    floor = jnp.maximum(state.oldest, new_oldest)
    too_old = pb.txn_mask & has_reads & (pb.read_version < floor)
    return floor, too_old


@jax.named_scope("endpoint_ranks")
def endpoint_ranks_live_packed(pb: RankBatch) -> tuple[jax.Array, ...]:
    """(rb, re, read_live, wb, we, write_live): the shared precursor of
    every acceptance path, with no device sort: the host packer already
    emitted rank-space intervals (order-isomorphic with exact tie
    structure), so this is just the liveness masks (slot populated AND
    range non-empty)."""
    read_live = pb.read_mask & (pb.read_begin < pb.read_end)
    write_live = pb.write_mask & (pb.write_begin < pb.write_end)
    return (pb.read_begin, pb.read_end, read_live,
            pb.write_begin, pb.write_end, write_live)


@jax.named_scope("verdicts")
def pack_loser_mask(losers: jax.Array) -> jax.Array:
    """bool [B, R] -> uint32 [B] bitset (bit c = coalesced read slot c
    lost) when R <= 32 — an 8x cut of the report path's device→host
    transfer; wider R (no production config) stays bool."""
    b, r = losers.shape
    if r > 32:
        return losers
    lanes = jnp.arange(r, dtype=jnp.uint32)
    return (losers.astype(jnp.uint32) << lanes[None, :]).sum(
        axis=1, dtype=jnp.uint32
    )


def rebase_hist(hist, delta_v):
    """rebase over either history design. The window history's base
    versions shift, so its prebuilt RMQ table must follow."""
    if isinstance(hist, HistState):
        base = rebase(hist.base, delta_v)
        return HistState(base, sparse_table(base.versions),
                         rebase(hist.delta, delta_v), hist.merges)
    return rebase(hist, delta_v)


def _rows_in_use(n_used, frozen=None):
    """int32, the boundary slots a history uses, its levels summed: off its
    ``n_used`` leaves (one for the plain history, base and delta for the
    window history). Leaves with a leading shard axis (the mesh engine's,
    parallel/sharded_resolver.py) give one count a shard.

    ``frozen`` is the window history's (base.versions, live floor), and
    the FIRST of ``n_used`` then its base's: the base is frozen between
    merges, so it holds rows that expired since the last one, and the
    next merge drops them before anything can overflow. They are no use
    of the capacity: the base counts as that merge would leave it alone,
    one slot where its versions, clamped at the floor, change (a merged
    step function changes only where the clamped base or the delta does,
    so this plus the delta's rows bounds what any merge keeps)."""
    used = [jnp.asarray(u, jnp.int32) for u in n_used]
    if frozen is not None:
        v, floor = frozen
        floor = jnp.asarray(floor)
        v = jnp.where(v <= floor.reshape(floor.shape + (1,)), NEG_VERSION, v)
        steps = 1 + jnp.sum((v[..., 1:] != v[..., :-1]).astype(jnp.int32),
                            axis=-1)
        used[0] = jnp.minimum(used[0], steps)
    return sum(used)


_rows_in_use_jit = jax.jit(_rows_in_use)


@jax.jit
def _capacity_reading_jit(n_used, overflow, merges, frozen=None):
    """int32 [3] off a history's ``n_used`` / ``overflow`` leaves (one of
    each for the plain history, base and delta for the window history)
    and the window history's ``merges`` (0 for the plain one): boundary
    slots in use (_rows_in_use, which ``frozen`` is for), whether any
    overflow flag is up, and the merges since boot. The leaves are read,
    not donated, and the result is no part of the state: enqueued behind
    a batch's last dispatch it holds what THAT batch left, whatever is
    enqueued after it (TPUConflictSet.resolve_async).

    Where the leaves carry a shard axis the slots in use are the FULLEST
    shard's (the fail-safe engages where the first shard would overflow)
    and the merges are summed over the shards. With no such axis this is
    the program it always was."""
    over = functools.reduce(jnp.logical_or, [jnp.any(o) for o in overflow])
    return jnp.stack([jnp.max(_rows_in_use(n_used, frozen)),
                      over.astype(jnp.int32),
                      jnp.sum(jnp.asarray(merges, jnp.int32))])


# ---------------------------------------------------------------------------
# Resident kernel: the endpoint-key dictionary and the MVCC history persist
# in device memory across dispatches. The history is stored in RANK SPACE — a width-1
# ConflictState/HistState whose "key" rows are int32 ranks into the
# resident dictionary (INT32_MAX = the +inf sentinel, exactly the role the
# all-inf row plays at full width) — so ALL of the step-function machinery
# above (_paint_tail, _dedup_compact, _merge_delta, _maybe_merge, rebase,
# advance_hist) is reused verbatim at W=1, and per-dispatch device work
# never touches a full-width key except the (usually tiny) delta merge.
# ---------------------------------------------------------------------------


class RankBatch(NamedTuple):
    """One padded resolver batch in RESIDENT rank space: every endpoint is
    an int32 rank into the resident dictionary (host-computed against the
    post-merge mirror — see conflict_set._ResidentMirror), INT32_MAX for
    masked/padding slots.

    ``paint_src`` is the HOST-precomputed stable argsort of the write
    endpoints [wb..., we...] — the resident paint's sort permutation. It
    cannot depend on device-side acceptance because rejected writes ride
    the merge as delta-0 boundaries (version-preserving no-ops the
    compaction provably erases), so the device paint is pure gathers: the
    27-MB-modeled per-batch sort network disappears. Rank clipping (the
    mesh shard clamp) is monotone, so the same permutation stays sorted
    for every shard's clipped view."""

    read_begin: jax.Array  # int32 [B, R] resident ranks
    read_end: jax.Array  # int32 [B, R]
    read_mask: jax.Array  # bool [B, R]
    write_begin: jax.Array  # int32 [B, Q]
    write_end: jax.Array  # int32 [B, Q]
    write_mask: jax.Array  # bool [B, Q]
    read_version: jax.Array  # int32 [B] (relative)
    txn_mask: jax.Array  # bool [B]
    paint_src: jax.Array  # int32 [2·B·Q] stable argsort of write endpoints
    cont: jax.Array | None = None  # as BatchTensors.cont


class ResidentBatch(NamedTuple):
    """A RankBatch plus its dictionary DELTA: the sorted never-before-seen
    endpoint keys of this dispatch, +inf padded to the engine's static
    delta width, and beside each its ``cross`` rank — how many resident
    keys sort below it — which the host mirror computes anyway to splice
    the key into its own sorted view, so the device searches nothing. On
    the window path the ranks carry a leading [k] scan axis while the
    delta does NOT — one merge serves the whole window."""

    delta_keys: jax.Array  # int32 [M, W] sorted new keys, +inf padded
    delta_cross: jax.Array  # int32 [M] resident keys below; D + 1 padded
    ranks: RankBatch


class ResState(NamedTuple):
    """Device-resident dictionary + rank-space history (+ shard bounds).

    ``shard_lo``/``shard_hi`` are the mesh engine's per-shard keyspace
    bounds AS RANKS (hi = INT32_MAX for the last shard's +inf) — kept in
    device state, not per-batch arguments, because a dictionary insert
    shifts them exactly like it shifts history ranks. Single-chip engines
    carry the degenerate [1] bounds (0, INT32_MAX) and never read them."""

    dict_keys: jax.Array  # int32 [D + 1, W] sorted resident keys, +inf padded
    n_keys: jax.Array  # int32 — live resident key count
    hist: ConflictState | HistState  # width-1 rank-space history
    shard_lo: jax.Array  # int32 [S] rank bounds (mesh); [1] dummy otherwise
    shard_hi: jax.Array


_RANK_MIN = np.zeros(1, np.int32)  # width-1 "min key": rank 0 (the min key)


def init_res(
    dict_rows, dict_capacity: int, capacity: int,
    delta_capacity: int | None = None,
    shard_lo=None, shard_hi=None,
) -> ResState:
    """dict_rows: host-built initial dictionary [n0, W] (sorted; row 0 is
    the packed b""). delta_capacity selects the two-level window history
    (None = flat). shard_lo/hi: initial rank bounds ([1] defaults)."""
    n0, w = dict_rows.shape
    dict_keys = jnp.full((dict_capacity + 1, w), INT32_MAX, jnp.int32)
    dict_keys = dict_keys.at[:n0].set(jnp.asarray(dict_rows, jnp.int32))
    if delta_capacity is None:
        hist: ConflictState | HistState = init_state(capacity, 1, _RANK_MIN)
    else:
        hist = init_hist(capacity, 1, _RANK_MIN, delta_capacity)
    if shard_lo is None:
        shard_lo = np.zeros(1, np.int32)
        shard_hi = np.full(1, INT32_MAX, np.int32)
    return ResState(
        dict_keys=dict_keys,
        n_keys=jnp.int32(n0),
        hist=hist,
        shard_lo=jnp.asarray(shard_lo, jnp.int32),
        shard_hi=jnp.asarray(shard_hi, jnp.int32),
    )


def _dict_insert(dict_keys, n_keys, delta_keys, cross):
    """Merge M sorted-unique NEW keys into the resident dictionary.

    ``cross`` [M] is each delta row's count of resident keys below it,
    shipped by the host (conflict_set._ResidentMirror.insert_new): real
    delta keys are distinct from every resident key, so 'left' and 'right'
    agree; a +inf padding row carries D + 1 (all d1 rows), which puts it
    outside the histogram's bins and its merge position past the output
    window, so only real rows land.

    Returns (new_dict_keys, new_n_keys, shift) where shift[r] = how many
    inserted keys precede old rank r — the rank-rebase table: an existing
    rank r becomes r + shift[r]. The host guarantees fit (n_keys + m <=
    capacity), and real delta rows are disjoint from resident keys by
    construction.

    Costs what the delta costs and searches nothing: the tables over the
    dictionary's rows are a histogram and a prefix sum, and the rows move
    by streaming shifts — nothing searches or gathers once per dictionary
    row, nor once per delta slot."""
    d1, w = dict_keys.shape
    m_cap = delta_keys.shape[0]
    pos_d = jnp.arange(m_cap, dtype=jnp.int32) + cross
    m = jnp.sum((cross < d1).astype(jnp.int32))
    # A delta key is strictly below resident row r exactly when its
    # cross <= r, so shift is the running count of a histogram of cross.
    # The dict's +inf padding rows read exactly m.
    shift = jnp.cumsum(
        jnp.zeros(d1, jnp.int32).at[cross].add(
            1, mode="drop", indices_are_sorted=True
        )
    )
    # Old row r moves up to r + shift[r]. shift is nondecreasing, so the
    # rows keep their order after every binary digit of it, high to low,
    # and no two ever meet: each stage is one streaming pass that moves
    # the rows whose remaining shift has that bit up by 2**b, the shift
    # riding along as a last column. A vacated slot is zeroed so it stays
    # put; those holes end exactly at the delta's merge positions pos_d.
    # Only the bits of m run.
    rows = jnp.concatenate([dict_keys, shift[:, None]], axis=1)

    def stage(rows, k):
        below = jnp.concatenate(
            [jnp.zeros((k, w + 1), jnp.int32), rows[: d1 - k]]
        )
        arrives = (below[:, w:] & k) != 0
        leaves = (rows[:, w:] & k) != 0
        return jnp.where(arrives, below, jnp.where(leaves, 0, rows))

    for b in reversed(range(min(m_cap, d1 - 1).bit_length())):
        k = 1 << b
        rows = jax.lax.cond(
            m >= k, functools.partial(stage, k=k), lambda rows: rows, rows
        )
    out = rows[:, :w].at[pos_d].set(
        delta_keys, mode="drop", indices_are_sorted=True, unique_indices=True
    )
    return out, n_keys + m, shift


def _shift_rank_rows(keys: jax.Array, shift: jax.Array) -> jax.Array:
    """Rank-rebase a width-1 history key array ([..., C, 1]): each live
    rank r becomes r + shift[r]; the INT32_MAX sentinel is invariant."""
    r = keys[..., 0]
    d1 = shift.shape[0]
    shifted = r + shift[jnp.clip(r, 0, d1 - 1)]
    return jnp.where(r == INT32_MAX, r, shifted)[..., None]


def _shift_rank_vec(v: jax.Array, shift: jax.Array) -> jax.Array:
    """Rank-rebase a bare rank vector (shard bounds)."""
    d1 = shift.shape[0]
    shifted = v + shift[jnp.clip(v, 0, d1 - 1)]
    return jnp.where(v == INT32_MAX, v, shifted)


def _shift_hist(hist, shift):
    if isinstance(hist, HistState):
        return hist._replace(  # versions untouched: the RMQ table survives
            base=hist.base._replace(
                keys=_shift_rank_rows(hist.base.keys, shift)),
            delta=hist.delta._replace(
                keys=_shift_rank_rows(hist.delta.keys, shift)),
        )
    return hist._replace(keys=_shift_rank_rows(hist.keys, shift))


@jax.named_scope("dict_insert")
def apply_delta(res: ResState, delta_keys: jax.Array,
                delta_cross: jax.Array) -> ResState:
    """Fold this dispatch's key delta into the resident state: insert the
    new keys into the dictionary at the ranks the host shipped with them
    (``delta_cross``, see ResidentBatch) and rank-rebase the history +
    shard bounds past the inserted positions. The empty-delta steady state
    (high hit rate; every row's cross the padding D + 1) skips the whole
    merge via lax.cond."""
    any_new = jnp.any(delta_cross < res.dict_keys.shape[0])

    def do(res):
        nd, nn, shift = _dict_insert(res.dict_keys, res.n_keys, delta_keys,
                                     delta_cross)
        return ResState(
            dict_keys=nd,
            n_keys=nn,
            hist=_shift_hist(res.hist, shift),
            shard_lo=_shift_rank_vec(res.shard_lo, shift),
            shard_hi=_shift_rank_vec(res.shard_hi, shift),
        )

    return jax.lax.cond(any_new, do, lambda r: r, res)


def _dict_evict(dict_keys, n_keys, evict_ranks):
    """Remove E sorted-unique resident ranks from the dictionary — the
    exact inverse of _dict_insert (the tiered engine's DEMOTION delta).

    evict_ranks: int32 [E] strictly increasing ranks, INT32_MAX padded.
    Returns (new_dict_keys, new_n_keys, shift) where shift[r] <= 0 is the
    rank-rebase table for SURVIVING ranks (r becomes r + shift[r]). The
    host guarantees no evicted rank is referenced by device history or
    shard bounds (exact-liveness selection), so the off-by-one a demoted
    rank itself would take through the table is never observed. Same
    scatter-free merge-path construction as _paint_tail: kept row j
    reads source j + t where t = |{i : e_i - i <= j}| (e_i - i is
    nondecreasing for strictly increasing e_i)."""
    d1, _w = dict_keys.shape
    e_cap = evict_ranks.shape[0]
    real = evict_ranks != INT32_MAX
    n_ev = jnp.sum(real.astype(jnp.int32))
    i = jnp.arange(e_cap, dtype=jnp.int32)
    adj = jnp.where(real, evict_ranks - i, INT32_MAX)
    j = jnp.arange(d1, dtype=jnp.int32)
    t = jnp.searchsorted(adj, j, side="right").astype(jnp.int32)
    out = dict_keys[jnp.clip(j + t, 0, d1 - 1)]
    new_n = n_keys - n_ev
    out = jnp.where((j < new_n)[:, None], out, INT32_MAX)
    # Surviving rank r has no evicted rank equal to it, so the <= count
    # IS the strictly-below count — negate it for the shared shifters.
    shift = -jnp.searchsorted(evict_ranks, j, side="right").astype(jnp.int32)
    return out, new_n, shift


@jax.named_scope("dict_evict")
def apply_evict(res: ResState, evict_ranks: jax.Array) -> ResState:
    """Fold a demotion delta into the resident state: remove the evicted
    ranks from the dictionary and rank-rebase the history + shard bounds
    DOWN past the removed positions — the mirror image of apply_delta.
    The empty-delta case (no victims survived selection) skips the
    compaction via lax.cond, like apply_delta's steady state."""
    any_ev = jnp.any(evict_ranks != INT32_MAX)

    def do(res):
        nd, nn, shift = _dict_evict(res.dict_keys, res.n_keys, evict_ranks)
        return ResState(
            dict_keys=nd,
            n_keys=nn,
            hist=_shift_hist(res.hist, shift),
            shard_lo=_shift_rank_vec(res.shard_lo, shift),
            shard_hi=_shift_rank_vec(res.shard_hi, shift),
        )

    return jax.lax.cond(any_ev, do, lambda r: r, res)


@jax.named_scope("dict_remap")
def apply_dict_remap(res: ResState, new_dict, new_n, remap) -> ResState:
    """Full-repack tail: swap in the host-rebuilt dictionary and remap
    every device-held rank through ``remap`` (old rank -> new rank; exact
    for every LIVE history rank — the host includes all live keys in the
    new dictionary, see conflict_set._execute_repack)."""

    def rr(keys):
        r = keys[..., 0]
        m = remap[jnp.clip(r, 0, remap.shape[0] - 1)]
        return jnp.where(r == INT32_MAX, r, m)[..., None]

    hist = res.hist
    if isinstance(hist, HistState):
        hist = hist._replace(
            base=hist.base._replace(keys=rr(hist.base.keys)),
            delta=hist.delta._replace(keys=rr(hist.delta.keys)),
        )
    else:
        hist = hist._replace(keys=rr(hist.keys))
    rv = lambda v: jnp.where(  # noqa: E731 — tiny local lambda
        v == INT32_MAX, v, remap[jnp.clip(v, 0, remap.shape[0] - 1)]
    )
    return ResState(
        dict_keys=jnp.asarray(new_dict, jnp.int32),
        n_keys=jnp.asarray(new_n, jnp.int32),
        hist=hist,
        shard_lo=rv(res.shard_lo),
        shard_hi=rv(res.shard_hi),
    )


def clip_ranks(rbk: RankBatch, lo, hi) -> RankBatch:
    """Restrict every range to the shard's rank interval [lo, hi): the
    device-side analogue of the reference CommitProxy's per-resolver
    conflict-range split (CommitProxyServer.actor.cpp routes ranges to
    resolvers by keyRange shard). read_version/txn_mask are untouched
    (TOO_OLD is judged on the unclipped batch so all shards agree). Scalar
    int32 compares — out-of-shard ranges fall out of their masks via rb' >= re'. Both endpoints take the SAME
    two-sided clamp: one monotone map over all endpoints, so the host's
    paint permutation (RankBatch.paint_src, computed on unclipped ranks)
    stays sorted for the clipped view — a one-sided max/min pair would
    order a beyond-shard begin after a clamped +inf end and corrupt the
    gather-only paint."""
    clamp = lambda v: jnp.clip(v, lo, hi)  # noqa: E731
    rb = clamp(rbk.read_begin)
    re_ = clamp(rbk.read_end)
    wb = clamp(rbk.write_begin)
    we = clamp(rbk.write_end)
    return rbk._replace(
        read_begin=rb, read_end=re_, read_mask=rbk.read_mask & (rb < re_),
        write_begin=wb, write_end=we, write_mask=rbk.write_mask & (wb < we),
    )


def _rank_probe(keys: jax.Array, q: jax.Array, side: str) -> jax.Array:
    """searchsorted of bare int32 ranks into a width-1 history key array —
    the resident probe: one binary search of 4-byte gathers, no
    fingerprint cascade needed (ranks ARE the fingerprint)."""
    return searchsorted_words(keys, q[..., None], side=side)


@jax.named_scope("history_probe")
def _history_conflict_ranges_res(state: ConflictState, rbk: RankBatch) -> jax.Array:
    """bool [B, R]: read range slot overlaps a historical write newer than
    rv — the per-range form the conflicting-keys report path needs (which
    read ranges LOST, reference: conflictingKRIndices). Per-slot probes
    (the host already deduped the rank space; a probe step gathers 4
    bytes, so per-slot beats the probe-per-unique-key indirection)."""
    b, r = rbk.read_begin.shape
    lo = _rank_probe(state.keys, rbk.read_begin.reshape(-1), "right") - 1
    hi = _rank_probe(state.keys, rbk.read_end.reshape(-1), "left")
    st = sparse_table(state.versions)
    newest = range_max(
        st, jnp.maximum(lo, 0), hi, NEG_VERSION
    ).reshape(b, r)
    live = rbk.read_mask & (rbk.read_begin < rbk.read_end)
    return live & (newest > rbk.read_version[:, None])


def _history_conflicts_res(state: ConflictState, rbk: RankBatch) -> jax.Array:
    return jnp.any(_history_conflict_ranges_res(state, rbk), axis=1)


@jax.named_scope("history_probe")
def _history_conflict_ranges_hist_res(
    base: ConflictState, base_st: jax.Array, delta: ConflictState,
    rbk: RankBatch,
) -> jax.Array:
    b, r = rbk.read_begin.shape
    qb = rbk.read_begin.reshape(-1)
    qe = rbk.read_end.reshape(-1)
    newest_b = range_max(
        base_st,
        jnp.maximum(_rank_probe(base.keys, qb, "right") - 1, 0),
        _rank_probe(base.keys, qe, "left"),
        NEG_VERSION,
    )
    lo_d = jnp.maximum(_rank_probe(delta.keys, qb, "right") - 1, 0)
    hi_d = _rank_probe(delta.keys, qe, "left")
    dt = sparse_table(delta.versions)
    newest_d = range_max(dt, lo_d, hi_d, NEG_VERSION)
    newest = jnp.maximum(newest_b, newest_d).reshape(b, r)
    live = rbk.read_mask & (rbk.read_begin < rbk.read_end)
    return live & (newest > rbk.read_version[:, None])


def _history_conflicts_hist_res(hist: HistState, rbk: RankBatch) -> jax.Array:
    return jnp.any(
        _history_conflict_ranges_hist_res(
            hist.base, hist.base_st, hist.delta, rbk
        ),
        axis=1,
    )


@jax.named_scope("paint_compact")
def _paint_and_compact_res(
    state: ConflictState,
    rbk: RankBatch,
    accepted: jax.Array,
    commit_version: jax.Array,
    new_oldest: jax.Array,
) -> ConflictState:
    """Fold accepted writes into the step function WITHOUT re-sorting the
    whole history, and without a device endpoint sort: the history rows are
    already sorted, the batch's 2·B·Q new endpoints arrive with their sort
    permutation, and the two sorted sequences are interleaved by rank
    arithmetic (_paint_tail: the merge-path construction, each element's
    output slot its own index plus its cross-rank in the other sequence,
    history winning ties; at the delta's size, n = 16,386, its search and
    gathers once a slot are what is left of ROADMAP A5), the surviving
    boundaries compacted to the front by streaming shifts (_dedup_compact,
    whose docstring says what the chip charges for each kind of pass).

    The host ships the stable argsort of the write endpoints
    (rbk.paint_src) — legal because the permutation must not depend on
    device-side acceptance: a rejected (or shard-clipped-empty) write's
    endpoints enter the merge with coverage delta 0 and their containing
    segment's version, i.e. boundaries that do not change the step
    function, which _dedup_compact erases exactly like the old +inf
    parking did. The paint is therefore pure gathers over rank rows; full
    keys never materialize again until a repack."""
    b, q = rbk.write_begin.shape
    e2 = b * q
    valid = (
        accepted[:, None] & rbk.write_mask & (rbk.write_begin < rbk.write_end)
    )
    wr = rbk.write_begin.reshape(e2)
    er = rbk.write_end.reshape(e2)
    new_ranks = jnp.concatenate([wr, er])
    new_delta = jnp.concatenate(
        [valid.reshape(e2).astype(jnp.int32), -valid.reshape(e2).astype(jnp.int32)]
    )
    cross_rank = _rank_probe(state.keys, new_ranks, "right")
    seg = cross_rank - 1
    new_oldv = state.versions[jnp.maximum(seg, 0)]
    sidx = rbk.paint_src
    return _paint_tail(
        state,
        new_ranks[sidx][:, None],
        new_delta[sidx],
        new_oldv[sidx],
        cross_rank[sidx],
        commit_version,
        new_oldest,
    )


def _resolve_core_res(hist, rbk: RankBatch, commit_version, new_oldest,
                      report: bool = False, wave: bool = False,
                      clip=None, combine=None, accept=None):
    """Shared resident resolve body over either history design. Returns
    (verdicts[, levels][, losers], new_hist).

    The three hooks are the mesh's (parallel/sharded_resolver.py), where
    `hist` is ONE shard's and the batch is replicated; one chip hands in
    none and traces what it always traced. `clip(rbk)` is the batch cut to
    the shard's slice of the rank space: the shard probes and paints that,
    and merges on ITS demand, so whether the delta is folded in differs a
    shard, which is why no hook may run inside _maybe_merge's branches.
    `combine(mask)` ORs a shard's history bits over the shards, before
    acceptance, so every shard paints what ONE history would accept.
    `accept(base, local)` replaces the acceptance where it needs the
    shards' clipped graphs (the wave exchange); what it returns beside
    `accepted` rides out in the place of the levels, untouched."""
    two_level = isinstance(hist, HistState)
    local = rbk if clip is None else clip(rbk)
    if two_level:
        floor, too_old = too_old_mask_packed(hist.delta, rbk, new_oldest)
        with jax.named_scope("hist_merge"):
            demand = 2 * jnp.sum(
                (local.write_mask
                 & (local.write_begin < local.write_end)).astype(jnp.int32)
            )
        hist = _maybe_merge(hist, demand, floor)
        base_h, base_st, delta, _ = hist
        hist_mask = _history_conflict_ranges_hist_res(
            base_h, base_st, delta, local
        )
    else:
        floor, too_old = too_old_mask_packed(hist, rbk, new_oldest)
        hist_mask = _history_conflict_ranges_res(hist, local)
    with jax.named_scope("history_probe"):
        hist_conflict = jnp.any(hist_mask, axis=1)
    if combine is not None:
        hist_conflict = combine(hist_conflict)
    with jax.named_scope("history_probe"):
        base = rbk.txn_mask & ~too_old & ~hist_conflict
    ranks = endpoint_ranks_live_packed(rbk)
    if accept is None:
        accepted, levels = _accept_or_schedule(base, ranks, wave, rbk.cont)
    else:
        accepted, levels = accept(base, local)
    verdicts = assemble_verdicts(too_old, rbk.txn_mask, accepted)
    if two_level:
        delta = _paint_and_compact_res(
            delta, local, accepted, commit_version, floor
        )
        new_hist: ConflictState | HistState = hist._replace(delta=delta)
    else:
        new_hist = _paint_and_compact_res(
            hist, local, accepted, commit_version, floor
        )
    out = (verdicts, levels) if wave else (verdicts,)
    if report:
        if combine is not None:
            hist_mask = combine(hist_mask)
        losers = loser_range_mask(hist_mask, ranks, accepted, verdicts)
        return (*out, pack_loser_mask(losers), new_hist)
    return (*out, new_hist)


def resolve_batch_res(res: ResState, rb: ResidentBatch, commit_version,
                      new_oldest, report: bool = False, wave: bool = False):
    """Resolve one batch and fold its accepted writes into the history:
    delta merge + rank rebase, then the rank-space resolve core. Mirrors
    the reference call sequence ConflictBatch::detectConflicts →
    combineWriteConflictRanges → SkipList::addConflictRanges, as one
    compiled program.

    Returns (verdicts int8 [B], new_res) — with `report` (a static Python
    flag; each value compiles its own program), (verdicts, loser bitset
    uint32 [B], new_res); `wave` (static) switches intra-batch acceptance
    to the wave-commit schedule and inserts the int32 [B] wave levels right
    after the verdicts in every return shape."""
    res = apply_delta(res, rb.delta_keys, rb.delta_cross)
    out = _resolve_core_res(res.hist, rb.ranks, commit_version, new_oldest,
                            report=report, wave=wave)
    return (*out[:-1], res._replace(hist=out[-1]))


def resolve_many_res(res: ResState, rb: ResidentBatch, commit_versions,
                     new_oldests, wave: bool = False):
    """Window path: ONE delta merge + rank rebase for the whole window
    (the delta carries no scan axis), then a pure rank-space scan with no
    per-step dictionary work at all."""
    res = apply_delta(res, rb.delta_keys, rb.delta_cross)

    def body(h, xs):
        rbk, cv, old = xs
        out = _resolve_core_res(h, rbk, cv, old, wave=wave)
        return out[-1], out[:-1]

    hist, stacked = jax.lax.scan(
        body, res.hist, (rb.ranks, commit_versions, new_oldests)
    )
    return (*stacked, res._replace(hist=hist))


@functools.partial(jax.jit, donate_argnums=(0,))
def _resolve_res_jit(res, rb, commit_version, new_oldest):
    return resolve_batch_res(res, rb, commit_version, new_oldest)


@functools.partial(jax.jit, donate_argnums=(0,))
def _resolve_report_res_jit(res, rb, commit_version, new_oldest):
    return resolve_batch_res(res, rb, commit_version, new_oldest, report=True)


@functools.partial(jax.jit, donate_argnums=(0,))
def _resolve_many_res_jit(res, rb, commit_versions, new_oldests):
    return resolve_many_res(res, rb, commit_versions, new_oldests)


@functools.partial(jax.jit, donate_argnums=(0,))
def _resolve_res_wave_jit(res, rb, commit_version, new_oldest):
    return resolve_batch_res(res, rb, commit_version, new_oldest, wave=True)


@functools.partial(jax.jit, donate_argnums=(0,))
def _resolve_report_res_wave_jit(res, rb, commit_version, new_oldest):
    return resolve_batch_res(res, rb, commit_version, new_oldest,
                             report=True, wave=True)


@functools.partial(jax.jit, donate_argnums=(0,))
def _resolve_many_res_wave_jit(res, rb, commit_versions, new_oldests):
    return resolve_many_res(res, rb, commit_versions, new_oldests, wave=True)


@functools.partial(jax.jit, donate_argnums=(0,))
def _rebase_res_jit(res, delta_v):
    return res._replace(hist=rebase_hist(res.hist, delta_v))


@functools.partial(jax.jit, donate_argnums=(0,))
def _advance_hist_res_jit(res, commit_version, new_oldest):
    return (
        jnp.zeros((1,), jnp.int8),
        res._replace(hist=advance_hist(res.hist, commit_version, new_oldest)),
    )


@functools.partial(jax.jit, donate_argnums=(0,))
def _repack_res_jit(res, new_dict, new_n, remap):
    return apply_dict_remap(res, new_dict, new_n, remap)


@functools.partial(jax.jit, donate_argnums=(0,))
def _evict_res_jit(res, evict_ranks):
    """Demotion delta for the tiered dictionary: drop cold ranks from the
    hot tier and rebase ranks down. Elementwise over history rows like
    _rebase_res_jit / _repack_res_jit, so the mesh engine runs it on the
    per-device state under jit unchanged (dict replicated, hist sharded)."""
    return apply_evict(res, evict_ranks)


# ---------------------------------------------------------------------------
# Two-phase wave entry points (role-level global wave commit): a sharded
# resolver deployment splits one resolve into EDGES (history gate + this
# shard's clipped predecessor bitsets; no paint) and APPLY (level the
# OR-reduced GLOBAL graph + paint the globally accepted writes). The
# commit proxy is the reduction point between the phases
# (core/wavemesh.combine_edges); every shard levels the identical graph,
# so every shard reports the identical (wave, index) schedule. The mesh
# ShardedConflictSet performs the same exchange as an on-device
# all_gather inside one program and never needs these.
# ---------------------------------------------------------------------------


def _edge_pred(base, ranks, cont):
    """Phase 1's predecessor bitsets, by ROW. With continuation rows a
    transaction is a candidate only if every row is, and its rows' edges
    are folded onto its head row; the engine then renumbers head rows as
    transactions (conflict_set._heads_to_txns), since every shard sees
    every transaction but lays it out in rows of its own."""
    if cont is not None:
        base = txn_candidates(base, txn_segments(cont))
    return wave_pred_matrix(base, ranks, cont)


def _accepted_rows(accepted, cont):
    """Phase 2's answer by TRANSACTION (the exchange's index) -> by row:
    every row of a transaction carries its verdict into the paint."""
    if cont is None:
        return accepted
    return accepted[jnp.cumsum((~cont).astype(jnp.int32)) - 1]


def wave_edges_res(res: ResState, rb: ResidentBatch, new_oldest):
    """Resident phase-1: the dictionary delta merges HERE (the host
    packed ranks against the post-merge mirror), so the returned state
    carries the merged dictionary and the apply phase must not re-merge.
    History is still unpainted."""
    res = apply_delta(res, rb.delta_keys, rb.delta_cross)
    hist = res.hist
    if isinstance(hist, HistState):
        _floor, too_old = too_old_mask_packed(hist.delta, rb.ranks, new_oldest)
        hist_conflict = _history_conflicts_hist_res(hist, rb.ranks)
    else:
        _floor, too_old = too_old_mask_packed(hist, rb.ranks, new_oldest)
        hist_conflict = _history_conflicts_res(hist, rb.ranks)
    base = rb.ranks.txn_mask & ~too_old & ~hist_conflict
    p = _edge_pred(base, endpoint_ranks_live_packed(rb.ranks),
                   rb.ranks.cont)
    return too_old, hist_conflict, p, res


def wave_apply_res(
    res: ResState, rbk: RankBatch, cand, p, commit_version, new_oldest,
):
    """Resident apply: the dictionary already merged in wave_edges_res,
    so this is pure rank-space level + paint."""
    hist = res.hist
    accepted, levels = wave_level_from_graph(cand, p)
    accepted = _accepted_rows(accepted, rbk.cont)
    if isinstance(hist, HistState):
        floor = jnp.maximum(hist.delta.oldest, new_oldest)
        demand = 2 * jnp.sum(
            (rbk.write_mask & (rbk.write_begin < rbk.write_end)).astype(
                jnp.int32
            )
        )
        hist = _maybe_merge(hist, demand, floor)
        base_h, base_st, delta, _ = hist
        delta = _paint_and_compact_res(
            delta, rbk, accepted, commit_version, floor
        )
        new_hist: ConflictState | HistState = hist._replace(delta=delta)
    else:
        floor = jnp.maximum(hist.oldest, new_oldest)
        new_hist = _paint_and_compact_res(
            hist, rbk, accepted, commit_version, floor
        )
    return levels, res._replace(hist=new_hist)


# The edge entry is donated (the delta merge replaces the state, returned
# alongside); the apply entry donates like every resolve.
_wave_edges_res_jit = jax.jit(wave_edges_res, donate_argnums=(0,))
_wave_apply_res_jit = jax.jit(wave_apply_res, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# Speculative pipelined resolve (FDB_TPU_SPEC_RESOLVE=1): the host
# dispatches window N+1 against window N's OPTIMISTICALLY painted state
# (the resolve programs above paint accepted-so-far writes in the same
# program that decides them) while N's verdicts are still in flight —
# i.e. unconfirmed by the upper layer (tlog durability, wave apply,
# ratekeeper). The kernel side of the reconcile is two kinds of program:
#
# - _snapshot_jit: fresh device buffers for the pre-window state, taken
#   right before a speculative dispatch. The resolve entry points donate
#   their state argument (argnum 0), so the ACTIVE state never
#   double-buffers; the snapshot is the explicit, depth-bounded HBM cost
#   of speculation (one state copy per in-flight window), and rolling
#   back a mis-speculated window is a host pointer swap.
# - paint-only entry points (_paint_res_jit, _paint_many_res_jit):
#   re-advance a rolled-back state with a FORCED accept mask (the
#   speculative accepts ∩ the upper layer's confirmation) — the same
#   merge/GC/paint pipeline as the resolve bodies, minus the verdict
#   decision the upper layer already overrode.
# Every younger in-flight window is re-resolved against the corrected
# history (only genuinely-conflicted txns flip): the windows' ranks live in
# per-window coordinate systems, so no probe can prove a window clean.
# ---------------------------------------------------------------------------


@jax.jit
def _snapshot_jit(tree):
    """Device copy of an arbitrary state pytree (NOT donated — the live
    state keeps executing; see the speculation ring in conflict_set)."""
    return jax.tree_util.tree_map(jnp.copy, tree)


def _paint_core_res(hist, rbk: RankBatch, accepted, commit_version,
                    new_oldest):
    if isinstance(hist, HistState):
        floor, _ = too_old_mask_packed(hist.delta, rbk, new_oldest)
        demand = 2 * jnp.sum(
            (rbk.write_mask & (rbk.write_begin < rbk.write_end)).astype(
                jnp.int32
            )
        )
        hist = _maybe_merge(hist, demand, floor)
        base_h, base_st, delta, _ = hist
        delta = _paint_and_compact_res(delta, rbk, accepted, commit_version,
                                       floor)
        return hist._replace(delta=delta)
    floor = jnp.maximum(hist.oldest, new_oldest)
    return _paint_and_compact_res(hist, rbk, accepted, commit_version, floor)


def paint_batch_res(res: ResState, rb: ResidentBatch, accepted,
                    commit_version, new_oldest) -> ResState:
    """Resident edition: the dictionary delta re-applies exactly as the
    resolve body would (a rolled-back snapshot predates this window's
    insert, so the replayed merge reproduces the original rank space)."""
    res = apply_delta(res, rb.delta_keys, rb.delta_cross)
    return res._replace(
        hist=_paint_core_res(res.hist, rb.ranks, accepted, commit_version,
                             new_oldest)
    )


def paint_many_res(res, rb, accepted, commit_versions, new_oldests):
    res = apply_delta(res, rb.delta_keys, rb.delta_cross)

    def body(h, xs):
        rbk, acc, cv, old = xs
        return _paint_core_res(h, rbk, acc, cv, old), None

    hist, _ = jax.lax.scan(
        body, res.hist, (rb.ranks, accepted, commit_versions, new_oldests)
    )
    return res._replace(hist=hist)


@functools.partial(jax.jit, donate_argnums=(0,))
def _paint_res_jit(res, rb, accepted, commit_version, new_oldest):
    return paint_batch_res(res, rb, accepted, commit_version, new_oldest)


@functools.partial(jax.jit, donate_argnums=(0,))
def _paint_many_res_jit(res, rb, accepted, commit_versions, new_oldests):
    return paint_many_res(res, rb, accepted, commit_versions, new_oldests)
