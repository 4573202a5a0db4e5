#!/usr/bin/env python3
"""Headline benchmark: resolved txns/sec on a Zipf-0.99 hot-key stream.

Mirrors the reference's mako/YCSB-A resolver stress (bindings/c/test/mako,
Zipf theta 0.99 hot-key contention): a 1M-transaction stream in 8k-txn
batches, each txn doing 2 point reads + a 50% chance of a point write
(YCSB-A read/update mix), keys drawn from a scrambled bounded-Zipf(0.99)
distribution. One commit version per batch, identical semantics on both
engines:

- TPU engine (the PRODUCTION path): each batch is a flat wire blob (the
  resolver's RPC payload format, native/keypack.cpp) driven through
  TPUConflictSet.resolve_wire_async — C packer → device tensors → jitted
  step-function kernel, dispatched asynchronously so host packing overlaps
  device compute. NOT a bespoke packer: this is the path the runtime uses.
- CPU baseline: the C++ SkipList ConflictSet (native/skiplist.cpp), the
  same algorithmic design as the reference's fdbserver/SkipList.cpp,
  driven through ctypes with all marshalling done OUTSIDE the timed loop.

It runs on the device JAX finds and names it in the record ("device":
platform, device_kind, count). Without a TPU it exits non-zero, unless the
caller set JAX_PLATFORMS=cpu in so many words; such a record says
"valid": false. A sweep or phase that raises is recorded and makes the exit
code non-zero.

Prints ONE JSON line:
  {"metric": "resolved_txns_per_sec_per_chip", "value": ..., "unit":
   "txns/s", "vs_baseline": tpu_rate / cpu_rate, ...extras}
"""

from __future__ import annotations

import argparse
import ctypes
import json
import sys
import time
import traceback
from dataclasses import dataclass

import numpy as np

BATCH = 8192
N_READS = 2  # point reads per txn (ycsb default; see MODES)
WINDOW = 64  # MVCC window in commit versions (batches)
MAX_LAG = 8  # read-version staleness in versions (<< WINDOW: no TOO_OLD)
KEY_BYTES = 12  # codec width: 8-byte keys + point-range end fits exactly
_BIAS = np.uint32(0x80000000)


@dataclass(frozen=True)
class ModeConfig:
    """One §5 benchmark configuration (reference: mako run configs)."""

    n_reads: int  # point reads per txn
    n_writes: int  # point writes per txn (all-or-none via write_frac)
    write_frac: float
    theta: float  # Zipf skew (0 = uniform)
    batch: int


MODES = {
    # YCSB-A hot-key contention: 2 reads + 50% single write, Zipf 0.99.
    "ycsb": ModeConfig(2, 1, 0.5, 0.99, BATCH),
    # mako 90/10 op mix: 9 reads + 1 write every txn, kernel only, on an
    # engine built 9 slots wide. No cell: the benchmark's mako is
    # `mako_share_g8ui` (BENCHMARK.json; benchmark/configs/
    # mako_resolver_share.json), g8ui through the served role's 8 slots.
    "mako": ModeConfig(9, 1, 1.0, 0.99, 4096),
    # TPC-C new-order shape: wide txns (12 reads, 8 writes), uniform items,
    # kernel only. No cell: the benchmark's TPC-C is `tpcc_share_mix`
    # (BENCHMARK.json; benchmark/configs/tpcc_resolver_share.json), the
    # published mix of new-order, payment and delivery through the served
    # role, true range reads and all.
    "tpcc": ModeConfig(12, 8, 1.0, 0.0, 2048),
}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Backend init: the device JAX finds, or nothing.
# ---------------------------------------------------------------------------


def init_backend() -> dict:
    """{"platform", "device_kind", "count"} of the device this run is on.
    Raises unless it is a TPU or JAX_PLATFORMS=cpu was asked for: JAX's own
    fall-back to the CPU is silent, and a number taken there must never
    look like the chip's."""
    from foundationdb_tpu.utils import enable_compilation_cache, require_tpu

    enable_compilation_cache()
    return require_tpu("bench.py")


# ---------------------------------------------------------------------------
# Workload generation (scrambled bounded Zipf, YCSB-A style)
# ---------------------------------------------------------------------------


def zipf_sampler(rng: np.random.Generator, n_keys: int, theta: float = 0.99):
    """Bounded scrambled Zipf: rank r picked with p ∝ (r+1)^-theta, then
    mapped through a fixed permutation so hot keys are scattered across the
    keyspace (YCSB's ScrambledZipfianGenerator)."""
    w = (np.arange(1, n_keys + 1, dtype=np.float64)) ** (-theta)
    cdf = np.cumsum(w / w.sum())
    perm = rng.permutation(n_keys).astype(np.int64)

    def sample(shape) -> np.ndarray:
        u = rng.random(shape)
        return perm[np.minimum(np.searchsorted(cdf, u), n_keys - 1)]

    return sample


def gen_workload(n_txns: int, n_keys: int, seed: int,
                 mode: ModeConfig = MODES["ycsb"],
                 shifting_hotspot: bool = False):
    """Returns (read_ids [N, R], write_ids [N, Q], write_mask [N], lag [N]).

    shifting_hotspot replaces the stationary Zipf draw with a walking
    hotspot: every `period` txns the hot window's center advances half a
    span, so previously-hot keys cool off and eventually leave the MVCC
    window entirely. This is the tiered dictionary's intended regime —
    the resident working set stays bounded while the TOUCHED keyspace
    grows without bound — and the adversarial one for a single-tier
    resident dictionary (which must full-repack at every capacity cliff).
    The half-span overlap between consecutive hotspots forces re-touches
    of cooling keys, i.e. genuine promotions from the cold tier.
    """
    rng = np.random.default_rng(seed)
    if shifting_hotspot:
        # Geometry pinned to the tiered A/B: with keys = 100x the hot
        # capacity H, the hot window spans H/16 keys and walks half a
        # span every 1/32 of the stream. Every touched key yields TWO
        # dictionary entries (begin + end sentinel), so the MVCC-window
        # working set lands around H/3 — inside the hot tier — while the
        # cumulative touched set reaches ~2H and keeps growing with the
        # stream length.
        span = max(64, n_keys // 1600)
        period = max(mode.batch, n_txns // 32)
        idx = np.arange(n_txns, dtype=np.int64)
        center = (idx // period) * (span // 2) % n_keys

        def draw(k):
            off = rng.integers(0, span, (n_txns, k), dtype=np.int64)
            return (center[:, None] + off) % n_keys

        read_ids, write_ids = draw(mode.n_reads), draw(mode.n_writes)
    else:
        sample = zipf_sampler(rng, n_keys, mode.theta)
        read_ids = sample((n_txns, mode.n_reads))
        write_ids = sample((n_txns, mode.n_writes))
    write_mask = rng.random(n_txns) < mode.write_frac
    lag = np.minimum(rng.geometric(0.6, n_txns) - 1, MAX_LAG).astype(np.int64)
    return read_ids, write_ids, write_mask, lag


# ---------------------------------------------------------------------------
# Wire-blob assembly (vectorized; OUTSIDE the timed loop — a real proxy
# emits these bytes as its RPC payload, so generation is not resolver work)
# ---------------------------------------------------------------------------

# Fixed with-writes record layout (little-endian), nw in the header encodes
# whether the trailing write ranges are present; without-writes records are
# a strict prefix so a masked ragged flatten assembles the stream in numpy.
_REC_RANGE = 8 + 17  # (bl, el) + 8B begin + 9B end
_REC_HDR = 16


def build_wire_stream(read_ids, write_ids, write_mask, lag, n_batches,
                      mode: ModeConfig = MODES["ycsb"]):
    """Returns (blob uint8[...], txn_ends int64[n_txns+1])."""
    n, n_reads = read_ids.shape
    n_writes = write_ids.shape[1]
    rec_full = _REC_HDR + (n_reads + n_writes) * _REC_RANGE
    rec_nowrite = _REC_HDR + n_reads * _REC_RANGE
    be = read_ids.astype(">u8").view(np.uint8).reshape(n, n_reads, 8)
    wbe = write_ids.astype(">u8").view(np.uint8).reshape(n, n_writes, 8)
    cvs = np.repeat(np.arange(1, n_batches + 1, dtype=np.int64), mode.batch)
    rv = np.maximum(cvs - 1 - lag, 0)

    rec = np.zeros((n, rec_full), np.uint8)
    rec[:, 0:8] = rv.astype("<i8").view(np.uint8).reshape(n, 8)
    rec[:, 8:12] = np.frombuffer(
        np.int32(n_reads).astype("<i4").tobytes(), np.uint8
    )
    rec[:, 12:16] = (write_mask * n_writes).astype("<i4").view(np.uint8).reshape(n, 4)
    lens = np.frombuffer(
        np.array([8, 9], "<i4").tobytes(), np.uint8
    )  # (bl=8, el=9)

    def put_range(slot: int, keys_be: np.ndarray) -> None:
        off = _REC_HDR + slot * _REC_RANGE
        rec[:, off : off + 8] = lens
        rec[:, off + 8 : off + 16] = keys_be
        rec[:, off + 16 : off + 24] = keys_be
        rec[:, off + 24] = 0  # end = key + b"\x00"

    for r in range(n_reads):
        put_range(r, be[:, r])
    for q in range(n_writes):
        put_range(n_reads + q, wbe[:, q])

    rec_len = np.where(write_mask, rec_full, rec_nowrite)
    col = np.arange(rec_full)
    blob = rec[col[None, :] < rec_len[:, None]]  # ragged flatten, C speed

    ends = np.zeros(n + 1, np.int64)
    np.cumsum(rec_len, out=ends[1:])
    return blob, ends


def run_tpu_wire(
    n_batches, capacity, blob, txn_ends, repeats: int = 3,
    mode: ModeConfig = MODES["ycsb"], n_resolvers: int = 1,
    window: int = 32, pipeline_depth: int = 4,
    sample_keys: "list[bytes] | None" = None,
    reshard_mid: bool = False,
) -> tuple[float, int, bool, list[float], "list[int] | dict", dict]:
    """Drive the production path: TPUConflictSet.resolve_wire_window_async,
    `window` batches per device dispatch (one lax.scan program — amortizes
    per-dispatch latency the way the reference proxy batches commits per
    resolver RPC). Returns (sec, conflicts, overflow, window_latency_ms,
    shard_occupancy, extras) — occupancy empty unless n_resolvers > 1;
    extras carries the HOST-PACK seconds (the pack half of each window,
    timed apart from dispatch) and the dictionary-economics counters.

    Dispatch is a bounded pipeline (`pipeline_depth` windows in flight,
    the way a real proxy caps outstanding resolver RPCs): window i+depth
    is submitted, then window i's verdicts are collected to the host. The
    collect timestamp minus the submit timestamp is that window's
    dispatch→verdict latency — the resolver component of commit latency —
    so p50/p99 come from the SAME run that measures throughput, not a
    separate unpipelined pass.

    n_resolvers > 1 runs the mesh-sharded engine (§5's 4-resolver config:
    keyspace sharded over devices, per-shard verdicts psum'd on-device)
    with DENSITY splits: shard bounds at the quantiles of a key sample
    drawn from the stream itself, the way the runtime derives resolver
    ranges from DD density (uniform first-byte splits leave Zipf load
    pathological — VERDICT r2 weak-4). `sample_keys` provides the sample.

    reshard_mid demonstrates the runtime rebalance path (VERDICT r3 item
    5): the engine STARTS on uniform splits, occupancy is sampled at the
    midpoint, then reshard(density_splits(sample)) moves the bounds
    between dispatch windows and occupancy is sampled again at the end —
    the artifact shows the imbalance the density splits fix. Occupancy is
    then returned as {"uniform": [...], "density": [...]}."""
    from foundationdb_tpu.models.conflict_set import TPUConflictSet

    occupancy: "list | dict" = []

    def make_cs(force_uniform: bool = False):
        kw = dict(
            capacity=capacity,
            batch_size=mode.batch,
            max_read_ranges=mode.n_reads,
            max_write_ranges=mode.n_writes,
            max_key_bytes=KEY_BYTES,
            window_versions=WINDOW,
        )
        if n_resolvers > 1:
            from foundationdb_tpu.parallel.sharded_resolver import (
                ShardedConflictSet, density_splits,
            )

            splits = (density_splits(n_resolvers, sample_keys)
                      if sample_keys and not force_uniform else None)
            # auto_reshard off: this harness A/Bs split policies EXPLICITLY
            # (uniform-then-density via reshard_mid); the engine's default
            # auto-resharding would silently fix the uniform baseline
            # mid-run and erase the comparison.
            return ShardedConflictSet(
                n_shards=n_resolvers, splits=splits, auto_reshard=False, **kw
            )
        return TPUConflictSet(**kw)

    window = min(window, n_batches)
    n_windows = n_batches // window
    depth = max(1, min(pipeline_depth, n_windows))
    B = mode.batch

    # Warm-up compile.
    cs = make_cs()
    off1 = int(txn_ends[window * B])
    cs.resolve_wire_window_async(blob[:off1], list(range(1, window + 1)), B)()

    do_reshard = reshard_mid and n_resolvers > 1 and sample_keys
    best_dt, conflicts, overflowed = float("inf"), 0, False
    best_lat: list[float] = []
    occ_uniform: list = []
    extras: dict = {}
    for rep in range(repeats):
        cs = make_cs(force_uniform=bool(do_reshard))
        collectors: list = [None] * n_windows
        verdicts: list = [None] * n_windows
        submit_t = [0.0] * n_windows
        lat_ms = [0.0] * n_windows
        pack_ms = [0.0] * n_windows  # host pack half, timed apart
        t0 = time.perf_counter()
        for wi in range(n_windows):
            if do_reshard and wi == max(1, n_windows // 2):
                # Drain in-flight windows, sample the uniform-split load
                # imbalance, then move the bounds — reshard() re-clips the
                # device-resident histories between dispatches, no
                # recompile (parallel/sharded_resolver.py).
                from foundationdb_tpu.parallel.sharded_resolver import (
                    density_splits,
                )

                for j in range(max(0, wi - depth), wi):
                    if verdicts[j] is None:
                        verdicts[j] = collectors[j]()
                        lat_ms[j] = (time.perf_counter() - submit_t[j]) * 1e3
                occ_uniform = cs.shard_occupancy()
                cs.reshard(density_splits(n_resolvers, sample_keys))
            lo = int(txn_ends[wi * window * B])
            hi = int(txn_ends[(wi + 1) * window * B])
            cvs = list(range(wi * window + 1, (wi + 1) * window + 1))
            submit_t[wi] = time.perf_counter()
            prepared = cs.pack_wire_window(blob[lo:hi], cvs, B)
            pack_ms[wi] = (time.perf_counter() - submit_t[wi]) * 1e3
            collectors[wi] = cs.dispatch_window(prepared)
            if wi >= depth:
                j = wi - depth
                if verdicts[j] is None:
                    verdicts[j] = collectors[j]()  # blocks until host-visible
                    lat_ms[j] = (time.perf_counter() - submit_t[j]) * 1e3
        for j in range(max(0, n_windows - depth), n_windows):
            if verdicts[j] is None:
                verdicts[j] = collectors[j]()
                lat_ms[j] = (time.perf_counter() - submit_t[j]) * 1e3
        dt = time.perf_counter() - t0
        log(f"[tpu] rep {rep}: {dt:.3f}s "
            f"(window p50 {np.percentile(lat_ms, 50):.1f}ms "
            f"p99 {np.percentile(lat_ms, 99):.1f}ms)")
        if cs.overflowed:
            log("[tpu] WARNING: history capacity overflow — results invalid")
            overflowed = True
        if dt < best_dt:
            best_dt = dt
            best_lat = lat_ms
            conflicts = int(sum(int((v == 1).sum()) for v in verdicts))
            import hashlib

            extras = {
                # Byte-exact replay gate: the full verdict stream hashed
                # in window order. Two arms on the same seeds (e.g.
                # pipeline_ab's serial vs speculative) must produce
                # IDENTICAL digests — stronger than the conflict-count
                # parity vs the CPU skiplist, which could mask
                # compensating flips.
                "verdicts_sha256": hashlib.sha256(
                    np.stack([np.asarray(v) for v in verdicts]).tobytes()
                ).hexdigest(),
                "host_pack_s": round(sum(pack_ms) / 1e3, 4),
                "host_pack_ms_per_window": round(
                    sum(pack_ms) / max(1, n_windows), 3
                ),
                # Steady-state vs cold split: window 0 absorbs the whole
                # key population under the resident engine (a forced
                # full repack), so the per-dispatch claim is judged on
                # the WARM windows; the cold cost is quoted next to it.
                "host_pack_ms_cold": round(pack_ms[0], 3),
                "host_pack_ms_warm": (
                    round(float(np.median(pack_ms[1:])), 3)
                    if n_windows > 1 else None
                ),
                "dictionary": cs.dict_stats,
            }
            if getattr(cs, "spec", False):
                # Mis-speculation accounting rides in the record so the
                # AB harness (and ratekeeper dashboards) can quote the
                # repair rate next to the throughput claim.
                extras["spec"] = cs.spec_metrics()
            if n_resolvers > 1 and getattr(cs, "wave_commit", False):
                # Mesh wave commit: the realized-graph exchange account
                # (occupied predecessor tiles vs the dense all_gather) —
                # the measured side of the roofline's
                # exchange_bytes_per_batch term.
                extras["wave_exchange"] = cs.exchange_stats()
        if n_resolvers > 1:
            occupancy = cs.shard_occupancy()
    if do_reshard and occupancy and occ_uniform:
        mxu, mnu = max(occ_uniform), max(1, min(occ_uniform))
        mxd, mnd = max(occupancy), max(1, min(occupancy))
        log(f"[tpu] shard occupancy uniform {occ_uniform} "
            f"({mxu / mnu:.2f}x) → density {occupancy} ({mxd / mnd:.2f}x)")
        occupancy = {"uniform": occ_uniform, "density": occupancy}
    elif occupancy:
        mx, mn = max(occupancy), max(1, min(occupancy))
        log(f"[tpu] shard occupancy {occupancy} (max/min {mx / mn:.2f}x)")
    return best_dt, conflicts, overflowed, best_lat, occupancy, extras


def run_tpu_batch_latency(
    n_batches, capacity, blob, txn_ends,
    mode: ModeConfig = MODES["ycsb"], depth: int = 2,
    max_batches: int = 128,
) -> tuple[list[float], float]:
    """Honest per-batch commit latency at sustained load (VERDICT r3 item 7).

    The windowed path (run_tpu_wire) amortizes dispatch overhead across 32
    batches but each txn's verdict waits for the whole window — its p99 is
    queueing, not resolver latency. This probe dispatches ONE batch at a
    time, double-buffered (`depth` in flight, host packing overlapping
    device execute, exactly how the runtime resolver would pipeline
    consecutive proxy batches), and times each batch's submit→verdict. The
    result is the resolver component of per-txn commit latency at
    sustained single-batch dispatch, reported NEXT TO the windowed
    throughput number rather than hidden inside it.

    Returns (per_batch_latency_ms, elapsed_s) over min(n_batches,
    max_batches) batches.
    """
    from foundationdb_tpu.models.conflict_set import TPUConflictSet

    cs = TPUConflictSet(
        capacity=capacity, batch_size=mode.batch,
        max_read_ranges=mode.n_reads, max_write_ranges=mode.n_writes,
        max_key_bytes=KEY_BYTES, window_versions=WINDOW,
    )
    B = mode.batch
    n = min(n_batches, max_batches)
    # Warm-up compile on batch 0's shape.
    lo, hi = int(txn_ends[0]), int(txn_ends[B])
    cs.resolve_wire_async(blob[lo:hi], 1, count=B, as_array=True)()
    cs = TPUConflictSet(
        capacity=capacity, batch_size=mode.batch,
        max_read_ranges=mode.n_reads, max_write_ranges=mode.n_writes,
        max_key_bytes=KEY_BYTES, window_versions=WINDOW,
    )
    collectors: list = [None] * n
    submit_t = [0.0] * n
    lat_ms = [0.0] * n
    t0 = time.perf_counter()
    for b in range(n):
        lo, hi = int(txn_ends[b * B]), int(txn_ends[(b + 1) * B])
        submit_t[b] = time.perf_counter()
        collectors[b] = cs.resolve_wire_async(
            blob[lo:hi], b + 1, count=B, as_array=True
        )
        if b >= depth:
            j = b - depth
            collectors[j]()
            lat_ms[j] = (time.perf_counter() - submit_t[j]) * 1e3
    for j in range(max(0, n - depth), n):
        collectors[j]()
        lat_ms[j] = (time.perf_counter() - submit_t[j]) * 1e3
    return lat_ms, time.perf_counter() - t0


def run_tpu_adaptive(
    n_batches, capacity, blob, txn_ends,
    mode: ModeConfig = MODES["ycsb"], offered_tps: float | None = None,
    budget_ms: float = 250.0, max_window: int = 8,
    max_duration_s: float = 600.0, threaded: bool = True,
    repeats: int = 2,
) -> dict:
    """Adaptive dispatch (sched subsystem) over the same wire stream.

    Replaces the fixed ``batches_per_dispatch`` with the deadline
    coalescer: batches arrive paced at ``offered_tps`` (the fixed-window
    path's measured throughput, so the A/B compares latency at EQUAL
    offered load), the coalescer picks the window depth online from its
    fitted dispatch-cost model under the latency budget, and the
    PipelinedWindowRunner packs window N+1 on a worker thread while the
    device executes window N (double-buffered host packing).

    Latency per batch is arrival→verdict (queue wait + pack + dispatch +
    collect) — a strictly HARSHER accounting than the fixed path's
    submit→collect, so the recorded p99 cut is conservative.

    Window depths are quantized to powers of two and each candidate depth
    is warm-compiled OUTSIDE the timed loop (each distinct k is its own
    scan program; candidate depths the coalescer may never pick cost only
    compile time, which the persistent cache amortizes across runs).
    """
    from foundationdb_tpu.models.conflict_set import TPUConflictSet
    from foundationdb_tpu.sched.coalescer import AdaptiveCoalescer, quantized_depths
    from foundationdb_tpu.sched.packing import PipelinedWindowRunner

    B = mode.batch
    max_window = max(1, min(max_window, n_batches))
    depths = quantized_depths(max_window)
    kw = dict(
        capacity=capacity, batch_size=B, max_read_ranges=mode.n_reads,
        max_write_ranges=mode.n_writes, max_key_bytes=KEY_BYTES,
        window_versions=WINDOW,
    )
    interarrival = (B / offered_tps) if offered_tps else 0.0
    # Bound the paced run's wall time (offered load may be slow on CPU).
    n_use = n_batches
    if interarrival > 0:
        n_use = max(2, min(n_batches, int(max_duration_s / interarrival) + 1))

    # Warm-compile every candidate depth outside the timed loop.
    cs = TPUConflictSet(**kw)
    cv = 1
    for d in depths:
        if d > n_use:
            break
        hi = int(txn_ends[d * B])
        cs.resolve_wire_window_async(blob[:hi], list(range(cv, cv + d)), B)()
        cv += d

    def one_rep() -> dict:
        cs = TPUConflictSet(**kw)
        runner = PipelinedWindowRunner(cs, threaded=threaded)
        coal = AdaptiveCoalescer(budget_ms=budget_ms, max_window=max_window)
        lat_ms = [0.0] * n_use
        arrive_t = [0.0] * n_use
        inflight: list[tuple[int, int, float]] = []  # (first, k, submit_t)
        depth_hist: dict[int, int] = {}
        conflicts = 0
        head = 0      # next batch to dispatch
        arrived = 0   # batches whose arrival time has passed
        backlog_max = 0
        t0 = time.perf_counter()

        def collect_one() -> None:
            nonlocal conflicts
            j, k, st = inflight.pop(0)
            v = runner.collect_next()
            tend = time.perf_counter()
            coal.observe_dispatch(k, (tend - st) * 1e3)
            conflicts += int((np.asarray(v) == 1).sum())
            for b in range(j, j + k):
                lat_ms[b] = (tend - arrive_t[b]) * 1e3

        while head < n_use:
            now = time.perf_counter()
            if interarrival > 0:
                due = min(n_use, int((now - t0) / interarrival) + 1)
            else:
                due = n_use
            while arrived < due:
                arrive_t[arrived] = t0 + arrived * interarrival
                coal.note_arrival(arrive_t[arrived] * 1e3)
                arrived += 1
            queued = arrived - head
            backlog_max = max(backlog_max, queued)
            if queued == 0:
                time.sleep(
                    min(max(t0 + arrived * interarrival - now, 0.0), 0.05)
                )
                continue
            oldest_age_ms = (now - arrive_t[head]) * 1e3
            k = coal.decide(queued, oldest_age_ms)
            if k <= 0:
                hint_s = coal.wait_hint_ms(queued, oldest_age_ms) / 1e3
                next_arr = (t0 + arrived * interarrival - now
                            if arrived < n_use and interarrival > 0 else hint_s)
                time.sleep(min(max(min(hint_s, next_arr), 1e-4), 0.05))
                continue
            # Snap to a warm-compiled (quantized) depth — never a fresh
            # compile inside the timed loop.
            k = max(d for d in depths if d <= min(k, n_use - head))
            lo, hi = int(txn_ends[head * B]), int(txn_ends[(head + k) * B])
            runner.submit(blob[lo:hi], list(range(head + 1, head + k + 1)), B)
            inflight.append((head, k, time.perf_counter()))
            head += k
            depth_hist[k] = depth_hist.get(k, 0) + 1
            runner.dispatch_ready()  # push packed windows to the device
            while len(inflight) > 2:  # double-buffered: ≤2 windows in flight
                collect_one()
        while inflight:
            collect_one()
        dt = time.perf_counter() - t0
        runner.close()
        n_txns = n_use * B
        mean_depth = (sum(k * c for k, c in depth_hist.items())
                      / max(1, sum(depth_hist.values())))
        return annotate_latency({
            "value": round(n_txns / dt, 1),
            "txns": n_txns,
            "p50_ms": pct(lat_ms, 50),
            "p99_ms": pct(lat_ms, 99),
            "latency_budget_ms": budget_ms,
            "offered_tps": round(offered_tps, 1) if offered_tps else None,
            "max_window": max_window,
            "mean_depth": round(mean_depth, 2),
            "depth_hist": {str(k): c for k, c in sorted(depth_hist.items())},
            "windows": sum(depth_hist.values()),
            "conflicts": conflicts,
            "backlog_max": backlog_max,
            # Kept up with the offered load: the dispatch queue never grew
            # past two full windows, so the achieved rate IS the offered
            # rate and the p99 is a steady-state number, not a
            # growing-queue artifact.
            "kept_up": backlog_max <= 2 * max_window,
            "pack_busy_s": round(runner.pack_busy_s, 3),
            "double_buffered": threaded,
        }, sum(depth_hist.values()))

    # Best-of-N, mirroring the fixed windowed path's repeats: a paced run
    # is wall-clock sensitive (one host-contended window IS the p99 of a
    # ~30-window run), so each side gets the same number of attempts and
    # reports its best. Preference: kept-up reps by lowest p99.
    best: dict | None = None
    for rep in range(max(1, repeats)):
        rec = one_rep()
        log(f"[adaptive] rep {rep}: {rec['value']:,.0f} txns/s "
            f"p99 {rec['p99_ms']}ms kept_up={rec['kept_up']}")
        if best is None or (rec["kept_up"], -rec["p99_ms"]) > (
            best["kept_up"], -best["p99_ms"]
        ):
            best = rec
    return best


# ---------------------------------------------------------------------------
# CPU baseline path
# ---------------------------------------------------------------------------


def marshal_cpu_batches(n_batches, read_ids, write_ids, write_mask, lag,
                        mode: ModeConfig = MODES["ycsb"]):
    """Pre-marshal every batch to the C ABI (outside the timed loop).

    Blob layout: one 9-byte record per range (8-byte BE key + 0x00); the
    begin endpoint is bytes [9i, 9i+8), the end endpoint [9i, 9i+9).
    Ranges are emitted in per-txn order: reads then the optional writes.
    """
    B, R, Q = mode.batch, mode.n_reads, mode.n_writes
    out = []
    for b in range(n_batches):
        s = slice(b * B, (b + 1) * B)
        r_ids, w_ids, wm = read_ids[s], write_ids[s], write_mask[s]
        slots = np.concatenate([r_ids, w_ids], axis=1)
        live = np.ones((B, R + Q), bool)
        live[:, R:] = wm[:, None]
        ids = slots[live]
        m = ids.size
        recs = np.zeros((m, 9), np.uint8)
        recs[:, :8] = ids.astype(">u8").view(np.uint8).reshape(m, 8)
        blob = recs.tobytes()
        off = 9 * np.arange(m, dtype=np.int64)
        ranges = np.stack(
            [off, np.full(m, 8, np.int64), off, np.full(m, 9, np.int64)], axis=1
        )
        rc = np.full(B, R, np.int32)
        wc = (wm * Q).astype(np.int32)
        cv = b + 1
        rv = np.maximum(cv - 1 - lag[s], 0).astype(np.int64)
        out.append((blob, np.ascontiguousarray(ranges), rc, wc, rv,
                    cv, max(0, cv - WINDOW)))
    return out


def run_cpu(
    batches, mode: ModeConfig = MODES["ycsb"],
) -> tuple[float, int, list[float], np.ndarray]:
    """Returns (sec, conflicts, per_batch_latency_ms, verdicts int8
    [n_batches, batch]) — the CPU baseline's dispatch→verdict latency
    distribution, for the equal-p99 comparison the north-star metric
    requires (reference: mako's latency histograms), and every verdict, for
    callers that hold the device engine to it transaction by transaction."""
    from foundationdb_tpu.models.cpu_conflict_set import CPUSkipListConflictSet

    cs = CPUSkipListConflictSet()
    lib, ptr = cs._lib, cs._ptr
    i64p = ctypes.POINTER(ctypes.c_int64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    i8p = ctypes.POINTER(ctypes.c_int8)
    all_verdicts = np.zeros((len(batches), mode.batch), np.int8)
    conflicts = 0
    lat_ms = []
    t0 = time.perf_counter()
    for (blob, ranges, rc, wc, rv, cv, oldest), verdicts in zip(
            batches, all_verdicts):
        tb = time.perf_counter()
        lib.cs_resolve(
            ptr, blob,
            ranges.ctypes.data_as(i64p),
            rc.ctypes.data_as(i32p),
            wc.ctypes.data_as(i32p),
            rv.ctypes.data_as(i64p),
            np.int32(mode.batch), np.int64(cv), np.int64(oldest),
            verdicts.ctypes.data_as(i8p),
        )
        lat_ms.append((time.perf_counter() - tb) * 1e3)
        conflicts += int((verdicts == 1).sum())
    dt = time.perf_counter() - t0
    return dt, conflicts, lat_ms, all_verdicts


# Pinned CPU-baseline config (VERDICT weak-3): ONE fixed configuration —
# txn count, key count, seed — reused VERBATIM every round, so the
# baseline's absolute txns/s is comparable across round artifacts no
# matter what headline size/seed a given run used. Change these values
# only with a new round-over-round baseline series.
CPU_BASELINE_PIN = {
    "mode": "ycsb",
    "txns": 262_144,
    "keys": 1 << 16,
    "seed": 20260729,
}


def run_pinned_cpu_baseline() -> dict:
    """The fixed-config CPU skiplist baseline, with a machine-state note
    (the skiplist number is host-sensitive: a loaded host — e.g. a
    concurrent campaign miner — skews it, so the state it ran under is
    part of the record)."""
    import os

    mode = MODES[CPU_BASELINE_PIN["mode"]]
    n_batches = max(1, CPU_BASELINE_PIN["txns"] // mode.batch)
    n_txns = n_batches * mode.batch
    read_ids, write_ids, write_mask, lag = gen_workload(
        n_txns, CPU_BASELINE_PIN["keys"], CPU_BASELINE_PIN["seed"], mode
    )
    batches = marshal_cpu_batches(
        n_batches, read_ids, write_ids, write_mask, lag, mode
    )
    dt, conf, lat, _verdicts = run_cpu(batches, mode)
    try:
        load1 = round(os.getloadavg()[0], 2)
    except (OSError, AttributeError):
        load1 = None
    return annotate_latency({
        "config": dict(CPU_BASELINE_PIN),
        "txns_per_sec": round(n_txns / dt, 1),
        "elapsed_s": round(dt, 3),
        "conflicts": conf,
        "p50_ms": pct(lat, 50),
        "p99_ms": pct(lat, 99),
        "machine_state": {
            "cpu_count": os.cpu_count(),
            "loadavg_1m": load1,
        },
    }, len(lat))


# ---------------------------------------------------------------------------
# Roofline estimate: analytic bytes/FLOPs per resolve_batch against the
# chip's published peaks, so the ≥10× claim is falsifiable from the shapes
# alone. The peaks are keyed by the device_kind JAX reports; a device that
# is not in the table is an error, not a default.
# ---------------------------------------------------------------------------

V5E_DEVICE_KIND = "TPU v5 lite"

DEVICE_PEAKS = {
    V5E_DEVICE_KIND: {
        "bf16_flops": 197e12,  # MXU peak, bf16
        "hbm_bytes_per_s": 819e9,  # HBM bandwidth
        "vpu_int_ops_per_s": 4e12,  # order-of-magnitude VPU lane throughput
        "source": ("Google Cloud documentation, \"TPU v5e\": 197 TF bf16, "
                   "819 GB/s HBM; ~4e12 VPU int-ops/s is this repo's "
                   "order-of-magnitude estimate"),
    },
}


def device_peaks(device_kind: str) -> dict:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks on file for device_kind {device_kind!r}; "
            f"the roofline knows {sorted(DEVICE_PEAKS)}") from None


#: modeled steady-state fraction of endpoint keys NOT already resident
#: (the delta miss rate); measured hit rates ride in the bench record's
#: dictionary stats — this constant only scales the analytic counterfactual.
RESIDENT_MISS_FRAC = 0.02

#: modeled fraction of dispatches that trigger a demotion chunk under the
#: two-tier dictionary (FDB_TPU_DICT_HOT_CAPACITY). Each chunk ships
#: `demote_slots` 4-byte evict ranks; the counterfactual single-tier design
#: ships the ENTIRE hot dictionary (full repack) at every capacity cliff.
#: Measured demotion traffic rides in the bench record's dictionary stats
#: (demotion_bytes_per_dispatch) — this constant only scales the analytic
#: counterfactual.
TIERED_DEMOTE_FRAC = 0.05


def _roofline_one(mode: ModeConfig, capacity: int, wave_rounds: int,
                  peaks: dict) -> dict:
    """The analytic per-batch model of the one kernel design (see
    roofline_estimate): the window history amortizes the base table
    rebuild + merge over the batches one delta fill lasts; the
    device-resident dictionary takes the miss-fraction delta a dispatch;
    history probes are 4-byte rank searches, and every history stream
    (paint, compact, merge) moves 4-byte ranks."""
    B, R, Q = mode.batch, mode.n_reads, mode.n_writes
    H = capacity
    G = min(512, B)  # conflict_kernel._ACCEPT_BLOCK
    nblk = max(1, B // G)
    W = (KEY_BYTES + 3) // 4 + 1  # +1 length/terminator word (keypack)
    kb = 4 * W  # bytes per packed key row
    lgH = max(1.0, np.log2(H))
    N = 2 * B * (R + Q)  # batch endpoints (the delta's size bound)
    n2 = 2 * B * Q  # paint endpoints
    probes = 2 * B * R  # read endpoints probing the history

    cd = min(H, n2 + 2)  # delta capacity (conflict_set default sizing)
    lgCd = max(1.0, np.log2(cd))
    live = max(1.0, n2 * mode.write_frac)  # endpoints painted per batch
    period = max(1.0, cd / live)  # batches between delta→base merges

    # RMQ table builds: the delta table per batch, the base rebuild once
    # per merge; each endpoint probes base AND delta.
    table_bytes = lgCd * cd * 8 + (lgH * H * 8) / period
    table_ops = lgCd * cd + (lgH * H) / period
    lg_probe = lgH + lgCd

    # Per-slot 4-byte rank probes into the width-1 history — ranks ARE
    # the fingerprint, no cascade, no full-width fallback.
    search_bytes = probes * lg_probe * 4 + probes * 8
    search_ops = probes * (lg_probe + 2)
    # Dictionary traffic is the miss-fraction delta ship plus the
    # amortized on-device merge rewrite (dict capacity ~2H default).
    dict_bytes = RESIDENT_MISS_FRAC * (
        (N + 1) * kb + 2 * (2 * H) * kb + H * 4
    )
    # Rank paint: the sort permutation ships precomputed from the host
    # (acceptance-independent — rejected writes merge as delta-0
    # no-ops), so the device paint is pure gathers over rank rows.
    paint_sort_bytes = n2 * 24.0 + n2 * 4.0
    paint_sort_ops = n2 * 6.0
    # Bit-packed masks: uint32 bitset rows and wave tiles; acceptance is
    # pure VPU bitwise.
    rows_bytes = B * B / 8
    wave_bytes = nblk * wave_rounds * 2 * G * G / 8
    mask_ops = (B * B + nblk * wave_rounds * 2 * G * G) / 32
    mxu_flops = 0.0
    overlap_ops = B * B * R * Q * 3  # fused overlap compares

    # Paint/compact streaming over 4-byte rank rows: the small delta per
    # batch and the full base once per merge.
    m_batch = cd + n2
    m_merge = H + cd
    compact_bytes = 6 * m_batch * 4 + (6 * m_merge * 4) / period
    compact_ops = (
        m_batch * np.log2(max(m_batch, 2))
        + (m_merge * np.log2(max(m_merge, 2))) / period
    )

    int_ops = (table_ops + search_ops + paint_sort_ops
               + overlap_ops + mask_ops + compact_ops)
    bytes_moved = (table_bytes + search_bytes + dict_bytes
                   + paint_sort_bytes + rows_bytes + wave_bytes
                   + compact_bytes)
    t_vpu = int_ops / peaks["vpu_int_ops_per_s"]
    t_mxu = mxu_flops / peaks["bf16_flops"]
    t_hbm = bytes_moved / peaks["hbm_bytes_per_s"]
    t_bound = max(t_vpu, t_mxu, t_hbm)
    bound = "vpu" if t_bound == t_vpu else ("hbm" if t_bound == t_hbm else "mxu")
    return {
        "int_ops_per_batch": round(float(int_ops)),
        "mxu_flops_per_batch": round(float(mxu_flops)),
        "bytes_per_batch": round(float(bytes_moved)),
        "t_us_vpu": round(t_vpu * 1e6, 2),
        "t_us_mxu": round(t_mxu * 1e6, 2),
        "t_us_hbm": round(t_hbm * 1e6, 2),
        "bound": bound,
        "projected_peak_txns_per_sec": round(B / t_bound),
    }


def roofline_estimate(mode: ModeConfig, capacity: int, device_kind: str,
                      wave_rounds: int = 4, n_shards: int = 1,
                      exchange_stats: "dict | None" = None) -> dict:
    """Per-batch work estimate for the resolve program at this mode's
    shapes, against the published peaks of `device_kind` (ValueError for a
    device the table does not hold).

    Models the one kernel design: history table builds + rank probes,
    per-block fused overlap rows [G, B] as uint32 bitsets with the
    within-block [G, G] waves, then the merge/compact paint over rank
    rows. Bounds which resource saturates and what peak txns/s/chip the
    hardware admits — not exact."""
    import os

    peaks = device_peaks(device_kind)
    est = _roofline_one(mode, capacity, wave_rounds, peaks)
    est["resident_miss_frac_modeled"] = RESIDENT_MISS_FRAC
    # Tiered-dictionary counterfactual (ISSUE 18): the resident model at
    # the HOT-tier capacity (the dictionary the device actually holds)
    # plus amortized demotion traffic, vs the single-tier design's full
    # repack — which ships the whole hot dictionary — at every capacity
    # cliff. hot_cap comes from the live env knob so the modeled point
    # matches the engine that actually ran; 0/unset means untiered and the
    # record still carries the counterfactual at the full capacity.
    hot_cap = int(os.environ.get("FDB_TPU_DICT_HOT_CAPACITY", "0") or 0)
    hot_cap = hot_cap if 0 < hot_cap < capacity else capacity
    tr = (_roofline_one(mode, hot_cap, wave_rounds, peaks)
          if hot_cap != capacity else est)
    n_words = (KEY_BYTES + 3) // 4
    demote_slots = min(hot_cap // 2,
                       2 * mode.batch * mode.n_writes + 2)  # delta sizing
    demote_bytes = TIERED_DEMOTE_FRAC * 4 * max(1, demote_slots)
    repack_bytes = (hot_cap + 1) * 4 * (n_words + 1)  # whole-dict ship
    est["tiered"] = {
        "hot_capacity_modeled": hot_cap,
        "bytes_per_batch": round(tr["bytes_per_batch"] + demote_bytes),
        "demote_frac_modeled": TIERED_DEMOTE_FRAC,
        "demote_bytes_per_dispatch": round(demote_bytes, 1),
        "full_repack_counterfactual_bytes": repack_bytes,
        # The headline spill claim: rank-stable demotion delta vs shipping
        # the whole hot dictionary once per cliff.
        "repack_vs_demote_ratio": round(
            repack_bytes / max(demote_bytes, 1.0), 1),
    }
    # Buffer-donation audit (ISSUE 17 satellite): every state-mutating jit
    # in conflict_kernel (_resolve*, _advance*, _paint_many*) donates
    # argnum 0, so XLA aliases the history arrays in place instead of
    # materializing a copy per dispatch. The modeled saving is one full
    # state copy per dispatch: keys [capacity, W] int32 + versions + used
    # scalarized as (W + 2) words. Speculation's counter-term is the
    # explicit rollback snapshot (_snapshot_jit) each speculated window
    # takes — the SAME size, paid only on the speculative arm, and only
    # once per window regardless of depth.
    n_words = (KEY_BYTES + 3) // 4
    state_bytes = capacity * (n_words + 2) * 4
    est["donation"] = {
        "donate_argnums_state": True,
        "hbm_bytes_saved_per_dispatch": state_bytes,
        "spec_snapshot_bytes": state_bytes,
    }
    if n_shards > 1:
        # Mesh wave-commit exchange term (ISSUE 13): the predecessor-tile
        # OR-reduce that rebuilds the global conflict graph across the
        # resolver shards. Dense = what the packed [BP, BP/32] all_gather
        # ships per device per batch (every shard's matrix, uint32 words
        # — already 1/32 of an int32 edge matrix); scoped = a
        # tile-granular exchange shipping only OCCUPIED 32x32-bit tiles,
        # so bytes scale with the REALIZED graph, not BP². The scoped
        # figure is measured by the mesh engine
        # (ShardedConflictSet.exchange_stats) when the sharded wave run
        # happened, else None — the model never invents a graph density.
        bp = ((mode.batch + 31) // 32) * 32
        dense = n_shards * bp * (bp // 32) * 4
        term = {
            "n_shards": n_shards,
            "dense_all_gather": dense,
            "scoped_occupied_tiles": (
                exchange_stats.get("exchange_bytes_per_batch_scoped")
                if exchange_stats else None
            ),
            "measured": exchange_stats or None,
        }
        est["exchange_bytes_per_batch"] = term
    est["device_kind"] = device_kind
    est["assumes"] = peaks["source"]
    return est


# ---------------------------------------------------------------------------


def pct(lat_ms: list[float], q: float) -> float:
    return round(float(np.percentile(lat_ms, q)), 2) if lat_ms else 0.0


#: latency records need this many timed dispatches before their p99 is
#: quotable — a 1-window run's p50 == p99 "percentiles" are a single
#: sample wearing a costume (BENCH_r05 singletons, VERDICT weak-5).
MIN_LATENCY_SAMPLES = 32


def annotate_latency(rec: dict, n_samples: int,
                     co_corrected: bool = False) -> dict:
    """Stamp a record with its timed-dispatch count and whether its p99 is
    quotable. Mutates and returns `rec`.

    `co_corrected`: True only when latencies were measured from each
    request's SCHEDULED arrival time under open-loop load (the loadgen
    harness) — i.e. free of coordinated omission. Closed-loop records
    (everything else in this file) are stamped False so the two latency
    regimes can never be quoted interchangeably."""
    rec["latency_samples"] = int(n_samples)
    rec["co_corrected"] = bool(co_corrected)
    rec["p99_quotable"] = n_samples >= MIN_LATENCY_SAMPLES
    if not rec["p99_quotable"]:
        rec["latency_flag"] = f"latency_samples < {MIN_LATENCY_SAMPLES}"
    return rec


def _adaptive_vs_windowed(adaptive_rec, windowed_rate, windowed_lat) -> "dict | None":
    """Attach the fixed-vs-adaptive comparison the scheduler A/B is judged
    on (acceptance: ≥5× p99 cut at equal-or-better throughput)."""
    if not adaptive_rec or adaptive_rec.get("error"):
        return adaptive_rec
    w_p99 = pct(windowed_lat, 99)
    out = dict(adaptive_rec)
    if out.get("p99_ms"):
        out["p99_windowed_over_adaptive"] = (
            round(w_p99 / out["p99_ms"], 2) if w_p99 else None
        )
    if windowed_rate:
        out["throughput_vs_windowed"] = round(out["value"] / windowed_rate, 3)
    return out


def run_config(
    name: str, mode: ModeConfig, n_txns: int, n_keys: int, seed: int,
    capacity: int, device: dict, repeats: int = 3, n_resolvers: int = 1,
    window: int = 32, smoke: bool = False,
    latency_budget_ms: float = 250.0, adaptive_max_window: int = 8,
    adaptive: bool = True, shifting_hotspot: bool = False,
) -> dict:
    """Run one §5 benchmark configuration end-to-end (CPU baseline + TPU
    path on the same stream) and return its result dict. `device` is
    init_backend()'s: the record is valid, and has a roofline, only on a
    TPU."""
    if n_resolvers > 1:
        # The mid-run density reshard (reshard_mid) fires at window
        # n_windows // 2 — force ≥4 dispatch windows or a sharded sweep
        # would silently run whole on pathological uniform splits.
        window = max(1, min(window, max(1, n_txns // mode.batch) // 4))
    window = max(1, min(window, max(1, n_txns // mode.batch)))
    n_batches = max(1, n_txns // mode.batch) // window * window
    n_txns = n_batches * mode.batch
    log(f"[gen] {name}: {n_txns} txns, {n_batches} batches of "
        f"{mode.batch}, {n_keys} keys, R={mode.n_reads} "
        f"Q={mode.n_writes} wf={mode.write_frac} theta={mode.theta} "
        f"resolvers={n_resolvers}")
    read_ids, write_ids, write_mask, lag = gen_workload(
        n_txns, n_keys, seed, mode, shifting_hotspot=shifting_hotspot
    )

    log(f"[cpu] {name}: marshalling...")
    cpu_batches = marshal_cpu_batches(
        n_batches, read_ids, write_ids, write_mask, lag, mode
    )
    cpu_dt, cpu_conf, cpu_lat, _cpu_verdicts = run_cpu(cpu_batches, mode)
    cpu_rate = n_txns / cpu_dt
    log(f"[cpu] {name}: {cpu_dt:.2f}s → {cpu_rate:,.0f} txns/s "
        f"({cpu_conf} conflicts, {cpu_conf / n_txns:.1%}, "
        f"p99 {pct(cpu_lat, 99)}ms/batch)")

    log(f"[tpu] {name}: building wire stream...")
    blob, txn_ends = build_wire_stream(
        read_ids, write_ids, write_mask, lag, n_batches, mode
    )
    sample_keys = None
    if n_resolvers > 1:
        # Density sample for the shard splits: the first few batches'
        # write keys (what a proxy would have observed before splitting).
        n_sample = min(len(write_ids), 8 * mode.batch)
        sample_keys = [
            int(k).to_bytes(8, "big")
            for k in write_ids[:n_sample].reshape(-1)[:16384]
        ]
    tpu_dt, tpu_conf, overflowed, tpu_lat, occupancy, wire_extras = (
        run_tpu_wire(
            n_batches, capacity, blob, txn_ends, repeats=repeats,
            mode=mode, n_resolvers=n_resolvers, window=window,
            sample_keys=sample_keys, reshard_mid=n_resolvers > 1,
        )
    )
    tpu_rate = n_txns / tpu_dt
    log(f"[tpu] {name}: {tpu_dt:.2f}s → {tpu_rate:,.0f} txns/s "
        f"({tpu_conf} conflicts, {tpu_conf / n_txns:.1%})")
    batch_lat, batch_dt, batch_n = [], 0.0, 0
    if n_resolvers == 1 and not smoke:
        batch_lat, batch_dt = run_tpu_batch_latency(
            n_batches, capacity, blob, txn_ends, mode=mode
        )
        batch_n = len(batch_lat)
        log(f"[tpu] {name}: per-batch pipelined latency p50 "
            f"{pct(batch_lat, 50)}ms p99 {pct(batch_lat, 99)}ms "
            f"({batch_n * mode.batch / batch_dt:,.0f} txns/s at depth 2)")
    # Adaptive dispatch (sched subsystem) on the same stream, offered at
    # the fixed windowed path's measured rate — the A/B the scheduler PR
    # is judged on (scripts/sched_ab.sh extracts windowed vs adaptive).
    adaptive_rec: "dict | None" = None
    if adaptive and n_resolvers == 1 and not smoke:
        try:
            adaptive_rec = run_tpu_adaptive(
                n_batches, capacity, blob, txn_ends, mode=mode,
                offered_tps=tpu_rate, budget_ms=latency_budget_ms,
                max_window=adaptive_max_window,
                repeats=max(1, min(repeats, 2)),
            )
            log(f"[tpu] {name}: adaptive dispatch {adaptive_rec['value']:,.0f}"
                f" txns/s p50 {adaptive_rec['p50_ms']}ms "
                f"p99 {adaptive_rec['p99_ms']}ms "
                f"(mean depth {adaptive_rec['mean_depth']})")
        except Exception as e:  # noqa: BLE001 — recorded; exit is non-zero
            log(f"[tpu] {name}: adaptive dispatch failed: {e}")
            adaptive_rec = {"error": str(e)[:300]}
    if tpu_conf != cpu_conf:
        log(f"[warn] {name}: verdict divergence: tpu={tpu_conf} "
            f"cpu={cpu_conf} ({abs(tpu_conf - cpu_conf) / n_txns:.2%})")

    # HEADLINE (VERDICT r4 item 3): the PIPELINED per-batch path — one
    # batch per dispatch, depth-2 double buffering, exactly how a live
    # resolver serves proxies — because the north star is judged "at equal
    # p99" and the windowed mode structurally hides queueing latency. The
    # windowed number is kept as a secondary line (the throughput ceiling
    # when latency doesn't matter, e.g. bulk restore verification).
    pipeline_rate = (
        round(batch_n * mode.batch / batch_dt, 1) if batch_dt else None
    )
    headline_rate = pipeline_rate if pipeline_rate else round(tpu_rate, 1)
    head_p50 = pct(batch_lat, 50) if batch_lat else pct(tpu_lat, 50)
    head_p99 = pct(batch_lat, 99) if batch_lat else pct(tpu_lat, 99)
    head_samples = len(batch_lat) if batch_lat else len(tpu_lat)
    cpu_p99 = pct(cpu_lat, 99)
    return annotate_latency({
        "value": headline_rate,
        "vs_baseline": round(headline_rate / cpu_rate, 3),
        "headline_mode": "pipelined_depth2" if pipeline_rate else "windowed",
        "txns": n_txns,
        "conflict_rate": round(tpu_conf / n_txns, 4),
        "conflicts": tpu_conf,
        "verdict_parity": tpu_conf == cpu_conf,
        "cpu_baseline_txns_per_sec": round(cpu_rate, 1),
        # Headline latency: submit→verdict of a single pipelined batch —
        # the resolver component of per-txn commit latency — vs the CPU
        # baseline's per-batch latency (the equal-p99 clause of SURVEY §0).
        "p50_ms": head_p50,
        "p99_ms": head_p99,
        "p99_vs_cpu": (
            round(head_p99 / cpu_p99, 2) if cpu_p99 else None
        ),
        "cpu_p50_ms": pct(cpu_lat, 50),
        "cpu_p99_ms": cpu_p99,
        # Secondary: the windowed (32-batch scan) dispatch mode — higher
        # throughput, but each verdict waits for the whole window. This is
        # the FIXED-window baseline the adaptive scheduler is A/B'd against.
        "windowed": annotate_latency({
            "value": round(tpu_rate, 1),
            "vs_baseline": round(tpu_rate / cpu_rate, 3),
            "p50_ms": pct(tpu_lat, 50),
            "p99_ms": pct(tpu_lat, 99),
            "batches_per_dispatch": window,
            # Host pack seconds measured apart from dispatch, plus the
            # dictionary-economics counters.
            **wire_extras,
        }, len(tpu_lat)),
        # Adaptive dispatch (sched subsystem): deadline coalescing +
        # online window depth + double-buffered host packing, offered at
        # the windowed path's measured rate (equal-load latency A/B).
        "adaptive": _adaptive_vs_windowed(adaptive_rec, tpu_rate, tpu_lat),
        "resolvers": n_resolvers,
        "workload": "shifting_hotspot" if shifting_hotspot else "zipf",
        "shard_occupancy": occupancy or None,
        "overflowed": overflowed,
        "roofline": (
            roofline_estimate(
                mode, capacity, device["device_kind"], n_shards=n_resolvers,
                exchange_stats=wire_extras.get("wave_exchange"),
            ) if device["platform"] == "tpu"
            else {"skipped": "CPU backend asked for: no chip to bound"}
        ),
        "valid": (not overflowed) and device["platform"] == "tpu",
    }, head_samples)


def _has_error(rec) -> bool:
    """Did any phase or sweep of this record fail? Each is caught where it
    runs, so the rest of the record survives, and leaves {"error": ...}."""
    if isinstance(rec, dict):
        return "error" in rec or any(_has_error(v) for v in rec.values())
    return False


def main() -> None:
    import os

    ap = argparse.ArgumentParser()
    ap.add_argument("--txns", type=int, default=1_000_000)
    ap.add_argument("--keys", type=int, default=1 << 16)
    ap.add_argument("--capacity", type=int, default=1 << 18)
    ap.add_argument("--seed", type=int, default=20260729)
    ap.add_argument("--mode", choices=sorted(MODES), default=None,
                    help="run ONLY this config (default: ycsb headline plus "
                         "reduced-size mako/tpcc/4-resolver sweeps)")
    ap.add_argument("--resolvers", type=int, default=1,
                    help="mesh-sharded resolver count (§5 4-resolver config)")
    ap.add_argument("--window", type=int, default=32,
                    help="FIXED-dispatch resolver batches per device "
                         "dispatch (the adaptive scheduler's A/B baseline)")
    ap.add_argument("--latency-budget-ms", type=float, default=250.0,
                    help="adaptive dispatch: target submit→verdict latency "
                         "budget (sched coalescer)")
    ap.add_argument("--adaptive-max-window", type=int, default=8,
                    help="adaptive dispatch: max window depth (quantized "
                         "power-of-two depths are warm-compiled upfront)")
    ap.add_argument("--no-adaptive", action="store_true",
                    help="skip the adaptive-dispatch pass")
    ap.add_argument("--batch", type=int, default=None,
                    help="override the mode's batch size (smaller batches "
                         "lengthen the stream in MVCC windows — the tiered "
                         "A/B needs keys to age out within the run)")
    ap.add_argument("--theta", type=float, default=None,
                    help="override the mode's Zipf skew (0 = uniform keys "
                         "at the same txn shape; only with --mode)")
    ap.add_argument("--shifting-hotspot", action="store_true",
                    help="replace the stationary Zipf draw with a walking "
                         "hotspot (keys go cold on a schedule) — the tiered "
                         "dictionary A/B's workload knob")
    ap.add_argument("--smoke", action="store_true",
                    help="minimal validity run: one repeat, no latency "
                         "probe / adaptive pass / sweeps")
    ap.add_argument("--repair-sim", action="store_true",
                    help="run the transaction-repair goodput harness "
                         "(deterministic sim, oracle-verified; no TPU) "
                         "instead of the resolver kernel bench")
    ap.add_argument("--repair-txns", type=int, default=240)
    ap.add_argument("--repair-clients", type=int, default=12)
    ap.add_argument("--repair-keys", type=int, default=12)
    ap.add_argument("--wave-commit", choices=("env", "0", "1"),
                    default="env",
                    help="repair-sim resolve mode: reorder-don't-abort "
                         "wave scheduling (1), sequential-order abort "
                         "(0), or the FDB_TPU_WAVE_COMMIT env default "
                         "(scripts/wave_ab.sh fixes the env per arm)")
    ap.add_argument("--open-loop", action="store_true",
                    help="open-loop scale-out harness: boot a REAL "
                         "multi-process cluster over TCP per proxy count, "
                         "drive it with out-of-process Poisson generators "
                         "(coordinated-omission-correct latencies), and "
                         "print the open_loop_scaleout record — txns/s vs "
                         "proxy count, p99 vs offered load through/past "
                         "saturation, and the ratekeeper "
                         "overload-engage/recover run")
    ap.add_argument("--ol-proxies", default="1,2",
                    help="comma list of proxy-process counts to sweep")
    ap.add_argument("--ol-duration", type=float, default=4.0,
                    help="seconds of offered load per ladder point")
    ap.add_argument("--ol-generators", type=int, default=1,
                    help="open-loop generator processes per run")
    ap.add_argument("--ol-clients", type=int, default=512,
                    help="virtual client slots per generator")
    ap.add_argument("--ol-calib-rate", type=float, default=2500.0,
                    help="past-saturation capacity-probe offered rate")
    ap.add_argument("--ol-p99-bound-ms", type=float, default=750.0,
                    help="bounded-p99 clause for a sustainable point")
    ap.add_argument("--ol-min-scaling", type=float, default=1.15,
                    help="required sustainable-tps ratio across counts")
    ap.add_argument("--ol-no-overload", action="store_true",
                    help="skip the ratekeeper overload/recovery run")
    ap.add_argument("--autoscale-ab", action="store_true",
                    help="run the elastic-autoscale A/B (autoscale/): "
                         "closed-loop recruit/retire vs frozen fleet on "
                         "the same seeded flash-crowd schedule plus the "
                         "oscillation hysteresis gate, and print the "
                         "AUTOSCALE_AB record (CPU sim twin; no TPU)")
    ap.add_argument("--autoscale-fast", action="store_true",
                    help="CI-sized autoscale A/B schedules")
    ap.add_argument("--admission-ab", action="store_true",
                    help="run the admission-subsystem A/B goodput harness "
                         "(FDB_TPU_ADMISSION off vs on, same seeds, "
                         "deterministic sim, oracle-verified; no TPU) and "
                         "print the ADMISSION_AB record")
    ap.add_argument("--admission-min-ratio", type=float, default=1.2,
                    help="admission A/B acceptance gate on the mean "
                         "naive-loop goodput ratio")
    ap.add_argument("--repair-target", choices=("hottest", "coldest"),
                    default="hottest",
                    help="repair-sim RMW write target among the Zipf "
                         "picks: hottest = mutual hot-key RMW (cycle-"
                         "heavy, wave commit's worst case), coldest = "
                         "read-hot-write-cold chains (the reorderable "
                         "shape)")
    ap.add_argument("--wave-mesh-ab", action="store_true",
                    help="run the sharded-resolver wave-commit A/B "
                         "(repair/wave_mesh.py): deterministic schedule-"
                         "goodput at n_resolvers in {1,2,4} gated at 5% "
                         "of the single-resolver ratio, plus variance-"
                         "documented e2e sim goodputs; one WAVE_MESH_AB "
                         "JSON line")
    ap.add_argument("--n-resolvers", type=int, default=1,
                    help="repair-sim resolver role count: >1 drives the "
                         "role-level global wave protocol (per-shard "
                         "edge bitsets OR-reduced at the commit proxy — "
                         "scripts/wave_mesh_ab.sh sweeps {1,2,4})")
    args = ap.parse_args()
    if args.autoscale_ab:
        # Deterministic sim twin: CPU by design (control-plane A/B, no
        # device work anywhere in the measured path).
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from foundationdb_tpu.autoscale.ab import run_autoscale_ab

        print(json.dumps(run_autoscale_ab(seed=args.seed,
                                          fast=args.autoscale_fast)),
              flush=True)
        # rc-0 even when valid:false: the record's own flags are the
        # evidence; nonzero rc stays reserved for harness errors.
        sys.exit(0)
    if args.open_loop:
        # Real-socket control-plane harness: subprocess cluster + CPU
        # resolve engine by design — pin CPU so this process never takes
        # a chip (the server/loadgen subprocesses pin themselves).
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from foundationdb_tpu.loadgen.bench import run_open_loop_bench

        rec = run_open_loop_bench(
            proxy_counts=[int(p) for p in args.ol_proxies.split(",")],
            duration_s=args.ol_duration,
            generators=args.ol_generators,
            clients=args.ol_clients,
            seed=args.seed,
            calib_rate=args.ol_calib_rate,
            p99_bound_ms=args.ol_p99_bound_ms,
            min_scaling=args.ol_min_scaling,
            overload=not args.ol_no_overload,
            annotate=annotate_latency,  # one quotability rule, co_corrected
        )
        print(json.dumps(rec), flush=True)
        # rc-0 even when valid:false (e.g. a single-core host cannot show
        # proxy scaling): the record's own flags are the evidence; nonzero
        # rc stays reserved for harness errors.
        sys.exit(0)
    if args.admission_ab:
        # Pure simulation (replay-checked oracle engine): pin CPU so
        # this process never takes a chip.
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from foundationdb_tpu.admission.bench import run_admission_ab

        rec = run_admission_ab(min_ratio=args.admission_min_ratio)
        print(json.dumps(rec), flush=True)
        sys.exit(0 if rec.get("valid") else 1)
    if args.wave_mesh_ab:
        # Pure simulation + deterministic engine replay: pin CPU so
        # this process never takes a chip.
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from foundationdb_tpu.repair.wave_mesh import run_wave_mesh_ab

        rec = run_wave_mesh_ab()
        print(json.dumps(rec), flush=True)
        sys.exit(0 if rec.get("valid") else 1)
    if args.repair_sim:
        # Pure simulation (the conflict engine is the python oracle): pin
        # CPU so this process never takes a chip.
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from foundationdb_tpu.repair.bench import run_repair_goodput

        print(json.dumps(run_repair_goodput(
            n_txns=args.repair_txns, n_clients=args.repair_clients,
            n_keys=args.repair_keys, seed=args.seed,
            wave_commit=(None if args.wave_commit == "env"
                         else args.wave_commit == "1"),
            target_pick=args.repair_target,
            n_resolvers=args.n_resolvers,
        )), flush=True)
        return
    single = args.mode is not None or args.resolvers > 1
    headline_mode = MODES[args.mode or "ycsb"]
    if args.theta is not None or args.batch is not None:
        # Skew override for A/B harnesses that need the SAME txn shape at
        # a different key distribution (e.g. pipeline_ab's uniform arm:
        # ycsb reads/writes at theta 0), and batch-size override for the
        # tiered A/B (the MVCC window is WINDOW commit versions = WINDOW
        # batches, so smaller batches let keys go cold within one run).
        from dataclasses import replace as _dc_replace

        if args.theta is not None:
            headline_mode = _dc_replace(headline_mode, theta=args.theta)
        if args.batch is not None:
            headline_mode = _dc_replace(headline_mode, batch=args.batch)

    # Backend FIRST, and in this process only: nothing below starts a child
    # that needs the chip this process now holds.
    device = init_backend()
    on_tpu = device["platform"] == "tpu"
    log(f"[tpu] device={device} capacity={args.capacity}")
    result = {
        # A rate taken on the CPU backend is not the chip's, and is not
        # written under its name.
        "metric": ("resolved_txns_per_sec_per_chip" if on_tpu
                   else "resolved_txns_per_sec_cpu_backend"),
        "value": 0.0,
        "unit": "txns/s",
        "vs_baseline": 0.0,
        "valid": False,
        "mode": args.mode or "ycsb",
        "resolvers": args.resolvers,
        "backend": device["platform"],
        "device": device,
    }
    try:
        # Headline config: full-size run (ycsb unless --mode overrides).
        head = run_config(
            args.mode or "ycsb", headline_mode, args.txns, args.keys,
            args.seed, args.capacity, device,
            repeats=1 if args.smoke else (3 if on_tpu else 2),
            n_resolvers=args.resolvers, window=args.window,
            smoke=args.smoke,
            latency_budget_ms=args.latency_budget_ms,
            adaptive_max_window=args.adaptive_max_window,
            adaptive=not args.no_adaptive,
            shifting_hotspot=args.shifting_hotspot,
        )
        result.update({k: v for k, v in head.items() if k != "overflowed"})
        result["resolvers"] = args.resolvers

        # Pinned cross-round CPU baseline: same config verbatim every
        # round, absolute txns/s always reported next to the relative
        # vs_baseline numbers above.
        if args.smoke:
            result["cpu_baseline_pinned"] = {
                "skipped": "smoke run", "config": dict(CPU_BASELINE_PIN)}
            # Obs reconciliation identity (observability subsystem): a
            # short traced sim run must show complete span trees whose
            # per-stage sums reconcile against end-to-end latency with
            # the residue reported as `unattributed` — asserted here so
            # a stage-stamping regression fails the smoke gate, not a
            # reader of the next round's artifact.
            from foundationdb_tpu.obs import run_selfcheck

            obs_rec = run_selfcheck(txns=96)
            result["latency_breakdown_selfcheck"] = {
                k: obs_rec[k] for k in
                ("ok", "span_trees_checked", "unattributed_frac",
                 "problems")
            }
            if not obs_rec["ok"]:
                raise RuntimeError(
                    f"obs breakdown reconciliation failed: "
                    f"{obs_rec['problems'][:3]}")
        else:
            try:
                log("[cpu] pinned cross-round baseline "
                    f"({CPU_BASELINE_PIN['txns']} txns)...")
                result["cpu_baseline_pinned"] = run_pinned_cpu_baseline()
                log(f"[cpu] pinned baseline "
                    f"{result['cpu_baseline_pinned']['txns_per_sec']:,.0f} "
                    "txns/s")
            except Exception as e:  # noqa: BLE001 — recorded; exit is non-zero
                result["cpu_baseline_pinned"] = {
                    "error": str(e)[:300], "config": dict(CPU_BASELINE_PIN)}

        # Remaining §5 configs: mako 90/10, TPC-C new-order, 4-resolver
        # sharded — reduced size, one artifact.
        if not single and not args.smoke:
            sweeps = [
                ("mako", MODES["mako"], 1),
                ("tpcc", MODES["tpcc"], 1),
                ("ycsb_r4", MODES["ycsb"], 4),
            ]
            # Off-TPU each sweep costs minutes of interpreter time: shrink
            # further so a CPU-pinned run stays short.
            sweep_txns = min(args.txns, 262_144 if on_tpu else 65_536)
            configs: dict = {}
            for cname, cmode, nres in sweeps:
                if nres > device["count"]:
                    # The sharded engine maps one shard onto each device;
                    # nothing stands in for devices that are not there.
                    configs[cname] = {"skipped": (
                        f"needs {nres} devices, have {device['count']}")}
                    continue
                try:
                    configs[cname] = run_config(
                        cname, cmode, sweep_txns, args.keys, args.seed + 1,
                        args.capacity, device, repeats=1,
                        n_resolvers=nres, window=args.window,
                        latency_budget_ms=args.latency_budget_ms,
                        adaptive_max_window=args.adaptive_max_window,
                        adaptive=not args.no_adaptive,
                    )
                except Exception as e:  # noqa: BLE001 — recorded; the other
                    # sweeps still run, and the exit code is non-zero
                    log(f"[sweep] {cname} failed: {e}")
                    configs[cname] = {"error": str(e)[:300]}
            result["configs"] = configs
    except Exception:
        tb = traceback.format_exc()
        log(tb)
        result["error"] = tb.splitlines()[-1][:500] if tb else "unknown"
    print(json.dumps(result), flush=True)
    if _has_error(result):
        sys.exit(1)


if __name__ == "__main__":
    main()
