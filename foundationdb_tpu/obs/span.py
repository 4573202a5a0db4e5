"""Commit-lifecycle tracing: per-transaction spans + stage histograms.

Every perf record so far measured the commit path at its edges — p99
moved, but WHERE a transaction spent its time was invisible (the kernel
profiler's own ``unattributed_ms`` admits the gap). This module is the
runtime-side answer: a sampled transaction carries a trace context
(txn trace id) through the wire structs, every role stamps span
boundaries, and the CLIENT assembles the exact per-transaction breakdown
from the proxy's piggybacked stage spans (CommitResult.spans), so the
identity

    e2e == sum(stage durations) + unattributed

holds by ARITHMETIC per sampled transaction — the residue is reported,
never silently dropped. The reference's TraceEvent backbone stops at
per-role events; this is the FAFO-style exact per-stage cost attribution
(arxiv 2507.10757) the multi-core open-loop re-run needs to be
diagnosable.

Design rules:

- **Off by default, cheap when on.** No sink attached → role code takes
  one ``getattr`` and moves on. With a sink, only 1-in-N transactions
  (``sample_every``, default 64) pay the per-txn work; per-batch stamps
  (coalescer queue, tlog fsync) are amortized over the whole batch.
- **Deterministic in sim.** Sampling is counter-based (never RNG — it
  must not perturb the loop's seeded stream), trace ids are sequential,
  and all stamps come off the loop's virtual clock, so the same seed
  yields byte-identical span records. On a RealLoop, trace ids carry the
  pid so records from parallel generator processes never collide.
- **One clock, and it moves inside a loop turn.** Every span boundary on
  a role's hot path is ``span_now(loop)``, never a bare ``loop.now``: a
  RealLoop refreshes ``now`` once a pump turn, so a wait that begins and
  ends inside one turn would read 0 and a stamp taken behind a long
  synchronous bracket would read the turn's start. On a wall-time loop
  the span clock is ``time.perf_counter`` (on Linux the system-wide
  CLOCK_MONOTONIC that ``RealLoop.now`` reads too, so the two mix and
  two processes of one host may subtract each other's stamps); in sim it
  is the virtual ``loop.now``. ``stage_clock`` is the same clock as a
  callable, for synchronous brackets.
- **One histogram machinery.** Per-stage distributions reuse loadgen's
  mergeable log-binned ``LatencyHistogram`` — scrape lines from many
  processes SUM into one honest population percentile.

Stage vocabulary (``TXN_STAGES`` is an exclusive partition of a sampled
transaction's commit-path time; ``SUB_STAGES`` attribute the interior of
``resolve_wait``/``grv_wait`` at batch granularity and never enter the
reconciliation identity):

    grv_wait      client: read-version request -> grant (includes the GRV
                  proxy queue and any admission-saturation deferral)
    proxy_admit   proxy: commit arrival -> popped by batch formation
                  (lane queue; includes the admission probe)
    shaped_park   proxy: time parked in the admission shaped lane (0
                  unless shaped)
    batch_form    proxy: popped -> commit version acquired
    resolve_wait  proxy: version -> resolver verdicts (network + the
                  resolver sub-stages below)
    wave_apply    proxy: verdicts -> mutations assembled in (wave, index)
                  order
    tlog_durable  proxy: assemble -> every tlog acked the push fsync'd
    commit_publish proxy: durable -> reply send (sequencer committed-
                  version report, admission filter feed)
    reply         client: commit RPC round trip minus the proxy's total
                  (request + reply transport legs)

    grv_proxy_queue   GRV proxy: request arrival -> batch admit
    resolve_straggle  proxy: a batch's last resolver reply minus its
                      first — what the fan-out to several resolvers
                      adds to resolve_wait (0 with one resolver);
                      recorded in the proxy's own sink, txn-weighted
    rpc_decode        transport: ``wire.loads`` of one request frame
                      (NetTransport._on_frame; n = 1 per frame, only
                      while a sink is on)
    coalesce_queue    resolver: chain admission -> dispatch group start
    host_pack         engine: keys -> row tensors (_pack / _pack_wire),
                      and which transactions go into which dispatch
    wide_layout       engine: INSIDE host_pack, only over a batch that
                      holds a transaction with more ranges than a row has
                      slots — counting each transaction's rows and
                      splitting the batch into dispatches without
                      breaking one (_chunks)
    device_dispatch   resolver: the UMBRELLA over one batch's engine
                      bracket — the batch's TWO brackets, the dispatch
                      half (fail-safe decision, pack, rank, enqueue) and
                      the collect half (verdicts, reading, bookkeeping),
                      plus the modeled dispatch cost in sim, minus
                      host_pack. What runs between the two halves, the
                      successor's host half, is `overlap`, not this.
                      Despite the name it is host AND device time: the
                      stages below are its interior, the mesh engine's
                      reshard_probe and reshard among them, in the
                      dispatch half of the batch whose dispatch made
                      them. (Under the global wave protocol: both
                      phases' engine work, edges + level/paint.)
    dict_rank         engine: endpoints -> u64, mirror probe, delta
                      build, insert_new, ranks (_pack_resident without
                      its repack; _pack_dict)
    dict_repack       engine: the full dictionary repack / tiered
                      demotion, its device liveness sync included
    engine_enqueue    engine: the jitted resolve call until it returns
                      — argument transfer (H2D) and enqueue; does not
                      wait for the device
    verdict_wait      engine: the blocking read of the verdicts — what
                      is left of the device's execution when the role
                      comes to collect (all of it for a lone batch,
                      little behind a successor's host half) plus D2H
    headroom_sync     resolver: for a batch held in flight, the fetch of
                      its capacity reading, (headroom, overflowed) of
                      the state THAT batch left, taken on the device
                      behind its last dispatch and copied with the
                      verdicts: no wait of its own. For a batch collected
                      at once (a nearly empty one, the two-phase wave
                      path): cs.headroom() + cs.overflowed, a second
                      device round trip, as the serial role paid
    resolve_post      engine + resolver: verdict list, loser ranges,
                      admission feed (_collect after the wait), then
                      hot ranges, filter feed, counters (_finish_entry)
    reshard_probe     mesh engine only (ShardedConflictSet, the spec's
                      `resolver_mesh`): every AUTO_RESHARD_INTERVAL-th
                      dispatch, the device_get of the shards' rows in
                      use before the batch is packed. It waits for
                      whatever the device still runs: the bubble the
                      default policy costs. One sample a probe, on the
                      batch whose dispatch made it
    reshard           mesh engine only: one re-split, the quantiles of
                      the live history and the move of rows between
                      chips included (history down, re-clipped on the
                      host, up). One sample a re-split
    engine_unattributed  resolver: the bracket minus every stage above;
                      recorded, never dropped
    overlap           resolver: the end of a batch's dispatch half to the
                      start of its collect half — the time its device
                      execution had to hide in (the successor's host
                      half, or one turn of the loop). Outside the bracket
                      and outside the engine identity; wall-time loops
                      only
    wave_exchange     resolver: global wave commit only — phase-1 reply
                      to phase-2 arrival (the proxy's OR-reduce of the
                      shards' edge bitsets plus both network legs), the
                      comms cost the sharded schedule pays per window
    wave_level        resolver: global wave commit only — the phase-2
                      leveling + paint (interior of device_dispatch)
    spec_resolve      resolver: speculative dispatch only
                      (FDB_TPU_SPEC_RESOLVE) — window N+1's resolve
                      dispatched against N's optimistic paint (interior
                      of device_dispatch, the phase-A half)
    reconcile         resolver: speculative dispatch only — collect +
                      reconcile through the engine ring, including any
                      rollback/repair re-resolves (interior of
                      device_dispatch, the phase-B half)
    tlog_fsync        tlog: chain-ordered push -> durable ack (the disk
                      queue's real fsync included: it is synchronous,
                      and the span clock moves through it)

The read path (``READ_PATH_STAGES``; never in the commit identity: a read
is no commit, and half of a YCSB-F cluster's transactions are reads). Each
is ticked with the READ VERSION as the identifier the stages of one read
share; the client's two also carry the sampled transaction's tid. They
nest and overlap (grv_rtt holds grv_proxy_queue, which holds
grv_sequencer_rtt; read_rpc holds storage_version_wait + storage_lookup
and both transport legs), so they are never summed with one another:

    grv_rtt           client: read-version request -> grant, recorded
                      when it HAPPENS for every sampled transaction, so
                      a read-only one leaves it too (grv_wait, the same
                      interval, is recorded only with a commit's tree)
    read_rpc          client: a point read or batched point read, send ->
                      value (Transaction._fetch_key / _fetch_keys)
    grv_sequencer_rtt GRV proxy: get_live_committed_version + the epoch
                      confirm, n = the batch it serves. grv_proxy_queue
                      minus this and one BATCH_INTERVAL is what a
                      request waited for TOKENS, the ratekeeper's hand
    storage_version_wait  storage: 0 for a read at or under the applied
                      version (recorded, so the mean is over all reads),
                      else the park until the pull loop passes it
    storage_lookup    storage: get / get_multi / get_range from after
                      the version check to the return, weighted by keys
                      (holds read_coalesce / read_pack / read_dispatch
                      where the read rides the coalescer)
    storage_version_lag   storage: once a pull-loop iteration that
                      advanced, (the tlog's version it just saw - the
                      applied version before the apply) in SECONDS of
                      versions: what metrics()["version_lag"] polls for
                      the ratekeeper, as a distribution

Per process and per endpoint, wall-time loops only (the names carry what
they are about after a colon, so they are families, not tuples):

    rpc_inbound:<service>.<method>
                      transport: the sender's send stamp -> the receiver
                      has decoded the request frame (NetTransport.
                      _on_frame): the sender's flush, the wire, the
                      socket buffer WHILE THE RECEIVER'S THREAD WAS BUSY,
                      and the decode (rpc_decode is inside it). The
                      stamp rides the frame as one trailing element only
                      while the SENDER has a sink; recorded only while
                      the RECEIVER has one too. A sample a FRAME, where
                      resolve_wait is a sample a transaction. Exact
                      between processes of one host (one clock); across
                      hosts it holds their clock skew, clamped at 0.
    loop_busy:<role>, loop_idle:<role>
                      RealLoop.run_until: the seconds of each ~100 ms
                      slice the pump spent waiting in select()/sleep()
                      (idle) and doing anything else (busy: ready tasks,
                      socket callbacks, timers), one sample each a
                      slice, NOT sampled 1-in-N: sum(busy) / (sum(busy)
                      + sum(idle)) is the process's busy share, p95 how
                      full its worst slices are. <role> is server.py's
                      --role (proxy, resolver, tlog, storage, sequencer,
                      ratekeeper, ...); a loop server.py did not start
                      takes the first service its transport serves other
                      than admin; one that serves nothing is `client`.
                      The wait itself is entered under
                      TraceAnnotation fdb:loop_select, so a device idle
                      gap in the process that holds the chip falls under
                      it (the role had nothing to do) or under a stage.

The engine identity (``ENGINE_STAGES``), per batch and by ARITHMETIC like
the txn identity above, whether or not another batch was dispatched
between the batch's two halves:

    host_pack + device_dispatch ==
        host_pack + dict_rank + dict_repack + engine_enqueue
        + verdict_wait + headroom_sync + resolve_post
        + reshard_probe + reshard
        + engine_unattributed

The engine fills one per-dispatch record of stage seconds
(``TPUConflictSet.last_stage_s``, through ``stage_timer`` below); the
resolver hands it a fresh record before a batch, adds its own stages,
and ticks every stage with the batch's commit version after it, so the
spans of one batch share an identifier. The role keeps one batch on the
device while it packs the next (``Resolver._dispatch_entry``), so a
batch's collect half runs after its successor's dispatch half: record and
version are bound to the batch at dispatch (the engine's collector carries
them), never read off "the batch in hand". ``stage_timer`` also enters a
``jax.profiler.TraceAnnotation("fdb:<stage>", version=...)``: while a
profiler trace runs, the stages are host events on the profiler's own
clock, next to the device operations. The interior stages are wall-clock
attribution of synchronous work, which a sim loop's virtual clock cannot
see (it reads 0 inside one task step): they are recorded on wall-time
loops only, and sim keeps ``device_dispatch`` = the modeled cost.
"""

from __future__ import annotations

import os
import sys
import time
from collections import deque

from foundationdb_tpu.loadgen.harness import LatencyHistogram

#: Exclusive partition of a sampled txn's commit-path time: the
#: reconciliation identity is  e2e == sum(TXN_STAGES) + unattributed.
TXN_STAGES = (
    "grv_wait",
    "proxy_admit",
    "shaped_park",
    "batch_form",
    "resolve_wait",
    "wave_apply",
    "tlog_durable",
    "commit_publish",
    "reply",
)

#: Batch/role-level attribution INSIDE the txn stages (never summed into
#: the identity — they live within grv_wait / resolve_wait / tlog_durable).
SUB_STAGES = (
    "grv_proxy_queue",
    "resolve_straggle",
    "rpc_decode",
    "coalesce_queue",
    "host_pack",
    "wide_layout",
    "device_dispatch",
    "dict_rank",
    "dict_repack",
    "engine_enqueue",
    "verdict_wait",
    "headroom_sync",
    "resolve_post",
    "reshard_probe",
    "reshard",
    "engine_unattributed",
    "overlap",
    "wave_exchange",
    "wave_level",
    "spec_resolve",
    "reconcile",
    "tlog_fsync",
)

#: The interior of one batch's engine bracket (its dispatch half plus its
#: collect half): the engine identity is  host_pack + device_dispatch ==
#: sum(ENGINE_STAGES) + engine_unattributed.
ENGINE_STAGES = (
    "host_pack",
    "dict_rank",
    "dict_repack",
    "engine_enqueue",
    "verdict_wait",
    "headroom_sync",
    "resolve_post",
)

#: What the mesh engine's split policy adds to that interior, on the
#: batches whose dispatch looked or moved (ShardedConflictSet.
#: _maybe_auto_reshard); no other engine records them. The identity over
#: a mesh is  host_pack + device_dispatch == sum(ENGINE_STAGES)
#: + sum(MESH_ENGINE_STAGES) + engine_unattributed.
MESH_ENGINE_STAGES = (
    "reshard_probe",
    "reshard",
)

#: Read-plane batch-level stages (foundationdb_tpu/reads/): stamped via
#: stage_tick by the storage-side coalescer and the per-version watch
#: sweep. Like SUB_STAGES they never sum into the TXN identity (reads are
#: not commits), but they ride the same histograms/span export, so `cli
#: latency`, the flight recorder, and the doctor's attribution see the
#: read plane next to the commit path.
READ_STAGES = (
    "read_coalesce",
    "read_pack",
    "read_dispatch",
    "watch_sweep",
)

#: A read from the client down (module docstring). Apart from READ_STAGES
#: on purpose: those PARTITION the read plane's own time (the doctor sums
#: them into one denominator); these nest in one another, hold those, and
#: storage_version_lag is a distance, not time spent.
READ_PATH_STAGES = (
    "grv_rtt",
    "read_rpc",
    "grv_sequencer_rtt",
    "storage_version_wait",
    "storage_lookup",
    "storage_version_lag",
)


def obs_env_default() -> bool:
    """FDB_TPU_OBS env default (validated via the kernel flags' shared
    env_choice: unknown values raise with the accepted list)."""
    from foundationdb_tpu.core.types import env_choice

    return env_choice("FDB_TPU_OBS", "0", ("0", "1")) == "1"


def obs_sample_default() -> int:
    """FDB_TPU_OBS_SAMPLE: sample 1-in-N transactions (default 64)."""
    raw = os.environ.get("FDB_TPU_OBS_SAMPLE", "64")
    try:
        n = int(raw)
        if n < 1:
            raise ValueError
    except ValueError:
        raise ValueError(
            f"FDB_TPU_OBS_SAMPLE={raw!r} invalid: want an integer >= 1"
        ) from None
    return n


class TraceContext:
    """A sampled transaction's trace identity, propagated through the
    wire structs (CommitRequest.trace). Existence == sampled: unsampled
    transactions carry None and cost nothing downstream."""

    __slots__ = ("tid",)

    def __init__(self, tid: int):
        self.tid = tid

    def __repr__(self) -> str:
        return f"TraceContext({self.tid:#x})"


class SpanSink:
    """Per-loop span collector: ring of span records + per-stage mergeable
    histograms. Attaches as ``loop.span_sink`` (the Tracer convention) so
    role code reaches it ambiently.

    Span records are plain dicts ``{tid, name, start, dur, process}``
    (``version`` for batch-level records); ``start``/``dur`` are seconds
    on the emitting process's loop clock, rounded to 9 decimals so sim
    records are byte-identical under a seed."""

    def __init__(self, loop, sample_every: int | None = None,
                 ring_size: int = 8192, enabled: bool = True):
        self.loop = loop
        self.sample_every = (obs_sample_default() if sample_every is None
                             else max(1, int(sample_every)))
        self.enabled = enabled
        self.spans: deque[dict] = deque(maxlen=ring_size)
        self.stage_hists: dict[str, LatencyHistogram] = {}
        self.e2e_hist = LatencyHistogram()
        self.unattributed_hist = LatencyHistogram()
        self._sample_counter = 0
        self._stage_ticks: dict[str, int] = {}
        self._spans_dropped = 0  # ring evictions (maxlen overflow)
        self._next_tid = 0
        # RealLoop (deployed / loadgen generator): pid-salted trace ids so
        # parallel processes never collide. Never in sim — determinism.
        self._tid_base = (
            (os.getpid() & 0xFFFF) << 40
            if getattr(loop, "WALL_TIME", False) else 0
        )
        self.txns_sampled = 0
        self.txns_seen = 0
        loop.span_sink = self

    # -- sampling ------------------------------------------------------------

    def sample(self) -> TraceContext | None:
        """1-in-N counter-based sampling decision (deterministic: never
        draws from the loop RNG). Returns a TraceContext or None."""
        if not self.enabled:
            return None
        self.txns_seen += 1
        self._sample_counter += 1
        if self._sample_counter < self.sample_every:
            return None
        self._sample_counter = 0
        self._next_tid += 1
        self.txns_sampled += 1
        return TraceContext(self._tid_base | self._next_tid)

    # -- recording -----------------------------------------------------------

    def _hist(self, name: str) -> LatencyHistogram:
        h = self.stage_hists.get(name)
        if h is None:
            h = self.stage_hists[name] = LatencyHistogram()
        return h

    def record_stage(self, name: str, dur_s: float, n: int = 1) -> None:
        """Histogram-only stage sample (batch-level sub-stages)."""
        self._hist(name).record_n(dur_s * 1e3, n)

    def stage_tick(self, name: str, dur_s: float, n: int = 1,
                   version: "int | None" = None) -> None:
        """Sampled sub-stage record: 1-in-sample_every per stage NAME,
        counter-based (deterministic). The population sub-stages
        (grv_proxy_queue, tlog_fsync, per-batch resolver stages) ride the
        commit hot path on EVERY request while tracing is armed — at full
        recording they alone cost ~10% throughput, which would fail the
        subsystem's own overhead gate. They are distribution detail, not
        part of the reconciliation identity, so sampling them like the
        txn spans keeps the gate honest and the histograms statistical.

        ``version``: also ring a batch-level span record for the sampled
        tick (tid None, the batch's commit version attached) so the
        Chrome-trace/Perfetto export shows the sub-stage on the emitting
        role's track — the mesh wave stages (wave_exchange/wave_level)
        pass it so the sharded protocol's comms/level cost is visible on
        the timeline, not only in the flat tallies."""
        c = self._stage_ticks.get(name, 0) + 1
        if c >= self.sample_every:
            self._stage_ticks[name] = 0
            self.record_stage(name, dur_s, n)
            if version is not None:
                self.add_span(None, name, span_now(self.loop) - dur_s,
                              dur_s, version=version)
        else:
            self._stage_ticks[name] = c

    def add_span(self, tid: "int | None", name: str, start: float,
                 dur: float, process: str | None = None,
                 version: "int | None" = None) -> None:
        """One span record for the tree/timeline (ring-buffered)."""
        if process is None:
            cur = getattr(self.loop, "_current", None)
            process = cur.process if cur is not None else "<main>"
        rec = {
            "tid": tid,
            "name": name,
            "start": round(start, 9),
            "dur": round(dur, 9),
            "process": process,
        }
        if version is not None:
            rec["version"] = version
        if len(self.spans) == self.spans.maxlen:
            self._spans_dropped += 1  # eviction truncates the OLDEST tid
        self.spans.append(rec)

    def record_txn(self, tid: int, e2e_s: float,
                   stages: "list[tuple[str, float, float]]") -> float:
        """One sampled transaction's assembled breakdown: ``stages`` is
        [(stage name, absolute start, duration), ...] in TXN_STAGES
        vocabulary. Records the span tree, the per-stage histograms, the
        end-to-end histogram, and the arithmetic residue; returns the
        residue (seconds). Negative residue is clamped to 0 for the
        histogram but preserved in the span record — a negative value
        would mean double-counted stages and must stay visible."""
        attributed = 0.0
        for name, start, dur in stages:
            self.add_span(tid, name, start, dur)
            self._hist(name).record(dur * 1e3)
            attributed += dur
        unattributed = e2e_s - attributed
        start0 = min((start for _n, start, _d in stages), default=0.0)
        self.add_span(tid, "e2e", start0, e2e_s)
        self.add_span(tid, "unattributed", 0.0, round(unattributed, 9))
        self.e2e_hist.record(e2e_s * 1e3)
        self.unattributed_hist.record(max(0.0, unattributed) * 1e3)
        return unattributed

    # -- query ---------------------------------------------------------------

    def spans_for(self, tid: int) -> list[dict]:
        return [s for s in self.spans if s["tid"] == tid]

    def sampled_tids(self, complete_only: bool = False) -> list[int]:
        """Distinct tids in the ring, oldest first. ``complete_only``
        drops the OLDEST tid whenever the ring has evicted records: a
        txn's spans are appended as one contiguous block (record_txn),
        so front-eviction can truncate only the oldest surviving tid —
        completeness gates must not read that truncation as a missing
        stage (a false alarm that would only fire at scale)."""
        seen: dict[int, None] = {}
        for s in self.spans:
            if s["tid"] is not None:
                seen.setdefault(s["tid"])
        tids = list(seen)
        if complete_only and self._spans_dropped and tids:
            tids = tids[1:]
        return tids

    def breakdown(self) -> dict:
        """The latency_breakdown document (status JSON / cli latency):
        per-stage count/mean/p50/p99 plus the reconciliation block. The
        identity is judged on SUMS (exact arithmetic), not percentiles:
        attributed_ms + unattributed_ms == e2e_ms up to float rounding,
        with unattributed_frac the honesty headline."""
        stages = {
            name: {
                "count": h.count,
                "mean_ms": round(h.mean(), 4),
                "p50_ms": h.percentile(50),
                "p99_ms": h.percentile(99),
                "sum_ms": round(h.sum_ms, 4),
            }
            for name, h in sorted(self.stage_hists.items())
        }
        attributed_ms = sum(
            h.sum_ms for name, h in self.stage_hists.items()
            if name in TXN_STAGES
        )
        e2e_ms = self.e2e_hist.sum_ms
        unattributed_ms = e2e_ms - attributed_ms
        return {
            "enabled": self.enabled,
            "sample_every": self.sample_every,
            "txns_seen": self.txns_seen,
            "txns_sampled": self.txns_sampled,
            "stages": stages,
            "e2e": {
                "count": self.e2e_hist.count,
                "mean_ms": round(self.e2e_hist.mean(), 4),
                "p50_ms": self.e2e_hist.percentile(50),
                "p99_ms": self.e2e_hist.percentile(99),
                "sum_ms": round(e2e_ms, 4),
            },
            "attributed_ms": round(attributed_ms, 4),
            "unattributed_ms": round(unattributed_ms, 4),
            "unattributed_frac": (
                round(max(0.0, unattributed_ms) / e2e_ms, 4)
                if e2e_ms > 0 else 0.0
            ),
        }

    def dump(self) -> dict:
        """Mergeable raw form (histograms as bin lists): what crosses
        process boundaries — loadgen generators emit this next to their
        open-loop accounting and bench merges by histogram sum."""
        return {
            "sample_every": self.sample_every,
            "txns_seen": self.txns_seen,
            "txns_sampled": self.txns_sampled,
            "stages": {n: h.to_dict()
                       for n, h in sorted(self.stage_hists.items())},
            "e2e": self.e2e_hist.to_dict(),
            "unattributed": self.unattributed_hist.to_dict(),
        }

    @classmethod
    def merge_dumps(cls, dumps: "list[dict]") -> dict:
        """Sum several dump() documents (cross-process aggregation) and
        return a breakdown-shaped report over the merged population."""
        dumps = [d for d in dumps if d]
        stage_hists: dict[str, LatencyHistogram] = {}
        e2e = LatencyHistogram()
        seen = sampled = 0
        sample_every = 0
        for d in dumps:
            seen += d.get("txns_seen", 0)
            sampled += d.get("txns_sampled", 0)
            sample_every = max(sample_every, d.get("sample_every", 0))
            e2e.merge(LatencyHistogram.from_dict(d.get("e2e", {})))
            for name, hd in (d.get("stages") or {}).items():
                h = stage_hists.setdefault(name, LatencyHistogram())
                h.merge(LatencyHistogram.from_dict(hd))
        attributed_ms = sum(
            h.sum_ms for n, h in stage_hists.items() if n in TXN_STAGES
        )
        e2e_ms = e2e.sum_ms
        return {
            "merged_from": len(dumps),
            "sample_every": sample_every,
            "txns_seen": seen,
            "txns_sampled": sampled,
            "stages": {
                n: {"count": h.count, "mean_ms": round(h.mean(), 4),
                    "p50_ms": h.percentile(50), "p99_ms": h.percentile(99),
                    "sum_ms": round(h.sum_ms, 4)}
                for n, h in sorted(stage_hists.items())
            },
            "e2e": {"count": e2e.count, "mean_ms": round(e2e.mean(), 4),
                    "p50_ms": e2e.percentile(50),
                    "p99_ms": e2e.percentile(99),
                    "sum_ms": round(e2e_ms, 4)},
            "attributed_ms": round(attributed_ms, 4),
            "unattributed_ms": round(e2e_ms - attributed_ms, 4),
            "unattributed_frac": (
                round(max(0.0, e2e_ms - attributed_ms) / e2e_ms, 4)
                if e2e_ms > 0 else 0.0
            ),
        }

    def to_chrome_trace(self) -> dict:
        """Chrome-trace/Perfetto timeline of the sampled window: complete
        ("X") events, one track per emitting process, span name + trace
        id in args. Load via chrome://tracing or ui.perfetto.dev."""
        events = []
        pids: dict[str, int] = {}
        for s in self.spans:
            pid = pids.setdefault(s["process"], len(pids) + 1)
            events.append({
                "name": s["name"],
                "ph": "X",
                "pid": pid,
                "tid": (s["tid"] or 0) & 0xFFFFFFFF,
                "ts": round(s["start"] * 1e6, 3),
                "dur": round(s["dur"] * 1e6, 3),
                "args": {k: v for k, v in s.items()
                         if k in ("tid", "version", "process")},
            })
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "metadata": {
                "source": "foundationdb_tpu.obs",
                "processes": {str(v): k for k, v in pids.items()},
            },
        }

    def reset(self) -> None:
        """Clear collected spans/histograms (ladder points reuse one
        sink); the sampling counter and tid sequence keep running."""
        self.spans.clear()
        self._spans_dropped = 0
        self.stage_hists = {}
        self.e2e_hist = LatencyHistogram()
        self.unattributed_hist = LatencyHistogram()
        self.txns_sampled = 0
        self.txns_seen = 0


#: A committed sampled txn's tree must contain ALL of these (shaped_park
#: only when the txn rode the shaped lane).
REQUIRED_TREE = frozenset(
    s for s in TXN_STAGES if s != "shaped_park"
) | {"e2e", "unattributed"}

#: The proxy-side stages that must PARTITION [arrival, reply send]
#: contiguously — a gap here is a stage the proxy forgot to stamp.
_PROXY_CHAIN = ("proxy_admit", "shaped_park", "batch_form", "resolve_wait",
                "wave_apply", "tlog_durable", "commit_publish")


def check_txn_tree(spans: "list[dict]", tol: float = 1e-6) -> list[str]:
    """Completeness check for ONE sampled transaction's span records:
    every commit-path stage present, and the proxy chain contiguous (no
    stage gaps). Returns problems; empty == complete."""
    names = {s["name"] for s in spans}
    problems = [f"missing stage: {n}" for n in sorted(REQUIRED_TREE - names)]
    chain = sorted((s for s in spans if s["name"] in _PROXY_CHAIN),
                   key=lambda s: s["start"])
    for prev, nxt in zip(chain, chain[1:]):
        gap = nxt["start"] - (prev["start"] + prev["dur"])
        if abs(gap) > tol:
            problems.append(
                f"gap {gap:.9f}s between {prev['name']} and {nxt['name']}")
    # Per-txn reconciliation identity, straight off the records.
    e2e = sum(s["dur"] for s in spans if s["name"] == "e2e")
    attributed = sum(s["dur"] for s in spans if s["name"] in TXN_STAGES)
    resid = sum(s["dur"] for s in spans if s["name"] == "unattributed")
    if abs(e2e - attributed - resid) > tol:
        problems.append(
            f"identity broken: e2e {e2e:.9f} != attributed {attributed:.9f}"
            f" + unattributed {resid:.9f}")
    return problems


def span_sink(loop) -> "SpanSink | None":
    """The loop's span sink when tracing is armed and enabled, else None.
    THE hot-path gate: every role call site is
    ``sink = span_sink(loop)`` + ``if sink is not None`` — one getattr
    when tracing is off."""
    s = getattr(loop, "span_sink", None)
    return s if s is not None and s.enabled else None


class stage_timer:
    """``with stage_timer(record, "dict_rank", version):`` — one stage of
    one dispatch, on both clocks: its seconds are ADDED to
    ``record[stage]`` (a plain dict; chunks of one batch accumulate; None
    = time nothing), and the block runs inside a
    ``jax.profiler.TraceAnnotation("fdb:<stage>", version=...)``, a host
    event on the profiler's timeline while a trace runs and a flag check
    while none does. ``inside``: the stage this one is nested in, whose
    seconds it is carved out of, so the record stays a partition.
    ``.seconds`` holds the elapsed time after the block.

    The annotation class is taken from ``sys.modules`` — this module is
    imported by the client and by harnesses that must never load JAX;
    where JAX is not loaded this is a plain timer. THE one place that
    calls TraceAnnotation."""

    __slots__ = ("record", "stage", "version", "inside", "seconds",
                 "_t0", "_ann")

    def __init__(self, record: "dict | None", stage: str,
                 version: "int | None" = None, inside: "str | None" = None):
        self.record = record
        self.stage = stage
        self.version = version
        self.inside = inside
        self.seconds = 0.0
        self._ann = None

    def __enter__(self) -> "stage_timer":
        jax = sys.modules.get("jax")
        if jax is not None:
            self._ann = jax.profiler.TraceAnnotation(
                "fdb:" + self.stage, version=int(self.version or 0))
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = dt = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        rec = self.record
        if rec is not None:
            rec[self.stage] = rec.get(self.stage, 0.0) + dt
            if self.inside is not None:
                rec[self.inside] = rec.get(self.inside, 0.0) - dt


def span_now(loop) -> float:
    """One stamp on THE SPAN CLOCK, the clock every batch- and
    transaction-level span boundary is read from.

    A wall-time loop refreshes ``loop.now`` once a pump turn, so two
    stamps taken in one turn read the same and a stamp taken after a long
    synchronous bracket reads the turn's START: a queue wait measured
    with it is 0 by construction. There the span clock is
    ``time.perf_counter``: on Linux the same system-wide CLOCK_MONOTONIC
    that ``RealLoop.now`` reads, so it moves inside a turn, mixes with
    ``loop.now`` stamps, and two processes of ONE host may subtract each
    other's stamps (``rpc_inbound``); across hosts the difference holds
    the hosts' clock skew. A sim loop keeps its virtual ``loop.now``, so
    simulated span records stay byte-identical under a seed.

    The rule for role code: on the hot path a span boundary is
    ``span_now(loop)``, never a bare ``loop.now``."""
    if getattr(loop, "WALL_TIME", False):
        return time.perf_counter()
    return loop.now


def stage_clock(loop):
    """``span_now`` as a callable, for brackets that read it many times.
    Clock for SYNCHRONOUS work (engine resolve, host pack): the loop
    clock cannot advance inside one task step on a RealLoop, so deployed
    processes measure with perf_counter; sim keeps the virtual clock so
    records stay seed-deterministic (synchronous work is 0 virtual
    seconds there, honestly reported as such)."""
    if getattr(loop, "WALL_TIME", False):
        return time.perf_counter
    return lambda: loop.now
