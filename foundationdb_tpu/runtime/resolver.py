"""Resolver role: ordered batch conflict resolution over a ConflictSet.

Reference: fdbserver/Resolver.actor.cpp. Batches arrive tagged
(prev_version, version); the resolver must apply them in version-chain order
even when the network reorders them, so out-of-order batches park on a
promise keyed by their prev_version. The conflict engine behind it is
pluggable — TPUConflictSet (models/conflict_set.py, the jitted device
kernel), its mesh-sharded variant, or the brute-force oracle for tests —
all exposing resolve(txns, commit_version, oldest_version) → verdicts.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass

from foundationdb_tpu.core.types import (
    WAVE_LEVEL_CYCLE,
    TxnConflictInfo,
    Verdict,
)
from foundationdb_tpu.obs.span import (
    ENGINE_STAGES,
    span_sink,
    stage_clock,
    stage_timer,
)
from foundationdb_tpu.repair.hotrange import HotRangeSketch
from foundationdb_tpu.runtime.flow import Loop, Promise, rpc
from foundationdb_tpu.runtime.sequencer import MVCC_WINDOW_VERSIONS
from foundationdb_tpu.runtime.trace import Severity, trace
from foundationdb_tpu.sched.resolver_queue import ResolveScheduler


_NO_SPAN = contextlib.nullcontext()  # a stage with no sink: nothing timed


@dataclass
class _QueuedBatch:
    """A chain-admitted batch parked in the dispatch queue."""

    version: int
    txns: list
    oldest_version: int | None
    reply: Promise
    t_enq: float = 0.0  # chain-admission time (obs coalesce_queue stage)
    # (padded engine rows, wide transactions): Resolver._txn_rows, asked
    # once when the batch's group is dispatched.
    rows: tuple[int, int] = (0, 0)


class Resolver:
    REPLY_CACHE_SIZE = 256  # recent batches kept for retransmit replay

    def __init__(self, loop: Loop, conflict_set, init_version: int = 0,
                 scheduler: ResolveScheduler | None = None,
                 budget_s: float | None = None,
                 dispatch_cost_s: float = 0.0,
                 admission_filter=None):
        self.loop = loop
        self.cs = conflict_set
        # Modeled per-batch device-execution cost (virtual seconds).
        # Default 0 keeps dispatch instantaneous (the pre-existing sim
        # behavior); campaigns set it so the dispatch queue accumulates
        # real depth and the ratekeeper's resolver_queue backpressure
        # loop is exercisable end-to-end under simulation.
        self.dispatch_cost_s = dispatch_cost_s
        self._version = init_version  # end of the ADMITTED version chain
        self._waiters: dict[int, Promise] = {}  # prev_version -> wakeup
        self._replies: dict[int, list[Verdict]] = {}  # version -> verdicts
        # Admitted but not yet dispatched/resolved (retransmits of these
        # versions await the pending reply instead of erroring stale).
        self._pending: dict[int, Promise] = {}
        # Dispatch queue between chain admission and the engine: groups
        # consecutive batches per the deadline coalescer, exports queue
        # depth/occupancy for ratekeeper backpressure (sched subsystem).
        # Default budget 0 = immediate dispatch, semantics identical to the
        # unscheduled resolver.
        if scheduler is None and budget_s:
            scheduler = ResolveScheduler(loop, budget_s=budget_s)
        self.sched = scheduler or ResolveScheduler(loop)
        self.sched.attach(self._dispatch_group)
        self.batches_resolved = 0
        self.txns_resolved = 0
        # What this resolver was SENT: conflict ranges (read and write,
        # after the proxy's clip to this resolver's shard) and the txns
        # that brought it any. With several resolvers every one gets
        # every batch, so only these say whether the key split is even.
        self.ranges_received = 0
        self.txns_with_ranges = 0
        # Padded engine rows the resolved transactions took, and the
        # transactions with more ranges than one row's slots (_txn_rows).
        self.rows_dispatched = 0
        self.wide_txns = 0
        # Wave-commit accounting (engines publishing last_wave, i.e. the
        # reorder-don't-abort kernel/oracle): txns committed at a
        # non-zero wave serialized AFTER at least one same-window
        # predecessor instead of racing it (the reordered population),
        # and cycle aborts are the schedule's only intra-window losers —
        # together they make goodput gains attributable in the bench
        # records (ISSUE 7 satellite).
        self.txns_reordered = 0
        self.txns_cycle_aborted = 0
        # Exact CONFLICT verdict count (every engine; fail-safe rejections
        # counted separately above): the bench records' denominator for
        # attributing goodput gains to reorders vs residual aborts.
        self.txns_conflicted = 0
        # History-capacity fail-safe (engines exposing headroom(), i.e. the
        # fixed-capacity device kernels). The reference SkipList grows
        # unboundedly within the MVCC window and can never lose history
        # (fdbserver/SkipList.cpp); the TPU engine has fixed capacity, so
        # the Resolver must guarantee that capacity pressure degrades to
        # spurious CONFLICTs (always serializable), never to truncated
        # history (missed conflicts = serializability violation).
        self._headroom: int | None = None  # cached from last engine touch
        self._fail_safe_on = False
        self._unsafe_until: int | None = None  # version; set on true overflow
        self.overflow_events = 0
        self.txns_rejected_fail_safe = 0
        # Batches whose engine call raised: the RPC fails, the proxy
        # answers its clients commit_unknown_result, and the role keeps
        # serving (_dispatch_group's failure contract) — so a broken
        # engine shows here, not as a dead process.
        self.resolve_failures = 0
        # Per-range conflict-loss sketch for THIS resolver's key shard:
        # every rejected txn's losing read ranges are recorded (decayed),
        # exported via get_metrics and aggregated at the commit proxy
        # (repair subsystem — repair/hotrange.py).
        self.hot_ranges = HotRangeSketch(lambda: loop.now)
        # Recent-writes filter feed (admission subsystem): the resolver is
        # the AUTHORITATIVE feeder — every accepted write set of every
        # proxy passes through here, so its filter sees the union. Commit
        # proxies pull deltas (admission_delta) into their local probe
        # filters; fail-safe batches never feed (their rejections are
        # spurious and their "accepted" set is empty by construction).
        self.admission_filter = admission_filter
        # Role-level global wave protocol (core/wavemesh): per-version
        # state between resolve_edges (phase 1 — gate + clipped edge
        # bitsets, nothing painted) and resolve_apply (phase 2 — level
        # the proxy's OR-reduced global graph, paint, advance the chain).
        # The chain version advances at APPLY, so a successor's phase 1
        # parks on the ordinary _waiters machinery until this window's
        # schedule lands — no scheduler involvement, retransmits replay
        # from the caches.
        self._wave_pending_role: dict[int, dict] = {}
        self._edge_replies: dict[int, tuple] = {}
        # Stage seconds of the batch inside _serial_entry (obs/span.py
        # ENGINE_STAGES): the engine fills its stages into this same dict,
        # the role adds its own. None outside a traced serial batch.
        self._stage_rec: dict | None = None
        self.wave_batches = 0  # windows resolved via the global protocol

    @rpc
    async def begin_epoch(self, start_version: int) -> int:
        """Deployed-restart handshake (see tlog.begin_epoch): adopt the
        booting sequencer's chain start so the first batch's prev_version
        matches. Monotone; parked batches wake to observe the jump."""
        if start_version > self._version:
            self._version = start_version
            for p in list(self._waiters.values()):
                p.send(None)
            self._waiters.clear()
        return self._version

    @rpc
    async def resolve(
        self,
        prev_version: int,
        version: int,
        txns: list[TxnConflictInfo],
        oldest_version: int | None = None,
    ) -> tuple[
        list[Verdict], dict[int, list[tuple[bytes, bytes]]], bool,
        "list[int] | None",
    ]:
        """→ (verdicts, conflicting, fail_safe, wave): conflicting maps a
        txn's batch index to its conflicting read ranges, for txns that set
        report_conflicting_keys and got CONFLICT. fail_safe marks a batch
        rejected wholesale by the capacity fail-safe — its conflicts are
        spurious, so downstream hot-range accounting must skip them (the
        proxy's sketch would otherwise score uncontended ranges hot).
        wave is the engine's wave-commit schedule per txn index (None for
        sequential-order engines and fail-safe batches): the commit proxy
        applies same-version mutations in (wave, index) order so
        write-after-read chains land in dependency order.

        Chain admission is decoupled from engine dispatch: once a batch's
        prev_version matches, it takes its chain position immediately (so
        successors can queue behind it and the coalescer can form a
        window) and parks in the dispatch queue; the reply resolves when
        the scheduler dispatches its group."""
        while self._version != prev_version:
            if prev_version < self._version:
                # Retransmit of a batch whose reply was lost (proxy↔resolver
                # partition healed): replay the cached verdicts — resolving
                # again would double-paint its writes. A retransmit of a
                # batch still PARKED in the dispatch queue shares its
                # pending reply.
                cached = self._replies.get(version)
                if cached is not None:
                    if isinstance(cached, BaseException):
                        raise cached  # replayed failure (see _dispatch_group)
                    return cached
                pend = self._pending.get(version)
                if pend is not None:
                    return await pend.future
                raise ValueError(
                    f"stale resolve batch: prev={prev_version} < applied={self._version}"
                )
            p = self._waiters.setdefault(prev_version, Promise())
            await p.future
        # Chain position acquired: advance the admitted chain and wake the
        # successor BEFORE resolving, so consecutive batches pile into the
        # dispatch queue and coalesce.
        self._version = version
        reply = Promise()
        self._pending[version] = reply
        self.sched.enqueue(
            _QueuedBatch(version, txns, oldest_version, reply,
                         t_enq=self.loop.now)
        )
        w = self._waiters.pop(version, None)
        if w is not None:
            w.send(None)
        return await reply.future

    # -- role-level global wave commit (core/wavemesh) ------------------------
    #
    # With wave commit at n_resolvers > 1, a shard's clipped view cannot
    # be reordered alone — the commit proxy splits each resolve into two
    # chain-ordered phases: resolve_edges returns this shard's history
    # gate + clipped predecessor bitsets (nothing painted), the proxy
    # OR-reduces every shard's bitsets into the GLOBAL conflict graph
    # (exact: shards partition the keyspace), and resolve_apply levels
    # that graph identically on every shard (deterministic rule —
    # byte-identical (wave, index) schedules), paints the shard's
    # accepted writes, and advances the version chain.

    @rpc
    async def resolve_edges(
        self,
        prev_version: int,
        version: int,
        txns: list[TxnConflictInfo],
        oldest_version: int | None = None,
    ) -> tuple:
        """Phase 1: this shard's clipped gate verdicts + packed
        predecessor bitsets (wavemesh.WaveEdges wire tuple). The chain
        position is NOT advanced — that happens at resolve_apply, so a
        successor batch's phase 1 parks until this window's paint lands
        and probes a history that includes it."""
        cached = self._edge_replies.get(version)
        if cached is not None:
            return cached  # phase-1 retransmit (lost reply / proxy retry)
        while self._version != prev_version:
            if prev_version < self._version:
                cached = self._edge_replies.get(version)
                if cached is not None:
                    return cached
                raise ValueError(
                    f"stale resolve_edges: prev={prev_version} < "
                    f"applied={self._version}"
                )
            p = self._waiters.setdefault(prev_version, Promise())
            await p.future
            cached = self._edge_replies.get(version)
            if cached is not None:
                return cached
        from foundationdb_tpu.core.wavemesh import WaveEdges

        if not getattr(self.cs, "wave_global_capable", False):
            raise ValueError(
                "resolve_edges: this resolver's engine does not implement "
                "the global wave protocol"
            )
        if oldest_version is None:
            oldest_version = max(0, version - MVCC_WINDOW_VERSIONS)
        if not txns:
            # Empty window (idle heartbeat batches — the common case on a
            # quiet chain): there is no graph to exchange, so the chain
            # advances HERE and the proxy skips phase 2 entirely — one
            # round trip, same as the sequential path. The engine is not
            # touched (the classic path dispatches nothing for zero txns
            # either).
            reply = ("empty",)
            self._cache_edge_reply(version, reply)
            self._replies[version] = ([], {}, False, [])
            self._trim_replies()
            self.batches_resolved += 1
            self._advance_chain(version)
            return reply
        sink = span_sink(self.loop)
        clock = stage_clock(self.loop) if sink is not None else None
        t0 = clock() if sink is not None else 0.0
        rows = self._txn_rows(txns)
        # One exchange carries one schedule domain: a window whose
        # transactions (a client's wide ones take several rows each) do
        # not fit one engine dispatch is answered like a capacity event.
        # The batch conflicts as a whole, nothing is painted on any shard
        # and the chain advances at apply; raising here would park every
        # successor on a version that never applies.
        fail_safe = (
            self._should_fail_safe(rows[0], version, oldest_version)
            or rows[0] > getattr(self.cs, "batch_size", rows[0])
        )
        if fail_safe:
            import numpy as np

            payload = WaveEdges(
                count=len(txns),
                too_old=np.zeros(len(txns), bool),
                hist_conflict=np.zeros(len(txns), bool),
                chunks=[],
                fail_safe=True,
            )
        else:
            payload = self.cs.resolve_edges(txns, version, oldest_version)
        if sink is not None:
            sink.stage_tick("device_dispatch", clock() - t0,
                            n=max(1, len(txns)))
        self._wave_pending_role[version] = {
            "txns": txns,
            "oldest": oldest_version,
            "rows": rows,
            "fail_safe": fail_safe,
            "t_edges_done": self.loop.now,
        }
        reply = payload.to_wire()
        self._cache_edge_reply(version, reply)
        return reply

    def _cache_edge_reply(self, version: int, reply: tuple) -> None:
        """Bounded phase-1 reply cache (retransmit replay) — trimmed on
        EVERY insert; the empty-heartbeat fast path is the common case on
        a quiet chain and must not leak an entry per window."""
        self._edge_replies[version] = reply
        if len(self._edge_replies) > self.REPLY_CACHE_SIZE:
            del self._edge_replies[min(self._edge_replies)]

    @rpc
    async def resolve_apply(self, version: int, graph_wire: tuple) -> tuple[
        list[Verdict], dict[int, list[tuple[bytes, bytes]]], bool,
        "list[int] | None",
    ]:
        """Phase 2: level the combined global graph, paint, advance the
        chain. Reply shape matches resolve() so the proxy's downstream
        (verdict combine, hot ranges, wave-ordered apply) is unchanged."""
        if version <= self._version:
            cached = self._replies.get(version)
            if cached is not None:
                if isinstance(cached, BaseException):
                    raise cached
                return cached
            raise ValueError(
                f"stale resolve_apply: version={version} <= "
                f"applied={self._version}"
            )
        inflight = self._pending.get(version)
        if inflight is not None:
            # Retransmit while the first apply is still executing (reply
            # lost mid-RPC, proxy retried): share the pending reply, the
            # same idempotent-retry contract resolve() keeps.
            return await inflight.future
        pend = self._wave_pending_role.pop(version, None)
        if pend is None:
            raise ValueError(
                f"resolve_apply@{version} without a matching resolve_edges"
            )
        self._pending[version] = inflight = Promise()
        from foundationdb_tpu.core.wavemesh import WaveGraph

        graph = WaveGraph.from_wire(graph_wire)
        txns = pend["txns"]
        sink = span_sink(self.loop)
        if sink is not None:
            # The inter-phase gap: proxy-side OR-reduce + both network
            # legs — the global protocol's comms cost, attributed under
            # the resolver's device_dispatch umbrella (SUB_STAGES).
            sink.stage_tick("wave_exchange",
                            self.loop.now - pend["t_edges_done"],
                            n=max(1, len(txns)), version=version)
        if self.dispatch_cost_s:
            await self.loop.sleep(self.dispatch_cost_s)
        clock = stage_clock(self.loop) if sink is not None else None
        t0 = clock() if sink is not None else 0.0
        try:
            reply = self._apply_entry(version, txns, pend, graph)
        except BaseException as e:  # noqa: BLE001 — fail the RPC waiter
            self.resolve_failures += 1
            self._replies[version] = e
            self._trim_replies()
            self._pending.pop(version, None)
            inflight.fail(e)
            self._advance_chain(version)
            raise
        if sink is not None:
            dur = clock() - t0 + self.dispatch_cost_s
            n = max(1, len(txns))
            sink.stage_tick("wave_level", dur, n=n, version=version)
            sink.stage_tick("device_dispatch", dur, n=n)
        self._replies[version] = reply
        self._trim_replies()
        self._pending.pop(version, None)
        inflight.send(reply)
        self._advance_chain(version)
        return reply

    def _count_resolved(self, txns: list[TxnConflictInfo],
                        rows: tuple[int, int]) -> None:
        self.batches_resolved += 1
        self.txns_resolved += len(txns)
        for t in txns:
            n = len(t.read_ranges) + len(t.write_ranges)
            if n:
                self.ranges_received += n
                self.txns_with_ranges += 1
        self.rows_dispatched += rows[0]
        self.wide_txns += rows[1]

    def _txn_rows(self, txns: list[TxnConflictInfo]) -> tuple[int, int]:
        """(padded rows the engine gives `txns`, how many of them are wider
        than one row's slots): a transaction a row for engines with no
        slots (oracle, C++ skiplist)."""
        fn = getattr(self.cs, "txn_rows", None)
        return fn(txns) if fn is not None else (len(txns), 0)

    def _advance_chain(self, version: int) -> None:
        self._version = version
        w = self._waiters.pop(version, None)
        if w is not None:
            w.send(None)

    def _apply_entry(
        self, version: int, txns: list[TxnConflictInfo], pend: dict, graph
    ) -> tuple:
        """Phase-2 body: verdicts + schedule from the global graph, with
        the same counter/hot-range/filter bookkeeping as _resolve_entry."""
        oldest_version = pend["oldest"]
        fail_safe = bool(pend["fail_safe"] or graph.fail_safe)
        wave: list[int] | None = None
        if fail_safe:
            if pend["fail_safe"]:
                # Locally engaged: the engine never saw phase 1 — advance
                # its GC floor exactly like the single-phase fail-safe.
                if hasattr(self.cs, "advance"):
                    self.cs.advance(version, oldest_version)
                    self._headroom = self.cs.headroom()
            elif getattr(self.cs, "_wave_pending", None) is not None:
                # Another shard engaged: drop this shard's un-painted
                # phase-1 state (painting nothing IS the fail-safe
                # contract; the floor advances with the next window).
                self.cs.resolve_abandon()
            verdicts = [Verdict.CONFLICT] * len(txns)
            self.txns_rejected_fail_safe += len(txns)
        else:
            verdicts = self.cs.resolve_apply(graph)
            wave = getattr(self.cs, "last_wave", None)
            if self._post_resolve_check(version):
                verdicts = [Verdict.CONFLICT] * len(txns)
                self.txns_rejected_fail_safe += len(txns)
                fail_safe = True
                wave = None
        exact = None if fail_safe else getattr(self.cs, "last_conflicting",
                                               None)
        conflicting: dict[int, list[tuple[bytes, bytes]]] = {}
        for i, (t, v) in enumerate(zip(txns, verdicts)):
            if v != Verdict.CONFLICT:
                continue
            ranges = exact.get(i) if exact else None
            if ranges is None:
                ranges = [r for r in t.read_ranges if not r.empty]
            pairs = [(r.begin, r.end) for r in ranges]
            if not fail_safe and pairs:
                self.hot_ranges.record(pairs)
            if t.report_conflicting_keys and pairs:
                conflicting[i] = pairs
        if not fail_safe:
            self.txns_conflicted += sum(
                1 for v in verdicts if v == Verdict.CONFLICT
            )
            if self.admission_filter is not None:
                keys = [
                    bytes(w.begin)
                    for t, v in zip(txns, verdicts)
                    if v == Verdict.COMMITTED
                    for w in t.write_ranges if not w.empty
                ]
                self.admission_filter.record(keys, version)
        if wave is not None:
            self.txns_reordered += self.cs.last_reordered
            self.txns_cycle_aborted += sum(
                1 for lv in wave if lv == WAVE_LEVEL_CYCLE
            )
            self.wave_batches += 1
        self._count_resolved(txns, pend["rows"])
        return (verdicts, conflicting, fail_safe, wave)

    async def _dispatch_group(self, group: list[_QueuedBatch]) -> None:
        """Scheduler dispatch callback: resolve a consecutive run of
        admitted batches, version order preserved.

        Failure contract: chain admission already advanced past a failing
        batch, so its FAILURE is cached in the reply slot and replayed to
        retransmits (same determinism as a cached verdict). Correctness
        holds because a batch with no verdicts never commits — the proxy
        skips the tlog push and fails its clients with
        commit_unknown_result — so its writes belong in no history, and
        successors resolving without them is exact (a partial paint from
        a mid-batch engine error only ADDS spurious conflicts, never
        misses one)."""
        sink = span_sink(self.loop)
        if sink is not None:
            # Sub-stage attribution (obs subsystem), interior of the
            # proxy-measured resolve_wait: chain admission -> dispatch
            # start per batch, txn-weighted so the histograms reconcile
            # against per-txn populations.
            t0 = self.loop.now
            # The annotation marks the dispatch start on a profiler's
            # timeline (the queueing itself is in the past by now).
            with stage_timer(None, "coalesce_queue", group[0].version):
                for entry in group:
                    sink.stage_tick("coalesce_queue", t0 - entry.t_enq,
                                    n=max(1, len(entry.txns)))
        if self.dispatch_cost_s:
            # Modeled device execution time for this window (sim-only;
            # see __init__) — spent BEFORE the verdicts resolve, like the
            # real kernel's dispatch wall time.
            await self.loop.sleep(self.dispatch_cost_s * len(group))
        clock = stage_clock(self.loop) if sink is not None else None
        for entry in group:
            entry.rows = self._txn_rows(entry.txns)
        if getattr(self.cs, "spec", False):
            # Speculative pipelined resolve (FDB_TPU_SPEC_RESOLVE=1): the
            # engine's reconcile ring lets window N+1's resolve dispatch
            # against N's optimistic paint while N's verdicts are still
            # unconfirmed — phase A below dispatches the whole group,
            # phase B reconciles in version order.
            self._dispatch_group_spec(group, sink, clock)
            return
        for entry in group:
            self._serial_entry(entry, sink, clock)

    def _serial_entry(self, entry: _QueuedBatch, sink, clock) -> None:
        """One batch through the synchronous engine path: resolve, price
        the sub-stages, cache + deliver the reply. Shared by the serial
        group loop and the speculative path's fallback (reporting batches,
        fail-safe, oversize windows the ring cannot take)."""
        rec = None
        if sink is not None:
            # A fresh record per batch, so a batch that never packs
            # (fail-safe rejection, overflow) can't re-record the
            # PREVIOUS batch's stages — fail-safe engages exactly under
            # overload, when the attribution is being read.
            rec = self._stage_rec = {}
            if hasattr(self.cs, "last_stage_s"):
                self.cs.last_stage_s = rec
        t_eng = clock() if sink is not None else 0.0
        try:
            with (stage_timer(None, "device_dispatch", entry.version)
                  if sink is not None else _NO_SPAN):
                reply = self._resolve_entry(entry)
        except BaseException as e:  # noqa: BLE001 — fail the RPC waiter
            self._fail_entry(entry, e)
            return
        finally:
            self._stage_rec = None
        if sink is not None:
            n = max(1, len(entry.txns))
            # Synchronous work has no virtual duration: only a wall-time
            # loop attributes the bracket's interior, and only there do
            # the per-batch spans carry real seconds (sim span records
            # stay byte-identical under a seed).
            wall = getattr(self.loop, "WALL_TIME", False)
            version = entry.version if wall else None
            eng_s = (clock() - t_eng) + self.dispatch_cost_s
            pack_s = rec.get("host_pack")
            if pack_s is not None:
                # DISJOINT attribution: the engine bracket above
                # includes the synchronous host pack — carve it out
                # so host_pack + device_dispatch sums to the
                # interior, never above it.
                sink.stage_tick("host_pack", pack_s, n=n, version=version)
                eng_s = max(0.0, eng_s - pack_s)
                layout_s = rec.get("wide_layout")
                if layout_s is not None:
                    # Inside host_pack, not beside it: what laying wide
                    # transactions out in rows adds to the pack.
                    sink.stage_tick("wide_layout", layout_s, n=n,
                                    version=version)
            # The UMBRELLA: the whole engine bracket minus host_pack
            # (synchronous: perf-clocked on real loops, 0 virtual
            # seconds in sim by construction) plus the modeled dispatch
            # cost this batch's share paid.
            sink.stage_tick("device_dispatch", eng_s, n=n, version=version)
            if wall:
                # Its interior, and the residue: the engine identity
                # (obs/span.py), by arithmetic per batch.
                for stage in ENGINE_STAGES[1:]:
                    stage_s = rec.get(stage)
                    if stage_s is not None:
                        sink.stage_tick(stage, stage_s, n=n, version=version)
                        eng_s -= stage_s
                sink.stage_tick("engine_unattributed", max(0.0, eng_s),
                                n=n, version=version)
        self._send_entry(entry, reply)

    def _stage(self, name: str, version: int):
        """A role-side stage of the traced serial batch in hand (seconds
        into its record, annotation on the profiler's timeline); nothing
        at all outside one."""
        rec = self._stage_rec
        return (stage_timer(rec, name, version) if rec is not None
                else _NO_SPAN)

    def _send_entry(self, entry: _QueuedBatch, reply) -> None:
        self._replies[entry.version] = reply
        self._trim_replies()
        self._pending.pop(entry.version, None)
        entry.reply.send(reply)

    def _fail_entry(self, entry: _QueuedBatch, e: BaseException) -> None:
        self.resolve_failures += 1
        self._replies[entry.version] = e
        self._trim_replies()
        self._pending.pop(entry.version, None)
        entry.reply.fail(e)

    # -- speculative dispatch (FDB_TPU_SPEC_RESOLVE) --------------------------

    def _dispatch_group_spec(self, group: list[_QueuedBatch], sink,
                             clock) -> None:
        """Two-phase group dispatch over a speculative engine.

        Phase A walks the group in version order handing each batch to
        ``cs.spec_resolve_async`` — the engine snapshots, resolves against
        the optimistically painted state, and parks the window on its
        reconcile ring without forcing the device. Phase B (``_drain_spec``)
        collects in the same order; each collect reconciles the ring
        through that window, so a window whose speculation depended on a
        revoked write re-resolves through the engine's repair path before
        its verdicts are ever visible here.

        Batches the ring cannot take (fail-safe, reporting opt-ins,
        oversize) drain the ring FIRST and then resolve serially, so reply
        delivery order always equals version order and the serial path
        never observes a half-reconciled state.

        The capacity fail-safe changes shape under speculation. Phase A
        checks the cached headroom from the LAST reconcile (reading the
        device here would sync the pipeline away); the cache cannot be
        conservatively pre-charged per in-flight window because the
        engine's headroom is capped at its delta capacity (≈ one batch's
        worst-case growth — the in-program merge recovers it every batch),
        so stacking charges would veto all depth > 1. Correctness instead
        rests on reconcile-time detection: verdicts only become visible at
        drain, AFTER ``_post_resolve_check`` has read the device's sticky
        overflow flag — a window that resolved against possibly-truncated
        history is rejected wholesale there, and the unsafe window rejects
        everything younger until the MVCC floor passes the overflow.
        Spurious conflicts, never missed ones — the same guarantee as the
        serial path, detected one phase later."""
        pending: list[tuple[_QueuedBatch, object]] = []
        for entry in group:
            version, txns = entry.version, entry.txns
            oldest = entry.oldest_version
            if oldest is None:
                oldest = max(0, version - MVCC_WINDOW_VERSIONS)
            t_eng = clock() if sink is not None else 0.0
            rec: dict = {}
            if sink is not None and hasattr(self.cs, "last_stage_s"):
                self.cs.last_stage_s = rec
            coll = None
            if not self._should_fail_safe(entry.rows[0], version, oldest):
                try:
                    coll = self.cs.spec_resolve_async(txns, version, oldest)
                except BaseException as e:  # noqa: BLE001
                    self._drain_spec(pending, sink, clock)
                    self._fail_entry(entry, e)
                    continue
            if coll is None:
                # Serial fallback. The engine drains its own ring before a
                # serial resolve, but draining HERE delivers the pending
                # replies first — reply order stays version order.
                self._drain_spec(pending, sink, clock)
                self._serial_entry(entry, sink, clock)
                continue
            if sink is not None:
                n = max(1, len(txns))
                eng_s = (clock() - t_eng) + self.dispatch_cost_s
                pack_s = rec.get("host_pack")
                if pack_s is not None:
                    sink.stage_tick("host_pack", pack_s, n=n)
                    eng_s = max(0.0, eng_s - pack_s)
                # Interior of device_dispatch: the speculative dispatch
                # half (reconcile is ticked at collect). Sub-stage
                # sibling of wave_level — both price within the engine
                # bracket without double-counting the stage itself.
                sink.stage_tick("spec_resolve", eng_s, n=n, version=version)
                sink.stage_tick("device_dispatch", eng_s, n=n)
            pending.append((entry, coll))
        self._drain_spec(pending, sink, clock)

    def _drain_spec(self, pending: list, sink, clock) -> None:
        """Phase B: collect speculated windows in version order. Repairs
        happen inside the engine's reconcile; this side prices the wait
        (``reconcile`` sub-stage), applies the overflow fail-safe to
        windows now known to have resolved against possibly-truncated
        history, and feeds the per-window repair outcome to the
        coalescer's mis-speculation EWMA (the ratekeeper-facing clamp)."""
        while pending:
            entry, coll = pending.pop(0)
            version, txns = entry.version, entry.txns
            oldest = entry.oldest_version
            if oldest is None:
                oldest = max(0, version - MVCC_WINDOW_VERSIONS)
            rep0 = self._spec_repaired()
            t0 = clock() if sink is not None else 0.0
            try:
                verdicts = coll()
            except BaseException as e:  # noqa: BLE001
                self._fail_entry(entry, e)
                continue
            fail_safe = False
            wave = getattr(self.cs, "last_wave", None)
            overflow = self._post_resolve_check(version)
            if overflow or (self._unsafe_until is not None
                            and oldest <= self._unsafe_until):
                # True overflow surfaced while this (or an older in-ring)
                # window was in flight: every window that resolved before
                # the flag was observed may have missed conflicts against
                # truncated history — reject wholesale, same contract as
                # the chunked serial path.
                verdicts = [Verdict.CONFLICT] * len(txns)
                self.txns_rejected_fail_safe += len(txns)
                fail_safe = True
                wave = None
            coal = getattr(self.sched, "coalescer", None)
            if coal is not None and hasattr(coal, "note_misspec"):
                coal.note_misspec(self._spec_repaired() > rep0)
            reply = self._finish_entry(version, txns, verdicts, fail_safe,
                                       wave, entry.rows)
            if sink is not None:
                n = max(1, len(txns))
                rec_s = clock() - t0
                sink.stage_tick("reconcile", rec_s, n=n, version=version)
                sink.stage_tick("device_dispatch", rec_s, n=n)
            self._send_entry(entry, reply)

    def _spec_repaired(self) -> int:
        fn = getattr(self.cs, "spec_metrics", None)
        return int(fn()["spec_repaired"]) if fn is not None else 0

    def _trim_replies(self) -> None:
        if len(self._replies) > self.REPLY_CACHE_SIZE:
            del self._replies[min(self._replies)]

    def _resolve_entry(
        self, entry: _QueuedBatch
    ) -> tuple[
        list[Verdict], dict[int, list[tuple[bytes, bytes]]], bool,
        "list[int] | None",
    ]:
        version, txns, oldest_version = (
            entry.version, entry.txns, entry.oldest_version,
        )
        if oldest_version is None:
            oldest_version = max(0, version - MVCC_WINDOW_VERSIONS)
        wave: list[int] | None = None
        fail_safe = self._should_fail_safe(
            entry.rows[0], version, oldest_version)
        if fail_safe:
            # Conflict-everything: rejected txns paint nothing, so history
            # stops growing; advance() still slides the GC floor so expired
            # segments compact out and headroom recovers. Spurious aborts,
            # never missed conflicts.
            self.cs.advance(version, oldest_version)
            self._headroom = self.cs.headroom()
            verdicts = [Verdict.CONFLICT] * len(txns)
            self.txns_rejected_fail_safe += len(txns)
        else:
            verdicts = self.cs.resolve(txns, version, oldest_version)
            wave = getattr(self.cs, "last_wave", None)
            if self._post_resolve_check(version):
                # True overflow DURING this batch: chunked resolves paint
                # earlier chunks before later ones resolve, so post-overflow
                # chunks may have missed conflicts — reject the whole batch.
                verdicts = [Verdict.CONFLICT] * len(txns)
                self.txns_rejected_fail_safe += len(txns)
                fail_safe = True
                # The engine's schedule died with its verdicts: a wave
                # for a rejected batch would skew the attribution
                # counters below and invite a caller to reorder it.
                wave = None
        with self._stage("resolve_post", version):
            return self._finish_entry(version, txns, verdicts, fail_safe,
                                      wave, entry.rows)

    def _finish_entry(
        self, version: int, txns: list, verdicts: list[Verdict],
        fail_safe: bool, wave: "list[int] | None", rows: tuple[int, int],
    ) -> tuple[
        list[Verdict], dict[int, list[tuple[bytes, bytes]]], bool,
        "list[int] | None",
    ]:
        """Post-verdict bookkeeping shared by the serial and speculative
        paths: conflicting-range reporting, hot-range and admission feeds,
        wave attribution, throughput counters. Returns the reply tuple."""
        # Conflicting read ranges for txns that asked (reference: the
        # reply's conflictingKRIndices). Engines that track exact ranges
        # (oracle) report them; others degrade to the conservative
        # superset of all the txn's read ranges.
        exact = None if fail_safe else getattr(self.cs, "last_conflicting", None)
        conflicting: dict[int, list[tuple[bytes, bytes]]] = {}
        for i, (t, v) in enumerate(zip(txns, verdicts)):
            if v != Verdict.CONFLICT:
                continue
            ranges = exact.get(i) if exact is not None else None
            if ranges is None:
                ranges = [r for r in t.read_ranges if not r.empty]
            pairs = [(r.begin, r.end) for r in ranges]
            # Hot-range loss statistics (repair subsystem): every REAL
            # loss is recorded, reporting-opt-in or not; fail-safe
            # rejections are spurious and would poison the sketch.
            if not fail_safe:
                self.hot_ranges.record(pairs)
            if t.report_conflicting_keys:
                conflicting[i] = pairs
        if not fail_safe:
            self.txns_conflicted += sum(
                1 for v in verdicts if v == Verdict.CONFLICT
            )
            if self.admission_filter is not None:
                # Accepted write sets feed the recent-writes filter at
                # THIS batch's commit version (begin keys; wide ranges
                # degrade to their begin key — under-detection only, the
                # admission tiers tolerate it by construction).
                keys = [
                    bytes(w.begin)
                    for t, v in zip(txns, verdicts)
                    if v == Verdict.COMMITTED
                    for w in t.write_ranges if not w.empty
                ]
                self.admission_filter.record(keys, version)
        if wave is not None:
            # Attribution counters (see __init__): a committed txn past
            # its chunk's first wave was REORDERED behind a same-window
            # predecessor it would have raced (or lost to) under
            # sequential order. Engines publishing a wave schedule
            # publish ``last_reordered`` beside it, counted against RAW
            # per-chunk levels — recomputing from the published schedule
            # here would miscount later chunks' wave-0 txns as reordered
            # (its cross-chunk offsets exist only to keep the schedule
            # coherent), so a missing counter is an engine bug and loud.
            self.txns_reordered += self.cs.last_reordered
            self.txns_cycle_aborted += sum(
                1 for lv in wave if lv == WAVE_LEVEL_CYCLE
            )
        self._count_resolved(txns, rows)
        return (verdicts, conflicting, fail_safe, wave)

    # -- history-capacity fail-safe -----------------------------------------

    def _should_fail_safe(
        self, n_rows: int, version: int, oldest_version: int
    ) -> bool:
        """True → this batch must be rejected wholesale (all CONFLICT).

        Two triggers:
        - Proactive headroom check: resolving n_rows padded rows (one a
          transaction unless it is wide, _txn_rows) can add at most
          ``cs.worst_case_growth(n_rows)`` boundary slots; if the cached
          headroom (refreshed after every engine touch, so no extra device
          sync here) can't absorb that, painting could truncate history.
        - Unsafe window after a true overflow (belt and braces — should be
          unreachable with the proactive check): history painted at
          versions ≤ the overflow version may have been dropped, so every
          batch is rejected until the MVCC floor passes that version and
          the lost history would have expired anyway.
        """
        if not hasattr(self.cs, "headroom"):
            return False  # unbounded engines (oracle, C++ skiplist)
        if self._unsafe_until is not None:
            if oldest_version > self._unsafe_until:
                self._unsafe_until = None
                trace(self.loop).event(
                    "ResolverOverflowWindowExpired", version=version
                )
            else:
                return True
        if self._headroom is None:
            self._headroom = self.cs.headroom()
        needed = self.cs.worst_case_growth(n_rows)
        engaged = self._headroom < needed
        # Episode tracking with hysteresis: the per-batch decision above is
        # the correctness gate (an empty batch is always safe to resolve),
        # but engage/release trace events and the status flag follow the
        # EPISODE — released only once headroom recovers past the largest
        # demand seen — so interleaved idle batches don't flap WARN spam.
        if engaged:
            self._release_at = max(getattr(self, "_release_at", 0), needed)
            if not self._fail_safe_on:
                self._fail_safe_on = True
                trace(self.loop).event(
                    "ResolverFailSafeEngaged", Severity.WARN_ALWAYS,
                    headroom=self._headroom, needed=needed, version=version,
                )
        elif self._fail_safe_on and self._headroom >= self._release_at:
            self._fail_safe_on = False
            self._release_at = 0
            trace(self.loop).event(
                "ResolverFailSafeReleased", headroom=self._headroom,
                version=version,
            )
        return engaged

    def _post_resolve_check(self, version: int) -> bool:
        """Refresh cached headroom; detect true overflow (history truncated
        on device). Returns True iff overflow fired during this batch — the
        caller rejects the batch (chunked resolves mean later chunks saw
        possibly-truncated history) and the unsafe window rejects everything
        after it until the MVCC floor passes this version."""
        if not hasattr(self.cs, "headroom"):
            return False
        with self._stage("headroom_sync", version):
            # Two device reads after the verdicts: a second round trip.
            self._headroom = self.cs.headroom()
            overflowed = self.cs.overflowed
        if not overflowed:
            return False
        self.overflow_events += 1
        self._unsafe_until = version
        self.cs.clear_overflow()
        trace(self.loop).event(
            "ResolverHistoryOverflow", Severity.ERROR,
            version=version, headroom=self._headroom,
        )
        return True

    @rpc
    async def admission_delta(
        self, since_seq: int = 0
    ) -> tuple[int, list[tuple[bytes, int]]]:
        """Recent-writes filter delta feed (admission subsystem): (new
        seq, [(write key, commit version), ...]) recorded since the
        caller's last seq. Commit proxies poll this into their local
        probe filters; an empty reply is the steady state. Raises when
        the resolver runs without a filter (admission off) so a
        misconfigured poller fails loudly instead of probing nothing."""
        if self.admission_filter is None:
            raise ValueError("admission filter not enabled on this resolver")
        return self.admission_filter.delta_since(since_seq)

    @property
    def version(self) -> int:
        return self._version

    @rpc
    async def get_metrics(self) -> dict:
        """Status inputs (reference: resolver stats in status json)."""
        return {
            "batches_resolved": self.batches_resolved,
            "txns_resolved": self.txns_resolved,
            # Padded engine rows those transactions took, and how many of
            # them had more ranges than one row's slots (continuation
            # rows; rows a transaction is a window difference of the two).
            "rows_dispatched": self.rows_dispatched,
            "wide_txns": self.wide_txns,
            "ranges_received": self.ranges_received,
            "txns_with_ranges": self.txns_with_ranges,
            "version": self._version,
            "fail_safe_active": self._fail_safe_on
            or self._unsafe_until is not None,
            "overflow_events": self.overflow_events,
            "txns_rejected_fail_safe": self.txns_rejected_fail_safe,
            "resolve_failures": self.resolve_failures,
            # Where the engine's state lives, read off its arrays (None
            # for the host engines: oracle, C++ skiplist).
            "device": (self.cs.device_info()
                       if hasattr(self.cs, "device_info") else None),
            # Wave-commit attribution (reorder-don't-abort engines; both
            # zero under sequential-order resolution) + the exact conflict
            # count they are judged against.
            "txns_reordered": self.txns_reordered,
            "txns_cycle_aborted": self.txns_cycle_aborted,
            "txns_conflicted": self.txns_conflicted,
            # Windows resolved through the role-level global wave
            # protocol (resolve_edges/resolve_apply) — per-shard, so a
            # sharded deployment's status shows every shard exchanging.
            "wave_batches": self.wave_batches,
            # Speculative pipelined resolve (FDB_TPU_SPEC_RESOLVE; all
            # zero on serial engines): dispatched/confirmed/repaired
            # window counts, verdicts flipped by repair re-resolves,
            # version-chain rollbacks, and the CURRENT ring depth — the
            # repaired/dispatched ratio is the mis-speculation rate the
            # ratekeeper clamps speculation depth on (see
            # AdaptiveCoalescer.effective_spec_depth).
            "spec_dispatched": self._spec_stat("spec_dispatched"),
            "spec_confirmed": self._spec_stat("spec_confirmed"),
            "spec_repaired": self._spec_stat("spec_repaired"),
            "spec_flipped": self._spec_stat("spec_flipped"),
            "chain_rolls": self._spec_stat("chain_rolls"),
            "spec_depth": self._spec_stat("spec_depth"),
            "history_headroom": self._headroom,
            "hot_ranges": self.hot_ranges.top(),
            "conflict_losses": self.hot_ranges.losses_recorded,
            # Dispatch-queue backpressure (sched subsystem): the ratekeeper
            # throttles admission on queue_depth before the resolver
            # overflows; status JSON reports the full queue dict.
            "queue_depth": self.sched.queue_depth,
            # Rolling high-water: what the ratekeeper actually throttles
            # on — an instantaneous depth misses spikes shorter than its
            # 0.1s poll (campaign find; see ResolveScheduler._note_depth).
            "queue_depth_hw": self.sched.depth_high_water(),
            "queue": self.sched.metrics(),
            # Recent-writes filter (admission subsystem; None = admission
            # off): recorded counts, rotation, saturation, delta seq.
            "admission_filter": (
                self.admission_filter.metrics()
                if self.admission_filter is not None else None
            ),
            # Engine topology/capacity events (resident/mesh engines; all
            # zero for oracle and cpp): density reshards and forced full
            # repacks surface here so the flight recorder can annotate
            # them on the cluster timeline (pure-counter plane — the
            # recorder turns deltas into `reshard` annotations).
            "engine": {
                "auto_reshards": getattr(self.cs, "auto_reshards", 0),
                "reshard_moved_shards": getattr(
                    self.cs, "reshard_moved_shards", 0),
                "full_repacks": self._engine_dict_stat("full_repacks"),
                "evictions": self._engine_dict_stat("evictions"),
                # WHY the dictionary repacked (the arms of the engine's
                # need_repack; they sum to full_repacks when no tiered
                # fallback fires) and the seconds it spent doing so; the
                # delta's size per dispatch, and the dispatches whose
                # device delta was empty (no new key, or a repack took
                # them in), on which the kernel skips dict_insert; and
                # the programs the process compiled, so a compile inside
                # a window is not read as a stall. Monotonic since boot:
                # read as differences.
                "repacks_delta_overflow": self._engine_dict_stat(
                    "repacks_delta_overflow"),
                "repacks_dict_full": self._engine_dict_stat(
                    "repacks_dict_full"),
                "repacks_frag_due": self._engine_dict_stat(
                    "repacks_frag_due"),
                "repack_s": self._engine_dict_fstat("repack_s"),
                "delta_new_keys": self._engine_dict_stat("delta_new_keys"),
                "dispatches": self._engine_dict_stat("dispatches"),
                "delta_empty_dispatches": self._engine_dict_stat(
                    "delta_empty_dispatches"),
                "compiles": self._engine_dict_stat("compiles"),
                "compile_s": self._engine_dict_fstat("compile_s"),
                # Tiered-dictionary economics (all zero when tiering is
                # off — FDB_TPU_DICT_HOT_CAPACITY unset — or the engine
                # is not resident): obs/doctor's dict_thrash detector
                # reads the promotion/demotion pair; the recorder
                # annotates their deltas like reshard/repack deltas.
                "demotions": self._engine_dict_stat("demotions"),
                "promotions": self._engine_dict_stat("promotions"),
                "cold_tier_keys": self._engine_dict_stat("cold_tier_keys"),
                "dict_hot_occupancy": self._engine_dict_fstat(
                    "dict_hot_occupancy"),
                "demotion_bytes_per_dispatch": self._engine_dict_fstat(
                    "demotion_bytes_per_dispatch"),
            },
        }

    def _spec_stat(self, key: str) -> int:
        """An engine speculation counter (TPUConflictSet.spec_metrics),
        0 for serial engines / speculation off."""
        fn = getattr(self.cs, "spec_metrics", None)
        if fn is None:
            return 0
        return int(fn().get(key, 0))

    def _engine_dict_stat(self, key: str) -> int:
        """A resident-dictionary stat counter (TPUConflictSet.dict_stats
        property), 0 for engines without one / non-resident mode."""
        try:
            stats = getattr(self.cs, "dict_stats", None) or {}
        except Exception:
            return 0
        return int(stats.get(key, 0) or 0)

    def _engine_dict_fstat(self, key: str) -> float:
        """Float-valued dict_stats gauge (occupancy/bytes-per-dispatch),
        0.0 for engines without one / non-resident mode."""
        try:
            stats = getattr(self.cs, "dict_stats", None) or {}
        except Exception:
            return 0.0
        return float(stats.get(key, 0) or 0)
