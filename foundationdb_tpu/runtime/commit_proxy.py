"""Commit proxy: the batch engine of the write path.

Reference: fdbserver/CommitProxyServer.actor.cpp. Client commits queue up;
each batch gets ONE commit version from the sequencer, its conflict ranges
are split across resolvers by keyspace shard, per-resolver verdicts are
ANDed, versionstamped ops are rewritten now that the version is known,
surviving mutations are tagged by storage shard and pushed to every tlog,
and clients get their reply only after the tlogs ack durability. Batches
pipeline: the proxy does not wait for batch N before assembling N+1 — the
(prev_version, version) chain orders them at the resolvers and tlogs.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from foundationdb_tpu.core.errors import (
    AdmissionPreAborted,
    AdmissionShaped,
    CommitUnknownResult,
    DatabaseLocked,
    NotCommitted,
    ProcessKilled,
    TransactionTooOld,
)
from foundationdb_tpu.core.mutations import (
    Mutation,
    MutationType,
    resolve_versionstamps,
)
from foundationdb_tpu.core.types import KeyRange, TxnConflictInfo, Verdict
from foundationdb_tpu.core.wavemesh import clip_ranges
from foundationdb_tpu.obs.span import span_now, span_sink
from foundationdb_tpu.repair.hotrange import HotRangeSketch
from foundationdb_tpu.runtime.backup import BACKUP_TAG
from foundationdb_tpu.runtime.flow import (
    ActorCancelled, BrokenPromise, Loop, Promise, all_of, rpc,
)
from foundationdb_tpu.runtime.shardmap import KeyShardMap
from foundationdb_tpu.runtime.trace import Severity, trace
from foundationdb_tpu.sched.lanes import LaneQueue


@dataclass
class CommitRequest:
    """Reference: CommitTransactionRequest (fdbclient/CommitTransaction.h)."""

    read_version: int
    mutations: list[Mutation] = field(default_factory=list)
    read_ranges: list[KeyRange] = field(default_factory=list)
    write_ranges: list[KeyRange] = field(default_factory=list)
    report_conflicting_keys: bool = False
    # Bypass the database lock (reference: LOCK_AWARE option; DR agents
    # and operator tooling write to a locked database with this set).
    lock_aware: bool = False
    # Tenant authorization token (reference: AUTHORIZATION_TOKEN option):
    # verified by the proxy when the cluster enables authz (runtime/authz).
    token: str | None = None
    # Admission lane (reference: TransactionPriority — SYSTEM_IMMEDIATE /
    # DEFAULT / BATCH): "system" traffic (recovery, system keyspace) is
    # batched ahead of everything, "batch" bulk load is batched last with
    # starvation-free aging (sched/lanes.py).
    priority: str = "default"
    # Admission-control opt-out (admission subsystem; client option
    # admission_no_shape): fail with AdmissionShaped instead of queueing
    # this commit into the serializing shaped lane.
    admission_no_shape: bool = False
    # Consecutive pre-aborts this logical transaction has already eaten
    # (client-reported): at/above the policy's ceiling the proxy admits
    # the txn anyway, so a persistent loser degrades to the CANONICAL
    # conflict path (resolver loser report → repair engine / retry
    # ladder) instead of spinning on cheap rejections forever.
    admission_attempts: int = 0
    # Trace context (obs subsystem): the sampled txn's trace id, or None
    # (unsampled — the overwhelming default). Presence asks the proxy to
    # stamp its commit-path stage spans onto the reply.
    trace: "int | None" = None


@dataclass(frozen=True)
class CommitResult:
    version: int
    batch_order: int  # with `version`, determines the txn's versionstamp
    # Stage spans for a SAMPLED commit (obs subsystem): tuple of
    # (stage, start, dur) in proxy-clock seconds, plus the proxy_total
    # envelope — the client assembles its exact per-txn breakdown from
    # these. None (and absent on the wire) for unsampled txns.
    spans: "tuple | None" = None


class CommitProxy:
    BATCH_INTERVAL = 0.002
    MAX_BATCH = 512
    # Idle cadence: with no client commits, proxies still push EMPTY
    # batches through sequencer→resolver→tlogs (reference: proxies commit
    # empty batches at COMMIT_TRANSACTION_BATCH interval). This is what
    # keeps versions flowing when the cluster is quiet: tlog/storage
    # versions (and so MVCC GC floors and GRV freshness) advance smoothly
    # instead of jumping a whole window at the next sparse commit — a
    # 10s-interval committer (TimeKeeper) against a ~10s MVCC window
    # otherwise expires every fresh read version the moment the next
    # batch lands.
    IDLE_BATCH_INTERVAL = 0.25

    def __init__(
        self,
        loop: Loop,
        sequencer_ep,
        resolver_eps: list,
        resolver_map: KeyShardMap,
        tlog_eps: list,
        storage_map: KeyShardMap,
        controller_ep=None,
        epoch: int = 1,
        authz=None,
        tenant_mirror=None,
        admission=None,
        wave_commit: bool = False,
        wave_batch_limit: "int | None" = None,
    ):
        assert resolver_map.n_shards == len(resolver_eps)
        self.loop = loop
        self.sequencer = sequencer_ep
        self.resolvers = resolver_eps
        self.resolver_map = resolver_map
        self.tlogs = tlog_eps
        self.storage_map = storage_map
        self.controller = controller_ep
        self.epoch = epoch
        # Continuous backup: when enabled, every committed mutation is ALSO
        # tagged with the backup pseudo-tag so the backup worker can pull
        # the commit stream off the tlogs (reference: proxies write backup
        # mutations when backup/DR is active; runtime/backup.py).
        self.backup_enabled = False
        # Database lock (reference: error 1038): set by DR switchover /
        # operator tooling; the recruiter re-applies it across recoveries.
        self.locked = False
        # Tenant authz (runtime/authz.TokenAuthority) — None = authz off,
        # every commit trusted (the pre-7.x reference default).
        self.authz = authz
        # Live tenant-map view for TENANT-BOUND tokens (authz.check_commit
        # live_tenants; reference: proxies track the tenant map and check
        # token tenant ids against it). An authz.TenantMapMirror shared
        # with (or mirroring the one on) the storage servers; its view is
        # None until the first refresh — tenant-bound tokens fail CLOSED
        # in that window.
        self.tenant_mirror = tenant_mirror
        # Aggregated hot-range conflict statistics (repair subsystem):
        # the resolvers' per-shard loss reports, ANDed into combined
        # verdicts here, feed one decayed sketch per proxy — exported in
        # get_metrics / status JSON and piggybacked (with the failed
        # batch version) on every NotCommitted so the client repair
        # engine can re-read only the losers and back off on hot ranges.
        self.hot_ranges = HotRangeSketch(lambda: loop.now)
        # Priority-laned commit admission (sched subsystem): batch
        # formation drains system → default → batch, so a bulk load's
        # backlog never delays system traffic by more than the window
        # already being formed; aged batch entries promote to default
        # (starvation-free).
        self._queue: LaneQueue = LaneQueue(lambda: loop.now)
        # Admission policy (admission subsystem; None = admission off):
        # probes every request's read set at batch formation. Proven
        # losers pre-abort on the spot; likely losers park in the
        # serializing shaped lane below and are CO-SCHEDULED into one
        # dispatch window (same commit version) when the shape window
        # elapses — contenders land where a wave-commit resolver reorders
        # them instead of aborting, and the rest lose at most one window.
        self.admission = admission
        if admission is not None and admission.hot_ranges is None:
            # Wide-range shaping consults the proxy's own aggregated
            # hot-range sketch (the repair subsystem's loss odds).
            admission.hot_ranges = self.hot_ranges
        self._shaped: list[tuple[CommitRequest, Promise]] = []
        self._shaped_since = loop.now  # head-of-lane arrival (flush clock)
        # Batches being processed, by version (retire() answers them).
        self._inflight: dict[int, list[tuple[CommitRequest, Promise]]] = {}
        # Batches popped from _queue but not yet in _inflight (awaiting
        # their commit version): quiesce() must see them or a batch could
        # vanish from both sets mid-await and slip past a DR switchover.
        self._admitting = 0
        # The self-clocked batch (run()): what this proxy's last batch
        # took from its formation to its verdicts, and the mutations its
        # queue holds.
        self._resolve_s = 0.0
        self._queued_mutations = 0
        self.txns_committed = 0
        self.txns_conflicted = 0
        # Wave commit (reorder-don't-abort resolvers): with ONE resolver
        # the schedule rides the ordinary resolve reply; with several,
        # this proxy runs the two-phase global edge exchange
        # (resolve_edges → OR-reduce → resolve_apply; core/wavemesh) and
        # cross-checks that every shard reported the byte-identical
        # schedule. False = sequential AND-combine, wave replies ignored.
        self.wave_commit = bool(wave_commit)
        self.wave_exchanges = 0  # batches resolved via the global protocol
        # One exchange carries ONE schedule domain: an engine chunks
        # oversized windows and serializes them through the history,
        # which a one-shot edge exchange cannot reproduce — so wave
        # batches are capped at the engine chunk (the resolver raises
        # loudly past it; None = engine unchunked, e.g. the oracle).
        self.wave_batch_limit = wave_batch_limit
        # Highest batch version this proxy has seen durable on ALL tlogs;
        # piggybacked on pushes so storage can bound its GC floor
        # (reference: knownCommittedVersion).
        self._known_committed = 0
        # Bounds told to the tlogs at the acknowledgement (_notify_committed)
        self.commit_notifies_sent = 0

    # -- client face ----------------------------------------------------------

    @rpc
    async def commit(self, req: CommitRequest) -> CommitResult:
        if span_sink(self.loop) is not None:
            # Commit-path tracing (obs subsystem): stamp the arrival so
            # lane-queue time is attributable. Stamped for EVERY request
            # while tracing is armed (one attr write); all heavier work
            # is gated on req.trace (sampled txns only).
            req._obs_arrival = span_now(self.loop)
        p = Promise()
        self._queue.push((req, p), getattr(req, "priority", "default"))
        self._queued_mutations += len(req.mutations)
        return await p.future

    @rpc
    async def set_backup_enabled(self, enabled: bool) -> None:
        self.backup_enabled = enabled

    @rpc
    async def get_backup_enabled(self) -> bool:
        """Stream-continuity probe: a reconnecting DR/backup agent asks
        whether dual-tagging stayed on since its predecessor (the resume
        gate — a lapse means versions are missing from the tlog stream
        and a full re-bootstrap is required)."""
        return self.backup_enabled

    @rpc
    async def set_locked(self, locked: bool) -> None:
        self.locked = locked

    @rpc
    async def get_locked(self) -> bool:
        """Operator/DR probe: is the database lock in force here?"""
        return self.locked

    @rpc
    async def get_metrics(self) -> dict:
        """Status inputs (reference: commit proxy stats in status json)."""
        return {
            "txns_committed": self.txns_committed,
            "txns_conflicted": self.txns_conflicted,
            "commit_notifies_sent": getattr(self, "commit_notifies_sent", 0),
            # Batches resolved through the global wave edge exchange
            # (multi-resolver wave commit; 0 on every other config).
            # getattr: metric-harness stubs build proxies piecemeal.
            "wave_exchanges": getattr(self, "wave_exchanges", 0),
            "queued": len(self._queue),
            "lanes": self._queue.depths(),
            "lane_promotions": self._queue.promoted,
            "hot_ranges": self.hot_ranges.top(),
            "conflict_losses": self.hot_ranges.losses_recorded,
            # Admission subsystem (None = off): probe/shape/preabort
            # counters, false-positive accounting, lane occupancy, and
            # the filter saturation signal the ratekeeper polls.
            # getattr: metric-harness stubs build proxies piecemeal.
            "admission": (
                {**self.admission.metrics(),
                 "shaped_depth": len(getattr(self, "_shaped", ()))}
                if getattr(self, "admission", None) is not None else None
            ),
        }

    # -- batch engine ---------------------------------------------------------

    @property
    def live_tenants(self):
        return self.tenant_mirror.view if self.tenant_mirror else None

    # Serializing shaped lane: likely losers park here until the window
    # elapses (or the lane is deep), then ALL of them ride one batch —
    # deliberate co-scheduling (see __init__). The window bounds shaping
    # delay to a few batch ticks.
    SHAPE_WINDOW_S = 0.004
    SHAPE_MAX = 64
    # Cross-proxy filter feed: poll each resolver's admission_delta so
    # this proxy's probe filter also sees writes committed through PEER
    # proxies (its own batches self-feed with zero lag in _process_inner).
    ADMISSION_POLL_INTERVAL = 0.05

    def _admission_on(self) -> bool:
        return self.admission is not None and self.admission.enabled

    def _shape_flush_due(self) -> bool:
        """The lane flushes when its HEAD has parked a full shape window
        (so the first shaped txn of a burst always waits out the
        co-scheduling window collecting its contenders — the clock is
        the head's arrival, not the last flush) or the lane is deep."""
        return bool(self._shaped) and (
            self.loop.now - self._shaped_since >= self.SHAPE_WINDOW_S
            or len(self._shaped) >= self.SHAPE_MAX
        )

    def _held(self, since_last: float) -> bool:
        """Self-clocked batching: is the queue kept for the next tick?

        A resolver takes one batch at a time and a batch costs it much
        the same whatever it holds, so a batch every BATCH_INTERVAL
        whatever is outstanding only queues near-empty batches behind
        each other, and a commit waits for all of them. The interval is
        what the LAST batch took from its formation to its verdicts
        (reference: the proxy's batch interval follows its commit
        latency): commits that arrive meanwhile ride ONE batch. The wait
        is for company, so it shrinks with what the queued commits bring
        themselves: a queue averaging w mutations a commit is kept 1/w of
        the interval, and a bulk transaction (a load's hundred sets)
        leaves at the plain cadence. Never kept: a system-priority commit
        (it leaves on the next tick, as the lanes promise), a shaped lane
        that is due, a queue that is a full batch already. A batch slower
        than its predecessor does not hold the next one back (a stalled
        resolver's backlog shows in ITS queue, where the ratekeeper
        looks). Push and reply are not waited for: the next batch
        resolves while the last is made durable."""
        n = len(self._queue)
        # (max: a commit of conflict ranges alone still counts as one)
        work = max(self._queued_mutations, n)
        return (0 < n < self.MAX_BATCH
                and since_last * work < self._resolve_s * n
                and not self._queue.depths()["system"]
                and not self._shape_flush_due())

    async def run(self) -> None:
        last_batch = self.loop.now
        if self._admission_on():
            self.loop.spawn(self._admission_poller(),
                            name="commit_proxy.admission_poller")
        while True:
            await self.loop.sleep(self.BATCH_INTERVAL)
            if self._held(self.loop.now - last_batch):
                continue
            if not len(self._queue) and not self._shape_flush_due():
                if self.loop.now - last_batch < self.IDLE_BATCH_INTERVAL:
                    continue
                batch = []  # idle: empty batch keeps the version chain hot
            else:
                # BUGGIFY: degenerate one-txn batches exercise the version
                # chain/reply paths at maximum batch rate (reference:
                # BUGGIFY'd COMMIT_TRANSACTION_BATCH_COUNT_MAX).
                max_batch = 1 if self.loop.buggify("commit_proxy.tiny_batch") \
                    else self.MAX_BATCH
                if (self.wave_commit and len(self.resolvers) > 1
                        and self.wave_batch_limit):
                    max_batch = min(max_batch, self.wave_batch_limit)
                # Lane-ordered drain: system first, then default, then
                # batch (with aging) — a system txn is never queued behind
                # more than the window already forming.
                batch = self._queue.pop(max_batch)
                self._queued_mutations -= sum(
                    len(req.mutations) for req, _p in batch)
            if batch and span_sink(self.loop) is not None:
                # Stage stamp: batch formation popped these requests NOW.
                # Shaped requests keep their FIRST pop (the admission
                # gate re-stamps at flush so the park window is never
                # double-counted into batch_form).
                t_pop = span_now(self.loop)
                for req, _p in batch:
                    if not hasattr(req, "_obs_pop"):
                        req._obs_pop = t_pop
            if self.locked and batch:
                # Database locked (reference error 1038, checked at the
                # proxy): reject non-lock-aware commits; DR/operator txns
                # with LOCK_AWARE pass through.
                passed = []
                for req, p in batch:
                    if req.lock_aware:
                        passed.append((req, p))
                    else:
                        p.fail(DatabaseLocked("database is locked"))
                batch = passed
            if self.authz is not None and batch:
                # Tenant authorization (reference: TenantAuthorizer at the
                # commit boundary): every write must lie inside a prefix
                # the request's token authorizes; tenant-bound tokens are
                # additionally checked against the live tenant map.
                passed = []
                for req, p in batch:
                    try:
                        self.authz.check_commit(req, self.loop.wall_now,
                                                live_tenants=self.live_tenants)
                        passed.append((req, p))
                    except Exception as e:  # PermissionDenied
                        p.fail(e)
                batch = passed
            if self._admission_on():
                # After lock/authz (a denied commit must not burn a probe)
                # and BEFORE the sequencer trip: pre-aborted txns never
                # consume a version or a resolver slot.
                batch = self._admission_gate(batch)
            last_batch = self.loop.now
            # One version per batch; fetched in the batcher (not the spawned
            # worker) so batches acquire chain positions in queue order.
            self._admitting += 1
            try:
                prev_version, version = await self.sequencer.get_commit_version()
            except Exception:
                for _req, p in batch:
                    p.fail(CommitUnknownResult("sequencer unreachable"))
                continue
            except ActorCancelled:
                # Retired while waiting (a recovery recruits the next
                # generation's proxy): this batch has left the queue that
                # the recruiter fails, nothing of it was sent anywhere,
                # and unanswered its clients would wait for ever over a
                # healthy connection.
                for _req, p in batch:
                    p.fail(ProcessKilled("proxy retired: resubmit"))
                raise
            finally:
                self._admitting -= 1
            # Into _inflight HERE (not in the spawned task, which may not
            # have run yet when quiesce() samples).
            self._inflight[version] = batch
            self.loop.spawn(
                self._process(batch, prev_version, version, last_batch),
                name=f"commit_batch@{version}",
            )

    def _admission_gate(
        self, batch: list[tuple[CommitRequest, Promise]]
    ) -> list[tuple[CommitRequest, Promise]]:
        """Probe each request at admission; returns the batch to dispatch
        (admitted + any shaped-lane flush, shaped block CONTIGUOUS at the
        end so the whole contention neighborhood shares one window)."""
        passed: list[tuple[CommitRequest, Promise]] = []
        for req, p in batch:
            if getattr(req, "_admission_shaped", False):
                # Already shaped once (this is its flush ride): admit.
                passed.append((req, p))
                continue
            d = self.admission.decide(
                req.read_ranges, req.read_version,
                getattr(req, "priority", "default"),
                attempts=getattr(req, "admission_attempts", 0),
            )
            if d.action == "preabort":
                feed = [(r.begin, r.end)
                        for r in req.read_ranges if not r.empty]
                # A proven loss is real contention evidence: feed the
                # sketch so backoff odds keep flowing even when
                # pre-aborts replace resolver-reported conflicts.
                self.hot_ranges.record(feed)
                p.fail(AdmissionPreAborted(
                    "admission: read set overlaps a newer committed write",
                    hot_ranges=self.hot_ranges.scores(feed),
                    confirm_version=d.confirm_version,
                ))
                continue
            if d.action == "shape":
                if getattr(req, "admission_no_shape", False):
                    # Never parked: reverse the shape counters — "shaped"
                    # counts txns that actually rode the lane, or the
                    # false-positive denominator (and the campaign's
                    # shaped gate) would count rejections that shaped
                    # nothing.
                    self.admission.reclassify_no_shape(d)
                    p.fail(AdmissionShaped(
                        "admission: likely loser; shaped lane refused by "
                        "admission_no_shape"))
                    continue
                req._admission_shaped = True
                if hasattr(req, "_obs_arrival"):
                    # traced: the park begins
                    req._obs_park0 = span_now(self.loop)
                if not self._shaped:
                    self._shaped_since = self.loop.now  # new lane head
                self._shaped.append((req, p))
                continue
            passed.append((req, p))
        if self._shape_flush_due():
            flush, self._shaped = self._shaped, []
            for req, p in flush:
                # Exact-tier recheck at the flush ride: a loss that became
                # provable while the txn parked pre-aborts here instead of
                # burning its dispatch (sound — shadow-confirmed only).
                cv = self.admission.recheck_preabort(
                    req.read_ranges, req.read_version)
                if cv is not None:
                    feed = [(r.begin, r.end)
                            for r in req.read_ranges if not r.empty]
                    self.hot_ranges.record(feed)
                    p.fail(AdmissionPreAborted(
                        "admission: loss proven while shaped",
                        hot_ranges=self.hot_ranges.scores(feed),
                        confirm_version=cv,
                    ))
                    continue
                if hasattr(req, "_obs_park0"):
                    # Stage stamp: the park window closes here, and the
                    # pop is re-anchored to the flush so batch_form
                    # measures flush->version, not park-inclusive.
                    now = span_now(self.loop)
                    req._obs_park = now - req._obs_park0
                    req._obs_pop = now
                passed.append((req, p))
        return passed

    async def _admission_poller(self) -> None:
        """Pull resolver recent-writes deltas into the local probe filter
        (idempotent with the proxy's own-batch self-feed by design).

        Transient unreachability is retried silently; a resolver that
        answers "admission filter not enabled" is MISCONFIGURED (this
        proxy is armed, that resolver is not — per-process env drift in
        a deployment) and is reported loudly once, then dropped from the
        poll set: its feed can never materialize, and an eternal silent
        retry would quietly reduce pre-abort/shape coverage."""
        seqs = {i: 0 for i in range(len(self.resolvers))}
        dead: set[int] = set()
        while True:
            await self.loop.sleep(self.ADMISSION_POLL_INTERVAL)
            for i, r in enumerate(self.resolvers):
                if i in dead:
                    continue
                try:
                    seqs[i], entries = await r.admission_delta(seqs[i])
                except Exception as e:
                    if "admission filter not enabled" in str(e):
                        dead.add(i)
                        trace(self.loop).event(
                            "AdmissionDeltaMisconfigured",
                            Severity.WARN_ALWAYS, resolver=i,
                        )
                    continue  # unreachable: next poll
                if entries:
                    self.admission.filter.apply_delta(entries)

    # A batch stuck this long means the version chain is wedged (a gap from
    # lost pushes, or a peer's batch never arriving) — a state heartbeats
    # can't see because every process is alive. Ask the controller to force
    # recovery; the new generation retires this proxy and unwinds the batch.
    # Must exceed _with_retry's worst case (RPC_RETRIES × (failure-detection
    # delay + backoff) ≈ 4.4s) so the ladder's tail is reachable: transient
    # blips resolve by retry, only longer outages pay a generation change.
    WEDGE_TIMEOUT = 6.0

    async def _process(
        self,
        batch: list[tuple[CommitRequest, Promise]],
        prev_version: int,
        version: int,
        formed_at: float,
    ) -> None:
        watchdog = self.loop.spawn(
            self._wedge_watchdog(version), name=f"wedge_watchdog@{version}"
        )
        self._inflight[version] = batch
        try:
            await self._process_inner(batch, prev_version, version,
                                      formed_at)
        finally:
            self._inflight.pop(version, None)
            watchdog.cancel()

    def retire(self, reason: str) -> None:
        """Answer everything this proxy still holds. Its batch loop is
        being cancelled (a recovery recruits the next generation's proxy,
        or a stand-down) and nothing else ever will: each client would
        wait for ever over a healthy connection. What is queued or parked
        was sent nowhere: retryable. A batch in _process may have reached
        the logs, and may wait on a resolver or tlog of the old generation
        that never replies (parked behind a gap in its chain when it was
        replaced): commit_unknown_result, which is the truth; whatever
        that batch completes with later is dropped."""
        for _req, p in self._queue.drain() + self._shaped:
            p.fail(ProcessKilled(reason))
        self._shaped = []
        self._queued_mutations = 0
        for batch in self._inflight.values():
            for _req, p in batch:
                p.fail(CommitUnknownResult(reason))

    @rpc
    async def quiesce(self) -> None:
        """Resolve once every batch admitted before this call has fully
        completed (queued + in-flight drained). DR switchover uses this
        after locking: a batch that passed the lock check pre-lock is
        still entitled to its backup tagging, so dual-tagging must stay
        on until nothing admitted remains in flight."""
        while (len(self._queue) or self._shaped or self._inflight
               or self._admitting):
            await self.loop.sleep(self.BATCH_INTERVAL)

    async def _wedge_watchdog(self, version: int) -> None:
        await self.loop.sleep(self.WEDGE_TIMEOUT)
        trace(self.loop).event("CommitBatchWedged", Severity.WARN_ALWAYS,
                               version=version, timeout=self.WEDGE_TIMEOUT)
        if self.controller is not None:
            await self._request_recovery(f"commit batch@{version} wedged")

    async def _request_recovery(self, reason: str) -> None:
        try:
            await self.controller.request_recovery(self.epoch, reason)
        except Exception:
            pass  # controller unreachable: the heartbeat sweep is the backstop

    async def _process_inner(
        self,
        batch: list[tuple[CommitRequest, Promise]],
        prev_version: int,
        version: int,
        formed_at: float,
    ) -> None:
        sink = span_sink(self.loop)
        t_version = span_now(self.loop)  # commit version in hand as of entry
        t_resolved = t_assembled = t_pushed = t_version
        try:
            verdicts, conflicting, fail_safe, wave = await self._resolve(
                batch, prev_version, version
            )
            # The batcher's clock (run()). Never past the idle cadence: a
            # batch that sat out a resolver's stall must not make the
            # commits that come after the stall wait it out again.
            self._resolve_s = min(self.loop.now - formed_at,
                                  self.IDLE_BATCH_INTERVAL)
            t_resolved = span_now(self.loop)
            tagged = self._assemble(batch, verdicts, version, wave)
            t_assembled = span_now(self.loop)
            kc = self._known_committed
            if self.loop.buggify("commit_proxy.slow_push"):
                # Delayed push: later batches' pushes overtake ours at the
                # tlogs, exercising their version-chain parking.
                await self.loop.sleep(self.loop.rng.uniform(0, 0.05))
            await all_of(
                [
                    self.loop.spawn(
                        self._with_retry(
                            # epoch stamps the push for the tlog's
                            # generation fence: a retired proxy's push
                            # must FAIL at a newer generation's tlog,
                            # never false-ack as a duplicate.
                            lambda t=t: t.push(prev_version, version, tagged,
                                               kc, epoch=self.epoch)
                        ),
                        name=f"tlog_push@{version}",
                    )
                    for t in self.tlogs
                ]
            )
            t_pushed = span_now(self.loop)  # every tlog acked its fsync
            self._known_committed = max(self._known_committed, version)
            # Started BEFORE the sequencer hears of `version` (a read
            # version that names it finds the tlogs already told) and
            # awaited by nobody: the reply to the client does not wait.
            self.loop.spawn(self._notify_committed(self._known_committed),
                            name=f"commit_notify@{version}")
            await self.sequencer.report_committed(version)
        except Exception:
            # Resolver/tlog unreachable or locked mid-batch: the batch's fate
            # is genuinely unknown (it may yet reach disk) — that is exactly
            # commit_unknown_result, and clients retry idempotently.
            for _req, p in batch:
                p.fail(CommitUnknownResult(f"batch@{version} failed"))
            # Surviving the whole retry ladder means a generation member was
            # continuously unreachable (or locked) for seconds — and the
            # failed batch may have left a gap in the tlog version chain.
            # Treat it as a role failure and force recovery (reference: the
            # master marks a tlog failed on push failure and recovers).
            if self.controller is not None:
                self.loop.spawn(
                    self._request_recovery(f"batch@{version} failed its push/resolve"),
                    name=f"request_recovery@{version}",
                )
            return
        if self._admission_on() and not fail_safe:
            # Zero-lag local filter feed: this proxy's own accepted write
            # sets enter its probe filter at the batch version the moment
            # the verdicts land (peer proxies' writes arrive via the
            # resolver delta poll). Shaped outcome accounting rides the
            # same pass: a shaped txn that committed is a measured false
            # positive (shaping never changes verdicts, only scheduling).
            # Fail-safe batches are skipped on both counts — their
            # verdicts are spurious capacity rejections.
            accepted = []
            for (req, _p), v in zip(batch, verdicts):
                if getattr(req, "_admission_shaped", False):
                    self.admission.note_shaped_outcome(v)
                if v == Verdict.COMMITTED:
                    accepted.extend(req.write_ranges)
            self.admission.feed_accepted(accepted, version)
        t_reply = span_now(self.loop)
        for i, ((req, p), v) in enumerate(zip(batch, verdicts)):
            if v == Verdict.COMMITTED:
                self.txns_committed += 1
                spans = None
                if (sink is not None and req.trace is not None
                        and hasattr(req, "_obs_arrival")):
                    spans = self._obs_spans(
                        req, t_version, t_resolved, t_assembled, t_pushed,
                        t_reply)
                p.send(CommitResult(version, i, spans))
            elif v == Verdict.TOO_OLD:
                p.fail(TransactionTooOld())
            else:
                self.txns_conflicted += 1
                ranges = conflicting.get(i)
                # Feed the aggregate sketch with the loser ranges (exact
                # when a resolver reported them, else the txn's read set)
                # — but NOT for fail-safe batches: those rejections are
                # spurious and would score uncontended ranges hot (the
                # resolver-side sketch skips them for the same reason).
                feed = ranges if ranges is not None else [
                    (r.begin, r.end) for r in req.read_ranges if not r.empty
                ]
                if not fail_safe:
                    self.hot_ranges.record(feed)
                p.fail(NotCommitted(
                    conflicting_ranges=ranges,
                    # No fail_version on fail-safe batches: the rejection
                    # is capacity pressure, not contention, and a repair
                    # client re-submitting instantly (repair skips the
                    # exponential backoff) would amplify load on exactly
                    # the overloaded resolver. Without it the repair
                    # engine declines and the canonical backoff runs.
                    fail_version=None if fail_safe else version,
                    hot_ranges=(None if fail_safe
                                else self.hot_ranges.scores(feed)),
                ))

    @staticmethod
    def _obs_spans(req, t_version, t_resolved, t_assembled, t_pushed,
                   t_reply) -> tuple:
        """A sampled txn's proxy-side stage spans, piggybacked on its
        CommitResult: ((stage, start, dur), ...) in proxy-clock seconds.
        The stages PARTITION [arrival, version/resolve/.../push] exactly,
        and proxy_total carries the full envelope so the client's residue
        arithmetic (e2e == sum(stages) + unattributed) is exact. The park
        window (shaped lane) is carved out of the pop->version segment by
        the flush-time pop re-anchor in _admission_gate."""
        arrival = req._obs_arrival
        pop = getattr(req, "_obs_pop", arrival)
        spans = [("proxy_admit", arrival,
                  getattr(req, "_obs_park0", pop) - arrival)]
        park = getattr(req, "_obs_park", None)
        if park is not None:
            spans.append(("shaped_park", req._obs_park0, park))
        spans += [
            ("batch_form", pop, t_version - pop),
            ("resolve_wait", t_version, t_resolved - t_version),
            ("wave_apply", t_resolved, t_assembled - t_resolved),
            ("tlog_durable", t_assembled, t_pushed - t_assembled),
            # Durable -> reply send: the sequencer committed-version
            # report + admission filter feed. Attributed, not dumped
            # into the residue — the residue must mean "unknown".
            ("commit_publish", t_pushed, t_reply - t_pushed),
            ("proxy_total", arrival, t_reply - arrival),
        ]
        return tuple(spans)

    async def _notify_committed(self, bound: int) -> None:
        """Tell every tlog the known-committed bound NOW, at the
        acknowledgement, not with this proxy's next push one pipeline
        turn later (TLog.advance_known_committed): the storages may apply
        `bound` on their next peek. Called only once EVERY tlog has
        acknowledged the fsync of a batch at or above `bound`, so it says
        nothing the next push would not say; a fenced or partitioned
        proxy never gets all acknowledgements and never comes here. Best
        effort, no retry: a lost call costs the readers the wait they had
        before, and the next push carries the bound as ever."""
        if self.loop.buggify("commit_proxy.lose_commit_notify"):
            # Dropped, or late enough for later pushes to overtake it:
            # the storages fall back on the bound the next push carries.
            if self.loop.rng.random() < 0.5:
                return
            await self.loop.sleep(self.loop.rng.uniform(0, 0.05))
        self.commit_notifies_sent += 1
        try:
            await all_of([t.advance_known_committed(bound, self.epoch)
                          for t in self.tlogs])
        except Exception:
            pass  # locked, fenced or unreachable: the fallback stands

    RPC_RETRIES = 4  # worst case ~4.4s — must finish under WEDGE_TIMEOUT

    async def _with_retry(self, make_call):
        """Retry a chain-ordered RPC through transient unreachability; the
        callee side is idempotent (resolver reply cache / tlog duplicate
        ack), so retrying is safe and required for chain liveness."""
        backoff = 0.05
        for _ in range(self.RPC_RETRIES - 1):
            try:
                return await make_call()
            except BrokenPromise:
                await self.loop.sleep(backoff)
                backoff = min(1.0, backoff * 2)
        return await make_call()

    async def _resolve(
        self,
        batch: list[tuple[CommitRequest, Promise]],
        prev_version: int,
        version: int,
    ) -> tuple[
        list[Verdict], dict[int, list[tuple[bytes, bytes]]], bool,
        "list[int] | None",
    ]:
        """Fan the batch out to every resolver (filtered to its key shard)
        and AND the verdicts. Conflicts are never missed: any read/write
        overlap lands on whichever resolver owns those keys. As in the
        reference, the AND can over-abort with multiple resolvers — a txn
        rejected only by resolver A still painted its writes on resolver B,
        so later readers may see false conflicts. The mesh-sharded TPU
        engine (parallel/sharded_resolver.py) avoids this by ANDing shard
        verdicts on-device before painting; these role-level resolvers keep
        the reference semantics.

        Retransmits: a BrokenPromise (partition/kill mid-RPC) is retried;
        resolvers replay cached verdicts for already-applied versions, so
        retries cannot double-paint."""
        per_resolver: list[list[TxnConflictInfo]] = []
        for shard in self.resolver_map.shards:
            txns = [
                TxnConflictInfo(
                    read_version=req.read_version,
                    read_ranges=_clip(req.read_ranges, shard.range),
                    write_ranges=_clip(req.write_ranges, shard.range),
                    report_conflicting_keys=req.report_conflicting_keys,
                )
                for req, _p in batch
            ]
            per_resolver.append(txns)
        if self.wave_commit and len(self.resolvers) > 1:
            return await self._resolve_wave_global(
                per_resolver, prev_version, version
            )
        replied_at: list[float] = []

        async def ask(r, txns):
            reply = await self._with_retry(
                lambda: r.resolve(prev_version, version, txns)
            )
            replied_at.append(span_now(self.loop))
            return reply

        replies = await all_of(
            [
                self.loop.spawn(ask(r, txns), name=f"resolve@{version}")
                for r, txns in zip(self.resolvers, per_resolver)
            ]
        )
        sink = span_sink(self.loop)
        if sink is not None:
            # The batch waited for the slowest resolver: how far behind
            # the first reply the last one landed (0 with one resolver).
            sink.stage_tick("resolve_straggle",
                            max(replied_at) - min(replied_at),
                            n=len(batch), version=version)
        combined: list[Verdict] = []
        conflicting: dict[int, list[tuple[bytes, bytes]]] = {}
        # Any shard in fail-safe taints the whole batch's conflict stats:
        # its CONFLICTs are spurious capacity rejections, not contention.
        fail_safe = any(fs for _v, _c, fs, _w in replies)
        # Wave-commit schedule on THIS (sequential AND-combine) path:
        # usable only from a SINGLE resolver — a per-shard schedule of
        # clipped ranges is not serializable (each resolver misses the
        # others' edges). Multi-resolver wave deployments never reach
        # here (the global edge-exchange path above owns them); this
        # guard is the pinned regression that the clipped-graph path can
        # NEVER emit a wave schedule, even from a rogue reply.
        wave = replies[0][3] if len(replies) == 1 and not fail_safe else None
        for i in range(len(batch)):
            vs = [verdicts[i] for verdicts, _conf, _fs, _w in replies]
            if Verdict.TOO_OLD in vs:
                combined.append(Verdict.TOO_OLD)
            elif Verdict.CONFLICT in vs:
                combined.append(Verdict.CONFLICT)
                # Union the per-resolver conflicting ranges (each resolver
                # reports only its own key shard's clipped subranges).
                ranges = [
                    r for _v, conf, _fs, _w in replies for r in conf.get(i, [])
                ]
                if ranges:
                    conflicting[i] = ranges
            else:
                combined.append(Verdict.COMMITTED)
        return combined, conflicting, fail_safe, wave

    async def _resolve_wave_global(
        self,
        per_resolver: list[list[TxnConflictInfo]],
        prev_version: int,
        version: int,
    ) -> tuple[
        list[Verdict], dict[int, list[tuple[bytes, bytes]]], bool,
        "list[int] | None",
    ]:
        """Two-phase global wave commit across sharded resolvers: fan out
        resolve_edges (each shard's clipped gate + packed predecessor
        bitsets), OR-reduce them into the global conflict graph (exact —
        shards partition the keyspace), broadcast it, and collect every
        shard's independently computed schedule. The schedules must be
        BYTE-IDENTICAL (the leveling is a deterministic function of the
        shared graph); a divergence means an unserializable apply order
        is possible, so the batch fails into commit_unknown_result and
        recovery rather than committing on either schedule."""
        from foundationdb_tpu.core.wavemesh import WaveEdges, combine_edges

        edge_wires = await all_of(
            [
                self.loop.spawn(
                    self._with_retry(
                        lambda r=r, txns=txns: r.resolve_edges(
                            prev_version, version, txns
                        )
                    ),
                    name=f"resolve_edges@{version}",
                )
                for r, txns in zip(self.resolvers, per_resolver)
            ]
        )
        if all(t == ("empty",) for t in edge_wires):
            # Idle heartbeat window: every shard advanced its chain in
            # phase 1; nothing to level, order, or apply.
            return [], {}, False, []
        graph = combine_edges([WaveEdges.from_wire(t) for t in edge_wires])
        gw = graph.to_wire()
        replies = await all_of(
            [
                self.loop.spawn(
                    self._with_retry(
                        lambda r=r: r.resolve_apply(version, gw)
                    ),
                    name=f"resolve_apply@{version}",
                )
                for r in self.resolvers
            ]
        )
        self.wave_exchanges += 1
        # Fail-safe FIRST: a shard-local capacity event during apply
        # (true overflow — _post_resolve_check) legitimately makes that
        # shard's reply an all-CONFLICT with no schedule, which is a
        # DESIGNED degraded mode, not a divergence. The batch conflicts
        # wholesale (no shard's paint became durable for its clients;
        # partial paints on the healthy shards only add spurious
        # conflicts later, the standing failure contract) — exactly the
        # sequential path's fail-safe handling, no recovery.
        fail_safe = any(fs for _v, _c, fs, _w in replies)
        if fail_safe:
            fs_reply = next(r for r in replies if r[2])
            return list(fs_reply[0]), {}, True, None
        first = replies[0]
        for k, rep in enumerate(replies[1:], 1):
            if rep[3] != first[3] or rep[0] != first[0]:
                trace(self.loop).event(
                    "WaveScheduleDivergence", Severity.ERROR,
                    version=version, shard=k,
                )
                raise RuntimeError(
                    f"wave schedule divergence at batch@{version}: shard "
                    f"{k} disagrees with shard 0 — refusing to apply"
                )
        conflicting: dict[int, list[tuple[bytes, bytes]]] = {}
        for _v, conf, _fs, _w in replies:
            for i, ranges in conf.items():
                conflicting.setdefault(i, []).extend(ranges)
        return list(first[0]), conflicting, False, first[3]

    def _assemble(
        self,
        batch: list[tuple[CommitRequest, Promise]],
        verdicts: list[Verdict],
        version: int,
        wave: list[int] | None = None,
    ) -> dict[int, list[Mutation]]:
        """Tag committed txns' mutations by storage shard (reference:
        applyMetadataEffect + tag lookup in commitBatch).

        ``wave`` (a wave-commit resolver's schedule) reorders SAME-VERSION
        mutation application into the realized serialization order
        (wave level, then batch index): tlogs and storage servers apply a
        version's mutation list in order, so two committed blind writes to
        one key must land last-writer-in-realized-order, not last-writer-
        by-arrival. Versionstamps keep the BATCH index (uniqueness is
        per-slot; their ordering guarantee is by (version, index), which
        clients may only compare across versions they observed commit —
        and wave order never crosses a version boundary)."""
        tagged: dict[int, list[Mutation]] = {}
        order = range(len(batch))
        if wave is not None:
            order = sorted(order, key=lambda i: (max(wave[i], 0), i))
        for i in order:
            req, _p = batch[i]
            v = verdicts[i]
            if v != Verdict.COMMITTED:
                continue
            for m in resolve_versionstamps(req.mutations, version, i):
                if m.type == MutationType.CLEAR_RANGE:
                    for sub, team in self.storage_map.split_range_teams(
                        KeyRange(m.param1, m.param2)
                    ):
                        sub_m = Mutation(
                            MutationType.CLEAR_RANGE, sub.begin, sub.end
                        )
                        for tag in team:  # every replica of the shard's team
                            tagged.setdefault(tag, []).append(sub_m)
                else:
                    for tag in self.storage_map.team_for_key(m.param1):
                        tagged.setdefault(tag, []).append(m)
                if self.backup_enabled:
                    tagged.setdefault(BACKUP_TAG, []).append(m)
        return tagged


def _clip(ranges: list[KeyRange], shard: KeyRange) -> list[KeyRange]:
    # ONE clip rule (core/wavemesh.clip_ranges, imported at module level —
    # this runs per txn per resolver on the commit hot path): the wave
    # protocol's partition identity depends on this exact boundary
    # handling, so the proxy split, the A/B harness, and the tests share
    # the definition.
    return clip_ranges(ranges, shard.begin, shard.end)
