"""Ratekeeper: admission control from storage + tlog queuing metrics.

Reference: fdbserver/Ratekeeper.actor.cpp — polls StorageQueuingMetrics and
TLogQueuingMetrics, tracks the worst storage version lag, storage durability
lag, storage queue bytes and tlog queue bytes, computes a cluster-wide
transactions-per-second budget from the WORST signal, and leases per-interval
budgets to the GRV proxies, which block getReadVersion batches once the lease
is exhausted (that back-pressure is what keeps the MVCC window bounded).

Two priority lanes, like the reference's default/batch split: batch-priority
traffic is throttled at half the thresholds, so background work yields
long before interactive traffic feels anything.
"""

from __future__ import annotations

from foundationdb_tpu.runtime.flow import Loop, all_of, rpc
from foundationdb_tpu.runtime.sequencer import VERSIONS_PER_SECOND
from foundationdb_tpu.runtime.trace import Severity, trace

#: Every limiting reason _scale can report, in a FIXED order: get_rates
#: exports the current reason as ``limiting_reason_code`` (an index into
#: this tuple) so the signal survives the numbers-only metrics plane —
#: the obs flight recorder decodes transitions back to names from the
#: same tuple (obs/recorder.py annotation catalog).
LIMIT_REASONS = (
    "none",
    "storage_lag",
    "durability_lag",
    "storage_queue",
    "tlog_queue",
    "resolver_queue",
    "admission_filter",
)


class Ratekeeper:
    POLL_INTERVAL = 0.1
    BASE_TPS = 200_000.0  # optimistic starting ceiling, NOT the budget:
    # the ceiling calibrates toward measured throughput (see run())
    MAX_TPS = 2_000_000.0
    MIN_TPS = 100.0
    PROBE_GAIN = 1.05  # healthy + near ceiling → probe upward
    BACKOFF_MARGIN = 1.1  # degraded → ceiling = measured * margin
    EWMA_ALPHA = 0.3
    # Per-signal (soft, hard) limits: scale falls linearly from 1 at soft
    # to 0 at hard; the governing signal is whichever is worst (reference:
    # Ratekeeper takes the min over its limit reasons).
    LAG_SOFT = 1 * VERSIONS_PER_SECOND  # storage behind tlogs (versions)
    LAG_HARD = 4 * VERSIONS_PER_SECOND
    DLAG_SOFT = 2 * VERSIONS_PER_SECOND  # applied but not fsynced (versions)
    DLAG_HARD = 8 * VERSIONS_PER_SECOND
    SQ_SOFT = 16 << 20  # storage queue bytes (reference: TARGET_BYTES_PER_SS)
    SQ_HARD = 64 << 20
    TQ_SOFT = 64 << 20  # tlog queue bytes (reference: TARGET_BYTES_PER_TLOG)
    TQ_HARD = 256 << 20
    # Resolver dispatch-queue depth (batches parked behind the conflict
    # engine — sched subsystem backpressure): admission slows before the
    # resolver's queue, and ultimately its history capacity, overflows.
    RQ_SOFT = 16
    RQ_HARD = 128
    # Admission-filter saturation (admission subsystem): the commit
    # proxies' recent-writes filter fill fraction. A saturating filter
    # means the write rate is outrunning what admission can discriminate
    # — probes degrade toward all-hit — so the cluster throttles intake
    # BEFORE shaping collapses into shape-everything (the signal sits
    # next to resolver_queue, exactly as the ROADMAP item prescribed).
    AS_SOFT = 0.60
    AS_HARD = 0.99
    # Batch lane throttles at this fraction of every threshold.
    BATCH_FRACTION = 0.5

    def __init__(self, loop: Loop, storage_eps: list, tlog_eps: list | None = None,
                 proxy_eps: list | None = None, resolver_eps: list | None = None,
                 tag_quotas: dict[str, float] | None = None):
        self.loop = loop
        self.storages = storage_eps
        self.tlogs = list(tlog_eps or [])
        # Resolvers report dispatch-queue depth + occupancy (the sched
        # subsystem's backpressure surface in Resolver.get_metrics).
        self.resolvers = list(resolver_eps or [])
        # Commit proxies report txns_committed; their delta per poll is the
        # cluster's MEASURED service rate (reference: proxies report
        # released-transaction counts to the ratekeeper, which smooths
        # them into actualTps). Assignable after construction (recruitment
        # order creates proxies later).
        self.proxies = list(proxy_eps or [])
        self.base_tps = self.BASE_TPS
        self.measured_tps = 0.0
        self._last_committed: int | None = None
        # What the budget is SPENT on: read versions the GRV proxies
        # granted a second, smoothed like measured_tps from the counts
        # they report with their get_rates polls. Commits are a part of
        # it only (YCSB F asks two read versions a commit, a read-only
        # transaction one and no commit), so the healthy branch probes
        # the ceiling on whichever of the two runs near it.
        self.grv_tps = 0.0
        self.ceiling_probes = 0
        self.tps_limit = self.BASE_TPS
        self.batch_tps_limit = self.BASE_TPS
        self.worst_lag = 0
        self.worst_durability_lag = 0
        self.worst_storage_queue = 0
        self.worst_tlog_queue = 0
        self.worst_resolver_queue = 0
        self.worst_resolver_occupancy = 0.0
        self.worst_admission_saturation = 0.0
        self.limiting_reason = "none"
        # Limiting-reason transition count: a remote scraper (the flight
        # recorder polling over TCP) sees only numbers, so a reason that
        # engaged AND released between two polls would be invisible from
        # the code alone — the counter delta says "something transitioned
        # here" even when the endpoints look identical.
        self.limit_transitions = 0
        # Per-tag tps quotas (reference: TagThrottleApi manual throttles in
        # \xff\x02/throttle/): enforced by the GRV proxies' per-tag buckets.
        # The recruiter may pass a SHARED dict so operator quotas survive
        # recoveries (set_tag_quota mutates it in place; a freshly
        # recruited ratekeeper then starts with every standing quota —
        # without this, any kill-triggered recovery silently unthrottled
        # every quota'd tag; nemesis-campaign find, QuotaAbuseUnderKills).
        self.tag_quotas: dict[str, float] = (
            tag_quotas if tag_quotas is not None else {}
        )
        # Live GRV-proxy pollers (poller_id -> last get_rates time): the
        # cluster tps budget is LEASED in per-proxy shares (reference:
        # Ratekeeper::updateRate divides tpsLimit across proxies by their
        # reported request fractions; we split evenly). Without this,
        # every proxy refilled its bucket from the WHOLE cluster budget,
        # so an N-proxy scale-out silently multiplied admission by N and
        # the clamps this role exists for never engaged (open-loop
        # scale-out find). A poller that stops polling (retired
        # generation, dead process) ages out after POLLER_TTL and its
        # share returns to the survivors.
        self._pollers: dict[str, float] = {}
        # poller_id -> (its grvs_served at its last poll, its grant rate
        # between its last two polls); aged out with the lease.
        self._poller_grvs: dict[str, tuple[int, float]] = {}

    POLLER_TTL = 1.0

    def _grv_pollers(self, poller_id: "str | None",
                     grvs_served: "int | None" = None) -> int:
        now = self.loop.now
        if poller_id is not None:
            if grvs_served is not None:
                self._note_grvs(poller_id, grvs_served,
                                now - self._pollers.get(poller_id, now))
            self._pollers[poller_id] = now
        for pid, seen in list(self._pollers.items()):
            if now - seen > self.POLLER_TTL:
                del self._pollers[pid]
                self._poller_grvs.pop(pid, None)
        return max(1, len(self._pollers))

    def _note_grvs(self, poller_id: str, served: int, since: float) -> None:
        """One GRV proxy's grant rate between its last two polls. A first
        report, or a count under the last (a restarted proxy), baselines."""
        last, rate = self._poller_grvs.get(poller_id, (served, 0.0))
        if since > 0 and served >= last:
            rate = (served - last) / since
        self._poller_grvs[poller_id] = (served, rate)

    @rpc
    async def set_tag_quota(self, tag: str, tps: float | None) -> None:
        """Set (or clear with None) a transaction tag's tps quota —
        the ThrottleApi `throttle on tag` analogue."""
        if tps is None:
            self.tag_quotas.pop(tag, None)
        else:
            self.tag_quotas[tag] = float(tps)

    @rpc
    async def release_lease(self, poller_id: str) -> bool:
        """Retire-side half of the per-proxy budget lease: a deliberately
        retired GRV proxy hands its share back immediately, so the
        surviving proxies see the whole budget on their next get_rates
        poll instead of waiting out POLLER_TTL. Crash retirement still
        falls back to the TTL ageing path."""
        self._poller_grvs.pop(poller_id, None)
        return self._pollers.pop(poller_id, None) is not None

    async def run(self) -> None:
        while True:
            try:
                metrics = await all_of([s.metrics() for s in self.storages])
                self.worst_lag = max((m["version_lag"] for m in metrics), default=0)
                self.worst_durability_lag = max(
                    (m.get("durability_lag", 0) for m in metrics), default=0
                )
                self.worst_storage_queue = max(
                    (m.get("queue_bytes", 0) for m in metrics), default=0
                )
                if self.tlogs:
                    tmetrics = await all_of([t.metrics() for t in self.tlogs])
                    self.worst_tlog_queue = max(
                        (m["queue_bytes"] for m in tmetrics), default=0
                    )
                if self.resolvers:
                    rmetrics = await all_of(
                        [r.get_metrics() for r in self.resolvers]
                    )
                    # High-water over the resolver's rolling window, not
                    # the instantaneous depth: a spike that builds and
                    # drains between two 0.1s polls must still engage the
                    # backpressure loop (nemesis-campaign find).
                    self.worst_resolver_queue = max(
                        (m.get("queue_depth_hw", m.get("queue_depth", 0))
                         for m in rmetrics), default=0
                    )
                    # Windowed occupancy, not the lifetime ratio: the
                    # control loops downstream (autoscale) need "is the
                    # dispatcher saturated NOW" — the lifetime average
                    # rises asymptotically and never forgets a past
                    # overload (see ResolveScheduler.
                    # dispatch_occupancy_recent).
                    self.worst_resolver_occupancy = max(
                        ((m.get("queue") or {}).get(
                            "dispatch_occupancy_recent",
                            (m.get("queue") or {}).get(
                                "dispatch_occupancy", 0.0))
                         for m in rmetrics),
                        default=0.0,
                    )
                await self._calibrate()
                self.tps_limit = self.base_tps * self._scale(1.0)
                self.batch_tps_limit = self.base_tps * self._scale(
                    self.BATCH_FRACTION
                )
            except Exception:
                # A dead storage server shows up as a broken metrics RPC;
                # keep the last limit until it is replaced (reference keeps
                # serving with stale smoothed metrics too).
                pass
            await self.loop.sleep(self.POLL_INTERVAL)

    async def _calibrate(self) -> None:
        """Derive the tps ceiling from MEASURED role throughput instead of
        a constant (VERDICT r2 weak-5): smooth the commit proxies'
        txns_committed delta into measured_tps; while a signal degrades
        AND the proxies hold a backlog (the flow is admission-limited,
        not a background cause like a DD move), pull the ceiling down to
        just above what the roles demonstrably service; while healthy and
        running near the ceiling, probe it upward. The min-over-reasons
        linear scale then operates on a ceiling that tracks real capacity
        (reference: Ratekeeper's smoothed actualTps feeding tpsLimit).

        Failure containment: an unreachable proxy only skips THIS poll's
        calibration sample — the caller still updates the signal-based
        limits (a proxy outage must never freeze throttling). A committed
        count below the baseline means the proxy set changed (recovery
        swapped generations, counters restarted): re-baseline instead of
        injecting a spurious zero-rate sample."""
        if not self.proxies:
            return
        ms = []
        for p in self.proxies:
            try:
                ms.append(await p.get_metrics())
            except Exception:
                self._last_committed = None  # membership degraded: re-baseline
                return
        # Admission-filter saturation rides the same proxy metrics poll
        # (admission subsystem; proxies without a policy report None).
        self.worst_admission_saturation = max(
            ((m.get("admission") or {}).get("saturation", 0.0) for m in ms),
            default=0.0,
        )
        committed = sum(m.get("txns_committed", 0) for m in ms)
        # Backlog = admission-limited evidence: commits queued at the
        # proxies PLUS batches parked in resolver dispatch queues (the
        # sched subsystem's occupancy signal) — either means the flow is
        # pushing harder than the roles service.
        backlog = sum(m.get("queued", 0) for m in ms) + self.worst_resolver_queue
        if self._last_committed is None or committed < self._last_committed:
            self._last_committed = committed
            return
        rate = (committed - self._last_committed) / self.POLL_INTERVAL
        self._last_committed = committed
        a = self.EWMA_ALPHA
        self.measured_tps = (1 - a) * self.measured_tps + a * rate
        self.grv_tps = (1 - a) * self.grv_tps + a * sum(
            r for _n, r in self._poller_grvs.values())
        if self._scale(1.0) < 1.0 and backlog > 0:
            # Degrading under backlog: admission exceeds what the roles
            # service — converge the ceiling onto measurement. (Without
            # backlog, measured_tps is just DEMAND; clamping to it would
            # collapse the ceiling on any background blip.)
            self.base_tps = min(
                self.base_tps,
                max(self.MIN_TPS, self.measured_tps * self.BACKOFF_MARGIN),
            )
        elif max(self.measured_tps, self.grv_tps) > 0.7 * self.base_tps:
            self.base_tps = min(self.MAX_TPS, self.base_tps * self.PROBE_GAIN)
            self.ceiling_probes += 1

    def _scale(self, frac: float) -> float:
        signals = [
            ("storage_lag", self.worst_lag, self.LAG_SOFT, self.LAG_HARD),
            ("durability_lag", self.worst_durability_lag,
             self.DLAG_SOFT, self.DLAG_HARD),
            ("storage_queue", self.worst_storage_queue,
             self.SQ_SOFT, self.SQ_HARD),
            ("tlog_queue", self.worst_tlog_queue, self.TQ_SOFT, self.TQ_HARD),
            ("resolver_queue", self.worst_resolver_queue,
             self.RQ_SOFT, self.RQ_HARD),
            ("admission_filter", self.worst_admission_saturation,
             self.AS_SOFT, self.AS_HARD),
        ]
        worst, reason = 1.0, "none"
        for name, value, soft, hard in signals:
            soft, hard = soft * frac, hard * frac
            if value <= soft:
                s = 1.0
            elif value >= hard:
                s = 0.0
            else:
                s = 1.0 - (value - soft) / (hard - soft)
            if s < worst:
                worst, reason = s, name
        if frac == 1.0:
            if reason != self.limiting_reason:
                self.limit_transitions += 1
                trace(self.loop).event(
                    "RkLimitReasonChanged",
                    Severity.INFO if reason == "none" else Severity.WARN,
                    reason=reason, previous=self.limiting_reason,
                    scale=round(worst, 4))
            self.limiting_reason = reason
        return worst

    @rpc
    async def get_rate(self) -> float:
        """GRV proxies poll this as their admission budget (txns/sec)."""
        return self.tps_limit

    @rpc
    async def get_rates(self, poller_id: "str | None" = None,
                        grvs_served: "int | None" = None) -> dict:
        """Both lanes + the governing signal (status json reports these).

        `poller_id`: a GRV proxy identifying itself — counted into the
        live-poller set and handed its even SHARE of each lane budget
        (`tps_limit_share` / `batch_tps_limit_share`). The cluster-wide
        totals stay in `tps_limit`/`batch_tps_limit` for status and for
        callers that don't identify themselves. `grvs_served`: that
        proxy's count of read versions granted so far, the use of the
        budget that `grv_tps` smooths."""
        n_pollers = self._grv_pollers(poller_id, grvs_served)
        return {
            "tps_limit": self.tps_limit,
            "batch_tps_limit": self.batch_tps_limit,
            "grv_pollers": n_pollers,
            "tps_limit_share": self.tps_limit / n_pollers,
            "batch_tps_limit_share": self.batch_tps_limit / n_pollers,
            "limiting_reason": self.limiting_reason,
            # Numeric twin of limiting_reason (index into LIMIT_REASONS)
            # plus the transition counter: the flight recorder's remote
            # scrape keeps numbers only, and these two carry the reason
            # and its flapping through that plane.
            "limiting_reason_code": LIMIT_REASONS.index(self.limiting_reason),
            "limit_transitions": self.limit_transitions,
            "worst_storage_lag": self.worst_lag,
            "worst_durability_lag": self.worst_durability_lag,
            "worst_storage_queue_bytes": self.worst_storage_queue,
            "worst_tlog_queue_bytes": self.worst_tlog_queue,
            "worst_resolver_queue": self.worst_resolver_queue,
            "resolver_dispatch_occupancy": self.worst_resolver_occupancy,
            "admission_saturation": self.worst_admission_saturation,
            "tag_rates": dict(self.tag_quotas),
            # Tag quotas split the same way: a quota is a CLUSTER bound,
            # not a per-proxy one (N proxies each refilling the full
            # quota would hand an abusive tag N× its budget).
            "tag_rates_share": {
                t: q / n_pollers for t, q in self.tag_quotas.items()
            },
            "base_tps": self.base_tps,
            "measured_tps": self.measured_tps,
            "grv_tps": self.grv_tps,
            "ceiling_probes": self.ceiling_probes,
        }
