"""GRV proxy: batched read-version handout with ratekeeper admission.

Reference: fdbserver/GrvProxyServer.actor.cpp — clients' getReadVersion
requests queue up, a batch loop drains them every interval (one sequencer
round-trip serves the whole batch), and the reply is the cluster's live
committed version. Admission: a token bucket refilled from the
ratekeeper's tps budget; when empty, waiters simply stay queued, which is
exactly how the reference applies back-pressure. Two lanes mirror the
reference's TransactionPriority::DEFAULT / BATCH split: batch requests
draw from their own (stricter) bucket and are only drained after every
admitted default-priority request.
"""

from __future__ import annotations

import itertools
import os

from foundationdb_tpu.core.errors import ProcessKilled
from foundationdb_tpu.obs.span import span_now, span_sink
from foundationdb_tpu.runtime.flow import ActorCancelled, Loop, Promise, rpc

#: Unique-per-process GRV poller ids (pid + counter: deterministic in the
#: single-process sim, collision-free across deployed proxy processes).
_poller_seq = itertools.count()

PRIORITY_DEFAULT = "default"
PRIORITY_BATCH = "batch"
# Reference TransactionPriority::SYSTEM_IMMEDIATE: recovery/system-keyspace
# traffic is NEVER ratekeeper-throttled. Nemesis-campaign find
# (LaneStarvationHotStorm): system txns rode the default GRV bucket, so
# resolver_queue backpressure starved the system lane exactly when the
# cluster most needed it — lock checks, DR progress writes and system
# probes all stalled behind the storm they were supposed to outrank.
PRIORITY_SYSTEM = "system"


class GrvProxy:
    BATCH_INTERVAL = 0.001
    RATE_POLL_INTERVAL = 0.1
    MAX_TOKENS = 2000.0

    MAX_TAG_TOKENS = 100.0

    # Admission subsystem, GRV-side gate: no read set exists at GRV time,
    # so the probe signal here is the cluster-wide recent-writes FILTER
    # SATURATION (polled off the ratekeeper's rates next to the tps
    # budgets). At/above this saturation the filter can no longer
    # discriminate likely losers — shaping degrades to shape-everything —
    # so the GRV gate paces the intake instead: default/batch grants are
    # deferred every other interval (half-rate), while the system lane
    # stays unconditionally admitted (the lane contract).
    ADMISSION_DEFER_SAT = 0.75

    def __init__(self, loop: Loop, sequencer_ep, ratekeeper_ep=None,
                 tlog_eps: list | None = None, epoch: int = 0):
        self.loop = loop
        self.sequencer = sequencer_ep
        self.ratekeeper = ratekeeper_ep
        # Epoch-liveness confirmation set (reference: confirmEpochLive).
        # When given, every GRV batch confirms the generation's WHOLE
        # push set (chain + satellite tlogs — the same all-members rule
        # commits ack against) before replying: a read version is only
        # externally consistent if this generation could still commit at
        # mint time. A displaced generation's proxy (its tlogs locked or
        # epoch-fenced by recovery, its satellite unreachable across a
        # partition) must hand out NO read versions — otherwise a client
        # reads pre-fork state after another client's commit acked in
        # the successor generation. None = unconfirmed mode (static
        # wiring / unit harnesses without a recruitment protocol).
        self.tlogs = tlog_eps
        self.epoch = epoch
        # Queue entries: (promise, txn tags) — tags from the TAG
        # transaction option (reference: TagThrottle at the GRV proxy).
        self._queue: list[tuple[Promise, tuple[str, ...]]] = []
        self._batch_queue: list[tuple[Promise, tuple[str, ...]]] = []
        # System lane: admitted UNCONDITIONALLY every interval — no rate
        # bucket, no tag buckets (reference: SYSTEM_IMMEDIATE skips
        # ratekeeper). See PRIORITY_SYSTEM for the campaign find.
        self._system_queue: list[tuple[Promise, tuple[str, ...]]] = []
        self._tokens = self.MAX_TOKENS
        self._batch_tokens = self.MAX_TOKENS
        # Tagged admission is DEFERRED until the first rate poll lands:
        # a freshly recruited proxy has no tag buckets yet, and admitting
        # tagged traffic ungated in that window silently bypasses every
        # operator quota at each recovery (nemesis-campaign find,
        # QuotaAbuseUnderKills: kill-triggered generations gave an abusive
        # tag a free burst per kill). Queuing is the conservative choice;
        # untagged traffic is unaffected.
        self._have_tag_rates = ratekeeper_ep is None
        # Identify this proxy to the ratekeeper so the cluster budget is
        # leased in per-proxy SHARES (Ratekeeper._grv_pollers): with N
        # proxies each draws tps_limit/N — the scale-out contract.
        self.poller_id = f"grv-{os.getpid()}-{next(_poller_seq)}"
        unlimited = float("inf") if ratekeeper_ep is None else 0.0
        self._rate = unlimited
        self._batch_rate = unlimited
        self._tag_rates: dict[str, float] = {}  # quota'd tags only
        self._tag_tokens: dict[str, float] = {}
        self.grvs_served = 0
        # Of those, the grants that spent the ratekeeper's budget (the
        # system lane spends none): what the rate poll reports.
        self._grvs_budgeted = 0
        self.tag_throttled = 0  # admissions deferred by a tag bucket
        # Admission-saturation deferral (see ADMISSION_DEFER_SAT).
        self._admission_sat = 0.0
        self._defer_flip = False
        self.admission_defer_ticks = 0

    @rpc
    async def get_read_version(self, priority: str = PRIORITY_DEFAULT,
                               tags: list[str] | None = None) -> int:
        p = Promise()
        entry = (p, tuple(tags or ()))
        queue = {
            PRIORITY_BATCH: self._batch_queue,
            PRIORITY_SYSTEM: self._system_queue,
        }.get(priority, self._queue)
        queue.append(entry)
        sink = span_sink(self.loop)
        if sink is None:
            return await p.future
        # Sub-stage attribution (obs subsystem): time from arrival to the
        # batched grant — token-bucket waits, tag throttling, and the
        # admission-saturation deferral all land here (the interior of
        # the client-measured grv_wait stage).
        t0 = span_now(self.loop)
        version = await p.future
        sink.stage_tick("grv_proxy_queue", span_now(self.loop) - t0)
        return version

    @rpc
    async def get_metrics(self) -> dict:
        """Status inputs (reference: GrvProxy metrics in status json)."""
        return {
            "grvs_served": self.grvs_served,
            "queued": len(self._queue),
            "batch_queued": len(self._batch_queue),
            "tag_throttled": self.tag_throttled,
            # Intervals on which default/batch grants were deferred by
            # admission-filter saturation (admission subsystem).
            "admission_defer_ticks": self.admission_defer_ticks,
        }

    def _admit(self, queue: list, tokens: float) -> tuple[list, list, float]:
        """Admit in arrival order, gated by the lane bucket AND every tag
        bucket the request carries. A tag-starved request stays queued (in
        order) without blocking untagged traffic behind it — that's the
        whole point of per-tag throttling (reference: tag-throttled GRV
        requests wait in their own queue)."""
        admitted: list[Promise] = []
        kept: list = []
        for p, tags in queue:
            if tokens != float("inf") and tokens < 1:
                kept.append((p, tags))
                continue
            if tags and not self._have_tag_rates:
                # No rates seen yet (fresh recruit): a tagged request
                # cannot be admission-checked, so it waits (see __init__).
                self.tag_throttled += 1
                kept.append((p, tags))
                continue
            starved = [
                t for t in tags
                if t in self._tag_tokens and self._tag_tokens[t] < 1
            ]
            if starved:
                self.tag_throttled += 1
                kept.append((p, tags))
                continue
            for t in tags:
                if t in self._tag_tokens:
                    self._tag_tokens[t] -= 1
            if tokens != float("inf"):
                tokens -= 1
            admitted.append(p)
        return admitted, kept, tokens

    async def run(self) -> None:
        self.loop.spawn(self._rate_poller(), name="grv.rate_poller")
        last_refill = self.loop.now
        while True:
            await self.loop.sleep(self.BATCH_INTERVAL)
            # Buckets refill by the loop's clock, not by the iteration: a
            # turn is BATCH_INTERVAL plus the sequencer call plus the
            # batch's own work, and a refill of one interval a turn hands
            # the clients two thirds of the ratekeeper's budget.
            now = self.loop.now
            elapsed, last_refill = now - last_refill, now
            # Saturation deferral (admission subsystem): on deferred
            # intervals default/batch buckets DO NOT refill — skipping
            # only the admission pass would let the skipped interval's
            # tokens accrue and double-spend next interval, leaving
            # long-run throughput untouched (the whole point is a real
            # half-rate intake; the bucket cap still allows bursts).
            defer = self._admission_sat >= self.ADMISSION_DEFER_SAT
            if defer:
                self._defer_flip = not self._defer_flip
            defer_now = defer and self._defer_flip
            if self._tokens != float("inf") and not defer_now:
                self._tokens = min(
                    self.MAX_TOKENS, self._tokens + self._rate * elapsed
                )
                self._batch_tokens = min(
                    self.MAX_TOKENS,
                    self._batch_tokens + self._batch_rate * elapsed,
                )
            for tag, rate in self._tag_rates.items():
                self._tag_tokens[tag] = min(
                    self.MAX_TAG_TOKENS,
                    self._tag_tokens.get(tag, 0.0) + rate * elapsed,
                )
            if (not self._queue and not self._batch_queue
                    and not self._system_queue):
                continue
            # System lane first, never gated: every queued system request
            # is admitted this interval regardless of buckets.
            s_admitted = [p for p, _tags in self._system_queue]
            self._system_queue = []
            if defer_now:
                # Deferred interval: default and batch grants sit out
                # (no admission, no refill — see above); waiters stay
                # queued in order, exactly like an empty token bucket.
                self.admission_defer_ticks += 1
                admitted, b_admitted = [], []
            else:
                admitted, self._queue, self._tokens = self._admit(
                    self._queue, self._tokens
                )
                b_admitted, self._batch_queue, self._batch_tokens = (
                    self._admit(self._batch_queue, self._batch_tokens)
                )
            batch = s_admitted + admitted + b_admitted
            if not batch:
                continue
            sink = span_sink(self.loop)
            t_seq = span_now(self.loop) if sink is not None else 0.0
            try:
                version = await self.sequencer.get_live_committed_version()
                await self._confirm_epoch_live()
            except Exception as e:
                for p in batch:
                    p.fail(e)
                continue
            except ActorCancelled:
                # Retired while waiting: these have left the queues that
                # the recruiter fails (the commit proxy's twin case).
                for p in batch:
                    p.fail(ProcessKilled("proxy retired: ask again"))
                raise
            if sink is not None:
                # Stage grv_sequencer_rtt (obs/span.py): what the batch
                # paid for its version. A request's grv_proxy_queue minus
                # this and one BATCH_INTERVAL is what it waited for
                # TOKENS, the ratekeeper's hand.
                sink.stage_tick("grv_sequencer_rtt",
                                span_now(self.loop) - t_seq, n=len(batch),
                                version=version)
            self.grvs_served += len(batch)
            self._grvs_budgeted += len(batch) - len(s_admitted)
            for p in batch:
                p.send(version)

    async def _confirm_epoch_live(self) -> None:
        """One parallel confirm round per GRV batch (the reference's
        amortization: confirmEpochLive per batch, not per request). ALL
        members must answer — commit acks require all, so liveness does
        too; any locked/fenced/unreachable member means this generation
        can no longer commit and must stop minting read versions.

        Epoch 0 (static wiring, no recruitment protocol) skips the round
        entirely: with no generations there is nothing to fence against,
        so the check is vacuous and the fan-out is pure per-batch latency
        in the common read path; a recovery lock is still observed via
        the normal commit/read paths (r5 review finding)."""
        if not self.tlogs or not self.epoch:
            return
        tasks = [
            self.loop.spawn(t.confirm_epoch(self.epoch),
                            name="grv.confirm_epoch")
            for t in self.tlogs
        ]
        failed = None
        for t in tasks:
            try:
                await t
            except Exception as e:
                failed = e
        if failed is not None:
            from foundationdb_tpu.core.errors import ProcessKilled

            raise ProcessKilled(
                f"grv epoch {self.epoch} unconfirmed: {failed}") from failed

    async def release_lease(self) -> bool:
        """Deliberate-retirement half of the budget lease (autoscale /
        stand-down path): return this proxy's ratekeeper share NOW rather
        than letting it age out over the live-poller TTL. Safe to call
        when unwired (no ratekeeper) or when the lease already expired."""
        if self.ratekeeper is None:
            return False
        return bool(await self.ratekeeper.release_lease(self.poller_id))

    async def _rate_poller(self) -> None:
        if self.ratekeeper is None:
            return
        while True:
            try:
                # The poll also REPORTS what the budget is spent on, read
                # versions granted: the ratekeeper's ceiling follows it
                # (Ratekeeper._calibrate, grv_tps).
                rates = await self.ratekeeper.get_rates(
                    self.poller_id, self._grvs_budgeted)
                # Per-proxy share when the ratekeeper leases one (older
                # ratekeepers hand back only the cluster totals).
                self._rate = rates.get("tps_limit_share",
                                       rates["tps_limit"])
                self._batch_rate = rates.get("batch_tps_limit_share",
                                             rates["batch_tps_limit"])
                tag_rates = rates.get("tag_rates_share",
                                      rates.get("tag_rates", {}))
                # Drop buckets for cleared quotas so those tags go back
                # to unlimited.
                self._tag_rates = dict(tag_rates)
                self._tag_tokens = {
                    t: self._tag_tokens.get(t, 0.0) for t in tag_rates
                }
                self._have_tag_rates = True
                # Admission-filter saturation rides the same poll
                # (admission subsystem; absent = admission off = 0).
                self._admission_sat = float(
                    rates.get("admission_saturation", 0.0) or 0.0
                )
            except Exception:
                pass  # keep last known rate while ratekeeper is unreachable
            await self.loop.sleep(self.RATE_POLL_INTERVAL)
