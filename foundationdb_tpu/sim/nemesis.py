"""Nemesis catalog: composable, seeded cross-subsystem fault actions.

Reference: the fault half of flow/sim2.actor.cpp plus the buggify'd
workload actors — but organised Jepsen-style as a *catalog of nemeses*:
each action is a small seeded actor that perturbs ONE subsystem (process
kills, storage reboots, pair/region partitions, clog storms, data-movement
kicks, DR failover, hot-range write storms, lane floods, tag-quota abuse,
cross-tenant probes, live consistency audits), and a campaign
(sim/campaigns.py) composes several of them against live workloads under
one TOML-declared, seed-replayable schedule.

Every random draw comes from the cluster loop's seeded RNG, so a failing
(spec, seed) pair replays bit-identically — the same guarantee the
FaultInjector gives, extended to cross-subsystem compositions.

Exactness contract: actions that *generate traffic* (WriteStorm,
TagQuotaAbuse, CrossTenantProbe, SystemProbe) keep exact accounting in the
shared ``NemesisContext`` and expose a ``verify(ctx, db)`` coroutine the
campaign runner calls after quiesce — conservation sums, admission bounds,
denial counts. Campaigns gate on these exact oracles (plus byte parity and
the workloads' own invariants), never on "it didn't crash".
"""

from __future__ import annotations

import struct
from dataclasses import dataclass, field

from foundationdb_tpu.core.errors import FdbError
from foundationdb_tpu.runtime.flow import all_of


class CampaignCheckFailed(FdbError):
    """An exact-oracle gate failed — the campaign found a bug."""

    code = 1501


@dataclass
class NemesisContext:
    """Shared state between a campaign's actions, workloads, and gates.

    The campaign runner attaches it to the cluster as
    ``cluster.nemesis_ctx`` so spec-driven workloads (e.g.
    FailoverZipfRepair) can coordinate with actions (e.g. DRSwitchover)
    without new plumbing through the workload interface."""

    cluster: object
    db: object
    extra: dict = field(default_factory=dict)  # dr agent, secondary db, ...
    counters: dict = field(default_factory=dict)  # exact accounting
    reports: list = field(default_factory=list)  # live consistency audits
    latencies: dict = field(default_factory=dict)  # lane -> [seconds]
    events: list = field(default_factory=list)  # (t, action, detail)
    defects: list = field(default_factory=list)  # live-observed violations
    flags: dict = field(default_factory=dict)  # e.g. {"failover": True}
    stopped: bool = False

    @property
    def loop(self):
        return self.cluster.loop

    def record(self, action: str, **detail) -> None:
        self.events.append((round(self.loop.now, 4), action, detail))

    def bump(self, key: str, n: int = 1) -> None:
        self.counters[key] = self.counters.get(key, 0) + n


class Nemesis:
    """One schedulable fault action.

    Schedule knobs shared by every action: ``at`` (virtual seconds before
    the first fire), ``every`` (mean inter-fire interval, jittered from
    the loop RNG), ``count`` (max fires; 0 = until the campaign stops the
    action). ``fire`` may return False to decline (precondition not met —
    does not consume the fire budget)."""

    name = "nemesis"

    def __init__(self, at: float = 0.0, every: float = 0.5, count: int = 1):
        self.at = at
        self.every = every
        self.count = count
        self.fired = 0

    async def run(self, ctx: NemesisContext) -> None:
        loop = ctx.loop
        if self.at:
            await loop.sleep(self.at)
        while not ctx.stopped and (self.count <= 0 or self.fired < self.count):
            ok = await self.fire(ctx)
            if ok is not False:
                self.fired += 1
            if self.count > 0 and self.fired >= self.count:
                return
            await loop.sleep(self.every * (0.5 + loop.rng.random()))

    async def fire(self, ctx: NemesisContext):  # pragma: no cover - interface
        raise NotImplementedError

    async def verify(self, ctx: NemesisContext, db) -> None:
        """Post-quiesce exact-oracle gate; default: nothing to check."""


# -- process faults -----------------------------------------------------------


class ProcessKiller(Nemesis):
    """Kill random generation processes (recovery must re-form the chain).
    Reuses the FaultInjector's safe-to-kill rule: never the last reachable
    tlog copy, never the last controller candidate."""

    name = "kill"

    def __init__(self, max_kills: int = 2, include_controller: bool = False,
                 **kw):
        super().__init__(count=max_kills, **kw)
        self.include_controller = include_controller
        self.kills: list[str] = []

    async def fire(self, ctx: NemesisContext):
        from foundationdb_tpu.sim.workloads import FaultInjector

        cluster = ctx.cluster
        rng = ctx.loop.rng
        gen = cluster.controller.generation
        victims = sorted(gen.heartbeat_eps)
        if self.include_controller and getattr(cluster, "cc_heartbeats", {}):
            victims.append(cluster.controller.identity)
        victim = victims[rng.randrange(len(victims))]
        helper = FaultInjector(cluster, max_kills=0)
        if not helper._safe_to_kill(gen, victim):
            return False
        self.kills.append(victim)
        ctx.bump("kills")
        ctx.record(self.name, victim=victim)
        cluster.net.kill(victim)


class ResolverKill(Nemesis):
    """Kill one RESOLVER of the current generation, anchored mid-traffic.

    The wave-commit composition this exists for (ISSUE 13): under the
    role-level global wave protocol a resolver dies BETWEEN edge
    exchanges — in-flight batches lose a shard mid-two-phase, the commit
    proxy's retries break, the batch fails into commit_unknown_result,
    and recovery re-forms the chain with fresh resolvers whose NEXT
    windows must again produce byte-identical global schedules (the
    campaign gates exact reordered/cycle counters accumulated AFTER the
    recovery). ``after_acked`` anchors the kill on the workloads' shared
    acked counter so it provably lands mid-stream."""

    name = "resolver_kill"

    def __init__(self, index: "int | None" = None, after_acked: int = 0,
                 **kw):
        kw.setdefault("count", 1)
        super().__init__(**kw)
        self.index = index
        self.after_acked = after_acked
        self.kills: list[str] = []

    async def fire(self, ctx: NemesisContext):
        cluster = ctx.cluster
        while ctx.counters.get("acked", 0) < self.after_acked:
            if ctx.stopped:
                return False
            await ctx.loop.sleep(0.02)
        gen = cluster.controller.generation
        victims = sorted(p for p in gen.heartbeat_eps if "resolver" in p)
        if not victims:
            return False
        idx = (self.index if self.index is not None
               else ctx.loop.rng.randrange(len(victims)))
        victim = victims[idx % len(victims)]
        self.kills.append(victim)
        ctx.bump("kills")
        ctx.bump("resolver_kills")
        ctx.record(self.name, victim=victim)
        cluster.net.kill(victim)


class StorageReboot(Nemesis):
    """Kill a random storage server's process, then revive it after
    ``down_s`` and restart its pull loop — the machine-reboot mode where
    the disk survives (cluster.heal_region's single-storage analogue)."""

    name = "storage_reboot"

    def __init__(self, down_s: float = 0.5, **kw):
        super().__init__(**kw)
        self.down_s = down_s

    async def fire(self, ctx: NemesisContext):
        cluster = ctx.cluster
        loop = ctx.loop
        procs = cluster.storage_procs()
        live = [
            (i, p) for i, p in enumerate(procs)
            if (cluster.process_prefix + p) not in loop.dead_processes
        ]
        if len(live) <= 1:
            return False  # keep at least one storage serving
        i, proc = live[loop.rng.randrange(len(live))]
        ctx.bump("storage_reboots")
        ctx.record(self.name, storage=proc)
        cluster.net.kill(proc)
        await loop.sleep(self.down_s)
        cluster.net.reboot(proc)
        loop.spawn(cluster.storages[i].run(),
                   process=cluster.process_prefix + proc,
                   name=f"storage{i}.run")


# -- network faults -----------------------------------------------------------


def _fault_procs(cluster) -> list[str]:
    gen = cluster.controller.generation
    return sorted(gen.heartbeat_eps) + cluster.storage_procs() + ["<main>"]


class PairPartition(Nemesis):
    """Transient partition between two random processes."""

    name = "pair_partition"

    def __init__(self, length: float = 0.6, **kw):
        super().__init__(**kw)
        self.length = length

    async def fire(self, ctx: NemesisContext):
        cluster = ctx.cluster
        rng = ctx.loop.rng
        procs = _fault_procs(cluster)
        a = procs[rng.randrange(len(procs))]
        b = procs[rng.randrange(len(procs))]
        if a == b:
            return False
        ctx.bump("partitions")
        ctx.record(self.name, a=a, b=b)
        cluster.net.partition(a, b)
        await ctx.loop.sleep(self.length)
        cluster.net.heal(a, b)


class RegionPartition(Nemesis):
    """Sever (or blackout) the active region for ``length`` virtual
    seconds; multi-region clusters must fail over and, on heal, catch the
    region back up. mode='partition' keeps the region alive-but-severed
    (the zombie-generation case); mode='fail' kills it outright."""

    name = "region_partition"

    def __init__(self, length: float = 3.0, mode: str = "partition", **kw):
        super().__init__(**kw)
        assert mode in ("partition", "fail"), mode
        self.length = length
        self.mode = mode

    async def fire(self, ctx: NemesisContext):
        cluster = ctx.cluster
        if not cluster.multi_region:
            return False
        region = cluster.active_region
        ctx.bump("region_faults")
        ctx.record(self.name, region=region, mode=self.mode)
        if self.mode == "partition":
            cluster.net.partition_region(region + "/")
            await ctx.loop.sleep(self.length)
            cluster.net.heal_region_partition(region + "/")
        else:
            cluster.net.fail_region(region + "/")
            await ctx.loop.sleep(self.length)
            cluster.heal_region(region)


class ClogStorm(Nemesis):
    """Clog several random links at once (slow-but-alive, no failure
    detector fires). ``targets``: optional list of [src_prefix, dst_prefix]
    pairs — every current process pair matching the prefixes is clogged,
    so campaigns can aim the storm at one subsystem boundary (e.g.
    proxy→resolver) across generations (role names carry .e{epoch})."""

    name = "clog_storm"

    def __init__(self, links: int = 3, factor: float = 80.0,
                 length: float = 0.4, targets: list | None = None, **kw):
        super().__init__(**kw)
        self.links = links
        self.factor = factor
        self.length = length
        self.targets = targets

    def _targeted_pairs(self, cluster) -> list[tuple[str, str]]:
        procs = _fault_procs(cluster)
        pairs = []
        for src_pfx, dst_pfx in self.targets:
            srcs = [p for p in procs if p.startswith(src_pfx)]
            dsts = [p for p in procs if p.startswith(dst_pfx)]
            pairs.extend((a, b) for a in srcs for b in dsts if a != b)
        return pairs

    async def fire(self, ctx: NemesisContext):
        cluster = ctx.cluster
        rng = ctx.loop.rng
        if self.targets:
            pairs = self._targeted_pairs(cluster)
        else:
            procs = _fault_procs(cluster)
            pairs = []
            for _ in range(self.links):
                a = procs[rng.randrange(len(procs))]
                b = procs[rng.randrange(len(procs))]
                if a != b:
                    pairs.append((a, b))
        if not pairs:
            return False
        for a, b in pairs:
            cluster.net.clog(a, b, factor=self.factor,
                             duration=self.length * (0.5 + rng.random()))
        ctx.bump("clogs", len(pairs))
        ctx.record(self.name, links=len(pairs))


# -- data-plane faults --------------------------------------------------------


class DataMovementKick(Nemesis):
    """Force shard moves of a key range between storage teams while
    traffic (and possibly an audit) runs — the DD dual-tag window under
    adversarial timing. Failed moves (partitioned member, mid-recovery)
    are recorded and tolerated: DD's own rollback is part of what the
    campaign exercises."""

    name = "data_movement"

    def __init__(self, begin: str = "", end: str = "\xff", **kw):
        super().__init__(**kw)
        self.begin = begin.encode() if isinstance(begin, str) else begin
        self.end = end.encode() if isinstance(end, str) else end

    async def fire(self, ctx: NemesisContext):
        cluster = ctx.cluster
        dd = getattr(cluster, "data_distributor", None)
        if dd is None:
            raise CampaignCheckFailed(
                "DataMovementKick needs dataDistribution = true")
        n = len(cluster.storage_eps)
        k = max(1, cluster.n_replicas)
        dst = tuple((self.fired + j) % n for j in range(k))
        try:
            await dd.move_shard(self.begin, self.end, dst)
            ctx.bump("moves_ok")
        except Exception as e:  # rollback path exercised; recorded
            ctx.bump("moves_failed")
            ctx.record(self.name + ".failed", error=type(e).__name__)
            return
        ctx.record(self.name, dst=list(dst))


class DeviceStall(Nemesis):
    """Transiently multiply every live resolver's modeled dispatch cost by
    ``factor`` for ``length`` virtual seconds — device-side interference
    (TPU preemption, a co-tenant's burst, an XLA recompile): dispatch
    capacity collapses while open-loop traffic keeps arriving, so the
    resolve queue must absorb the backlog, the ratekeeper's
    resolver_queue backpressure must engage, and the queue must fully
    drain once the device recovers. The composition that makes the
    sched × ratekeeper contract deterministically testable: without a
    stall, commit arrivals breathe in lockstep with dispatch completions
    (reads wait on storage catch-up, which waits on the commit pipeline)
    and depth self-limits right below the soft threshold."""

    name = "device_stall"

    def __init__(self, factor: float = 12.0, length: float = 1.5,
                 after_acked: int = 0, **kw):
        kw.setdefault("count", 1)
        super().__init__(**kw)
        self.factor = factor
        self.length = length
        # Wall-clock scheduling misses: cluster startup/recovery eats a
        # seed-dependent slice of the front of the run, so `at` can fire
        # a stall before the storm's arrival window even opens (campaign
        # smoke found depth peaking at 10-14 of 16). Anchoring on the
        # workloads' shared acked counter provably lands it mid-traffic.
        self.after_acked = after_acked

    async def fire(self, ctx: NemesisContext):
        cluster = ctx.cluster
        while ctx.counters.get("acked", 0) < self.after_acked:
            if ctx.stopped:
                return False
            await ctx.loop.sleep(0.02)
        saved = [(r, r.dispatch_cost_s) for r in cluster.resolvers]
        if not saved or not any(c for _r, c in saved):
            raise CampaignCheckFailed(
                "DeviceStall needs resolverDispatchCost > 0 (a stall on a "
                "zero-cost device model is a no-op)")
        for r, c in saved:
            r.dispatch_cost_s = c * self.factor
        ctx.bump("device_stalls")
        ctx.record(self.name, factor=self.factor, length=self.length)
        try:
            await ctx.loop.sleep(self.length)
        finally:
            for r, c in saved:
                r.dispatch_cost_s = c


class ConsistencyAudit(Nemesis):
    """Run the cluster-wide consistency checker LIVE, mid-storm — the
    composition the checker's moved_rescans / re-snapshot machinery exists
    for. ``kick_move`` additionally fires a shard move of the audited
    range while the scan is in flight, forcing the
    too_old → re-snapshot → wrong_shard_server → re-resolve path.

    Exact gate: any divergence is a defect (byte parity is unconditional
    — movement and clogs may slow the audit, never falsify it)."""

    name = "consistency_audit"

    def __init__(self, begin: str = "", end: str = "\xff",
                 kick_move: bool = False, chunk_bytes: int = 512,
                 bytes_per_s: float = 0.0, **kw):
        super().__init__(**kw)
        self.begin = begin.encode() if isinstance(begin, str) else begin
        self.end = end.encode() if isinstance(end, str) else end
        self.kick_move = kick_move
        self.chunk_bytes = chunk_bytes
        # Slow pacing (bytes/s) stretches the walk across virtual seconds
        # so scheduled faults reliably land MID-SCAN; 0 = default pacer.
        self.bytes_per_s = bytes_per_s

    async def fire(self, ctx: NemesisContext):
        from foundationdb_tpu.consistency.checker import ConsistencyChecker
        from foundationdb_tpu.consistency.scanner import RatekeeperPacer

        cluster = ctx.cluster
        pacer = None
        if self.bytes_per_s:
            pacer = RatekeeperPacer(ctx.loop,
                                    getattr(cluster, "ratekeeper_ep", None),
                                    bytes_per_s=self.bytes_per_s)
        checker = ConsistencyChecker(cluster, ctx.db, begin=self.begin,
                                     end=self.end,
                                     chunk_bytes=self.chunk_bytes,
                                     pacer=pacer)
        mover = None
        scanning = [True]
        if self.kick_move and getattr(cluster, "data_distributor", None):
            async def kick():
                # Keep flipping the audited range between teams for as
                # long as the scan runs: a single move can miss the scan
                # window (seed-dependent — campaign smoke found it), a
                # rotation cannot.
                rotation = 0
                while scanning[0]:
                    await ctx.loop.sleep(0.05 if rotation == 0 else 0.25)
                    n = len(cluster.storage_eps)
                    k = max(1, cluster.n_replicas)
                    dst = tuple((1 + rotation + j) % n for j in range(k))
                    rotation += 1
                    try:
                        await cluster.data_distributor.move_shard(
                            self.begin, self.end, dst)
                        ctx.bump("moves_ok")
                    except Exception:
                        ctx.bump("moves_failed")

            mover = ctx.loop.spawn(kick(), name="audit.kick_move")
        try:
            report = await checker.run()
        finally:
            scanning[0] = False
        if mover is not None:
            await mover
        ctx.reports.append(report)
        ctx.bump("audits")
        ctx.bump("moved_rescans", report["moved_rescans"])
        ctx.record(self.name, status=report["status"],
                   moved_rescans=report["moved_rescans"],
                   resnapshots=report["resnapshots"])
        if report["divergences"]:
            ctx.defects.append(
                f"live audit divergent: {report['divergences'][:2]!r}")

    async def verify(self, ctx: NemesisContext, db) -> None:
        bad = [r for r in ctx.reports if r["status"] == "divergent"]
        if bad:
            raise CampaignCheckFailed(
                f"{len(bad)} live audits reported divergence")


class DRSwitchover(Nemesis):
    """fdbdr switch mid-run: lock the primary, drain DR through every
    acked commit, byte-compare BOTH sides at the drain point (exact
    parity gate), then release clients to the secondary via
    ctx.flags['failover'].

    ``after_acked``: wait until the workloads' shared 'acked' counter
    reaches this many commits first, so the switchover provably lands
    mid-traffic (and, with FailoverZipfRepair, mid-repair)."""

    name = "dr_switchover"

    def __init__(self, after_acked: int = 0, **kw):
        kw.setdefault("count", 1)
        super().__init__(**kw)
        self.after_acked = after_acked
        self.parity: dict | None = None

    async def fire(self, ctx: NemesisContext):
        agent = ctx.extra.get("dr_agent")
        if agent is None:
            raise CampaignCheckFailed("DRSwitchover needs dr = true")
        while ctx.counters.get("acked", 0) < self.after_acked:
            if ctx.stopped:
                # Workloads finished below the anchor (spec mistuned):
                # decline instead of spinning past the end of the run —
                # verify() then fails crisply with "never fired".
                return False
            await ctx.loop.sleep(0.02)
        target = await agent.switchover()
        ctx.record(self.name, drained_through=target)
        # Parity snapshot at the drain point: primary is locked+quiesced,
        # the secondary static until the flag below releases the clients —
        # both sides are frozen, so a plain range compare is exact.
        src_rows = await self._dump(ctx.db)
        dst_rows = await self._dump(ctx.extra["dst_db"])
        self.parity = {
            "rows": len(src_rows),
            "equal": src_rows == dst_rows,
            "drained_through": target,
        }
        if src_rows != dst_rows:
            ctx.defects.append(
                f"DR parity broken at switchover: primary {len(src_rows)} "
                f"rows vs secondary {len(dst_rows)}")
        ctx.flags["failover"] = True

    @staticmethod
    async def _dump(db):
        async def body(tr):
            tr.set_option("lock_aware")
            return await tr.get_range(b"", b"\xff", limit=1_000_000)

        return await db.run(body)

    async def verify(self, ctx: NemesisContext, db) -> None:
        if self.parity is None:
            raise CampaignCheckFailed("DR switchover never fired")
        if not self.parity["equal"]:
            raise CampaignCheckFailed(
                f"byte parity failed at switchover: {self.parity}")


# -- adversarial traffic ------------------------------------------------------


class WriteStorm(Nemesis):
    """Hot-range write storm: ``clients`` concurrent streams of
    read-modify-write increments over ``keys`` keys under ``prefix`` at
    the given admission ``priority`` — the contention/lane-flood traffic
    shape. Exact accounting: idempotency markers make the conservation
    sum immune to commit_unknown_result retries, so verify() can require
    sum(keys) == acked increments EXACTLY even under kills.

    One fire runs the whole storm (count=1); schedule with ``at``."""

    name = "write_storm"

    def __init__(self, prefix: str = "storm/", keys: int = 2,
                 clients: int = 4, txns: int = 40,
                 priority: str = "default", open_loop: bool = False,
                 arrival_s: float = 0.003, blind: bool = False,
                 width: int = 1, **kw):
        kw.setdefault("count", 1)
        super().__init__(**kw)
        self.prefix = prefix.encode() if isinstance(prefix, str) else prefix
        self.keys = keys
        self.clients = clients
        self.txns = txns
        assert priority in ("system", "default", "batch"), priority
        self.priority = priority
        # Open-loop mode: transactions arrive on a seeded ~arrival_s
        # schedule as INDEPENDENT tasks (millions-of-clients shape) — the
        # arrival rate does not slow down when the cluster does, which is
        # what actually drives resolver-queue depth and the ratekeeper's
        # backpressure loop; closed-loop clients self-throttle and can't.
        self.open_loop = open_loop
        self.arrival_s = arrival_s
        # Blind mode — the true lane-flood shape: each txn is one
        # idempotent SET of its own unique key, NO reads. Read-bearing
        # txns convoy with the commit pipeline (reads wait on storage
        # catch-up, which trails resolution by a full dispatch — campaign
        # smoke measured the release waves), so only blind traffic keeps
        # arriving at client rate while the device stalls. Exactness is
        # preserved: unique keys make retries idempotent, so
        # count(keys) == acked is still an exact conservation gate.
        self.blind = blind
        # Sets a blind transaction (`width` unique keys): the BULK shape.
        # A commit proxy keeps small commits until its last batch is back
        # from the resolvers and sends them as one; wide ones leave at
        # the plain cadence (CommitProxy._held), so only a bulk storm
        # piles batches up behind a stalled resolver.
        self.width = width

    def _key(self, i: int) -> bytes:
        return self.prefix + b"%04d" % i

    def _counter_key(self) -> str:
        return "storm_acked:" + self.prefix.decode()

    async def fire(self, ctx: NemesisContext):
        from foundationdb_tpu.core.types import strinc

        db = ctx.db
        loop = ctx.loop

        async def init(tr):
            self._set_priority(tr)
            tr.clear_range(self.prefix, strinc(self.prefix))
            for i in range(self.keys):
                tr.set(self._key(i), struct.pack("<q", 0))

        await db.run(init)

        async def one(cid: int, seq: int):
            if self.blind:
                unique = self.prefix + b"bl/%02d/%05d" % (cid, seq)

                async def body(tr, unique=unique):
                    self._set_priority(tr)
                    for j in range(self.width):
                        tr.set(unique + b"/%02d" % j, b"")
            else:
                k = self._key(loop.rng.randrange(self.keys))
                marker = (self.prefix + b"mk/%02d/%04d" % (cid, seq))

                async def body(tr, k=k, marker=marker):
                    self._set_priority(tr)
                    if await tr.get(marker) is not None:
                        return  # earlier attempt landed: exactly-once
                    tr.set(marker, b"")
                    (v,) = struct.unpack("<q", await tr.get(k))
                    tr.set(k, struct.pack("<q", v + 1))

            await db.run(body)
            ctx.bump(self._counter_key())
            ctx.bump("acked")

        if self.open_loop:
            tasks = []
            for seq in range(self.txns):
                tasks.append(loop.spawn(one(0, seq), name=f"storm.ol{seq}"))
                await loop.sleep(self.arrival_s * (0.5 + loop.rng.random()))
            await all_of(tasks)
        else:
            async def client(cid: int):
                for seq in range(self.txns // self.clients):
                    await one(cid, seq)

            await all_of([
                loop.spawn(client(i), name=f"storm.{self.priority}{i}")
                for i in range(self.clients)
            ])
        ctx.record(self.name, prefix=self.prefix.decode(),
                   acked=ctx.counters.get(self._counter_key(), 0))

    def _set_priority(self, tr) -> None:
        if self.priority == "batch":
            tr.set_option("priority_batch")
        elif self.priority == "system":
            tr.set_option("priority_system_immediate")

    async def verify(self, ctx: NemesisContext, db) -> None:
        acked = ctx.counters.get(self._counter_key(), 0)
        if self.blind:
            async def body(tr):
                rows = await tr.get_range(self.prefix + b"bl/",
                                          self.prefix + b"bl0",
                                          limit=1_000_000)
                return len(rows)

            landed = await db.run(body)
            if landed != acked * self.width:
                raise CampaignCheckFailed(
                    f"blind storm {self.prefix!r} not conserved: {landed} "
                    f"unique keys != {acked} acked txns x {self.width} "
                    f"(lost write)")
            return
        total = 0
        for i in range(self.keys):
            async def body(tr, i=i):
                return await tr.get(self._key(i))

            raw = await db.run(body)
            total += struct.unpack("<q", raw)[0] if raw else 0
        if total != acked:
            raise CampaignCheckFailed(
                f"write storm {self.prefix!r} not conserved: sum {total} != "
                f"{acked} acked increments (lost or double-applied update)")


class SystemProbe(Nemesis):
    """Latency probe stream on the system (or default) lane: one small
    txn per fire, commit latency recorded in ctx.latencies[lane]. The
    campaign gates the lane's p99 — bounded system-lane latency while a
    batch flood rages is the lanes subsystem's whole contract."""

    name = "system_probe"

    def __init__(self, lane: str = "system", **kw):
        kw.setdefault("every", 0.1)
        kw.setdefault("count", 0)
        super().__init__(**kw)
        assert lane in ("system", "default"), lane
        self.lane = lane

    async def fire(self, ctx: NemesisContext):
        db = ctx.db
        t0 = ctx.loop.now

        async def body(tr):
            if self.lane == "system":
                tr.set_option("priority_system_immediate")
            tr.set(b"probe/%s" % self.lane.encode(),
                   struct.pack("<q", self.fired))

        await db.run(body)
        ctx.latencies.setdefault(self.lane, []).append(ctx.loop.now - t0)
        ctx.bump("probes")


class BackpressureMonitor(Nemesis):
    """Samples the ratekeeper's resolver-queue signal every fire; verify()
    requires the backpressure loop ENGAGED (worst_resolver_queue reached
    ``engage_min``) and then DRAINED (final resolver queue empty) — the
    exact sched × network contract, not a liveness shrug."""

    name = "backpressure_monitor"

    def __init__(self, engage_min: int | None = None, **kw):
        kw.setdefault("every", 0.05)
        kw.setdefault("count", 0)
        super().__init__(**kw)
        self.engage_min = engage_min
        self.max_queue = 0
        self.engaged_reasons: set[str] = set()

    async def fire(self, ctx: NemesisContext):
        rk = getattr(ctx.cluster, "ratekeeper", None)
        if rk is None:
            return False
        self.max_queue = max(self.max_queue, rk.worst_resolver_queue)
        if rk.limiting_reason != "none":
            self.engaged_reasons.add(rk.limiting_reason)

    async def verify(self, ctx: NemesisContext, db) -> None:
        from foundationdb_tpu.runtime.ratekeeper import Ratekeeper

        engage_min = (Ratekeeper.RQ_SOFT if self.engage_min is None
                      else self.engage_min)
        if self.max_queue < engage_min:
            raise CampaignCheckFailed(
                f"resolver_queue backpressure never engaged: max depth "
                f"{self.max_queue} < {engage_min}")
        depths = [r.sched.queue_depth for r in ctx.cluster.resolvers]
        if any(depths):
            raise CampaignCheckFailed(
                f"resolver queues never drained: depths {depths}")
        ctx.record(self.name, max_queue=self.max_queue,
                   reasons=sorted(self.engaged_reasons))


class TagQuotaAbuse(Nemesis):
    """Quota abuse: set a tag tps quota, then flood GRV admission with
    that tag from ``clients`` greedy streams for one fire (count=1).
    verify(): admissions must stay under the token-bucket's EXACT upper
    bound quota·elapsed + burst — across recoveries (a kill must not
    reset the operator's quota; campaign-found defect class)."""

    name = "tag_quota_abuse"

    def __init__(self, tag: str = "abuser", quota: float = 12.0,
                 clients: int = 8, duration: float = 4.0, **kw):
        kw.setdefault("count", 1)
        super().__init__(**kw)
        self.tag = tag
        self.quota = quota
        self.clients = clients
        self.duration = duration
        self.admitted = 0
        self.elapsed = 0.0
        self.throttled_seen = 0  # high-water proxy tag_throttled sample

    async def fire(self, ctx: NemesisContext):
        cluster = ctx.cluster
        await cluster.ratekeeper_ep.set_tag_quota(self.tag, self.quota)
        # Let the proxies' rate poll pick the quota up before measuring:
        # the bucket exists only once get_rates() has been seen.
        await ctx.loop.sleep(0.25)
        # On an authz-armed cluster the abuser is a legitimate (tokened)
        # tenant of its own prefix — quota throttling and tenant
        # isolation are orthogonal, and an untokened abuser would be
        # denied at the read boundary before ever exercising the bucket.
        token = None
        priv = getattr(cluster, "authz_private_pem", None)
        if priv is not None:
            from foundationdb_tpu.runtime.authz import mint_token

            token = mint_token(priv, [b"quota/"], expires_at=1e12)
        loop = ctx.loop
        t0 = loop.now
        deadline = t0 + self.duration

        async def abuser(cid: int):
            while loop.now < deadline and not ctx.stopped:
                tr = ctx.db.transaction()
                tr.set_option("tag", self.tag)
                if token is not None:
                    tr.set_option("authorization_token", token)
                try:
                    await tr.get(b"quota/probe")
                except FdbError:
                    # Killed proxy / recovery: not an admission.
                    await loop.sleep(0.05)
                    continue
                self.admitted += 1
                ctx.bump("quota_admitted")

        async def sampler():
            # tag_throttled is per-proxy-generation (recoveries recruit
            # fresh proxies), so keep the max ever observed: any nonzero
            # sample proves the bucket actually pushed back.
            while loop.now < deadline and not ctx.stopped:
                self.throttled_seen = max(
                    self.throttled_seen,
                    max((p.tag_throttled for p in cluster.grv_proxies),
                        default=0))
                await loop.sleep(0.05)

        sampling = loop.spawn(sampler(), name="quota.sampler")
        await all_of([
            loop.spawn(abuser(i), name=f"quota.abuser{i}")
            for i in range(self.clients)
        ])
        await sampling
        self.elapsed = loop.now - t0
        ctx.record(self.name, admitted=self.admitted,
                   throttled_seen=self.throttled_seen,
                   elapsed=round(self.elapsed, 3))

    async def verify(self, ctx: NemesisContext, db) -> None:
        from foundationdb_tpu.runtime.grv_proxy import GrvProxy

        if self.elapsed <= 0:
            raise CampaignCheckFailed("quota abuse never ran")
        if self.admitted == 0:
            raise CampaignCheckFailed(
                "quota abuse admitted NOTHING — the gate is vacuous "
                "(abuser denied outright? cluster never served?)")
        if self.throttled_seen == 0:
            raise CampaignCheckFailed(
                "tag bucket never pushed back — the abuse load did not "
                "bind the quota, so enforcement was not exercised")
        # Token-bucket exact bound: rate·elapsed plus one full burst
        # allowance (bucket cap) and the per-client in-flight edge at the
        # deadline. Buckets start at ZERO on every proxy generation, and
        # tagged admission is deferred until a generation has seen rates
        # (the campaign-found fix in GrvProxy), so recoveries never add
        # burst — one cap covers the whole window.
        bound = (self.quota * self.elapsed + GrvProxy.MAX_TAG_TOKENS
                 + self.clients)
        if self.admitted > bound:
            raise CampaignCheckFailed(
                f"tag quota not enforced: {self.admitted} admissions > "
                f"bound {bound:.0f} (quota {self.quota}/s over "
                f"{self.elapsed:.2f}s) — quota lost (recovery?)")


class CrossTenantProbe(Nemesis):
    """Tenant-isolation probe under faults: carries a token scoped to its
    own prefix and, every fire, attempts an out-of-scope write that must
    end in a DEFINITIVE PermissionDenied whichever generation serves it.
    Any admission is cross-tenant leakage — an immediate defect."""

    name = "cross_tenant_probe"

    def __init__(self, prefix: str = "ctp/", **kw):
        kw.setdefault("every", 0.3)
        kw.setdefault("count", 0)
        super().__init__(**kw)
        self.prefix = prefix.encode() if isinstance(prefix, str) else prefix
        self._token = None
        self.denied = 0

    async def fire(self, ctx: NemesisContext):
        from foundationdb_tpu.core.errors import PermissionDenied
        from foundationdb_tpu.runtime.authz import mint_token

        priv = getattr(ctx.cluster, "authz_private_pem", None)
        if priv is None:
            raise CampaignCheckFailed(
                "CrossTenantProbe needs [campaign.cluster] authz = true")
        if self._token is None:
            self._token = mint_token(priv, [self.prefix], expires_at=1e12)

        async def in_scope(tr):
            tr.set_option("authorization_token", self._token)
            tr.set(self.prefix + b"n/%05d" % self.fired, b"v")

        await ctx.db.run(in_scope)  # the token itself works
        ctx.bump("acked")

        async def out_of_scope(tr):
            tr.set_option("authorization_token", self._token)
            tr.set(b"other-tenant/x", b"leak")

        try:
            await ctx.db.run(out_of_scope)
        except PermissionDenied:
            self.denied += 1
            return
        ctx.defects.append(
            f"cross-tenant write ADMITTED at t={ctx.loop.now:.2f}")

    async def verify(self, ctx: NemesisContext, db) -> None:
        if self.fired and self.denied != self.fired:
            raise CampaignCheckFailed(
                f"cross-tenant leakage: {self.fired - self.denied} of "
                f"{self.fired} out-of-scope writes admitted")


# -- registry (TOML name -> class + key mapping) ------------------------------

_COMMON = {"at": "at", "every": "every", "count": "count"}

NEMESIS_REGISTRY: dict[str, tuple[type, dict[str, str]]] = {
    "Kill": (ProcessKiller, {
        **_COMMON, "maxKills": "max_kills",
        "includeController": "include_controller",
    }),
    "ResolverKill": (ResolverKill, {
        **_COMMON, "index": "index", "afterAcked": "after_acked",
    }),
    "StorageReboot": (StorageReboot, {**_COMMON, "downSeconds": "down_s"}),
    "PairPartition": (PairPartition, {**_COMMON, "length": "length"}),
    "RegionPartition": (RegionPartition, {
        **_COMMON, "length": "length", "mode": "mode",
    }),
    "ClogStorm": (ClogStorm, {
        **_COMMON, "links": "links", "factor": "factor",
        "length": "length", "targets": "targets",
    }),
    "DeviceStall": (DeviceStall, {
        **_COMMON, "factor": "factor", "length": "length",
        "afterAcked": "after_acked",
    }),
    "DataMovementKick": (DataMovementKick, {
        **_COMMON, "begin": "begin", "end": "end",
    }),
    "ConsistencyAudit": (ConsistencyAudit, {
        **_COMMON, "begin": "begin", "end": "end",
        "kickMove": "kick_move", "chunkBytes": "chunk_bytes",
        "bytesPerSecond": "bytes_per_s",
    }),
    "DRSwitchover": (DRSwitchover, {**_COMMON, "afterAcked": "after_acked"}),
    "WriteStorm": (WriteStorm, {
        **_COMMON, "prefix": "prefix", "keys": "keys",
        "clients": "clients", "txns": "txns", "priority": "priority",
        "openLoop": "open_loop", "arrivalSeconds": "arrival_s",
        "blind": "blind", "width": "width",
    }),
    "SystemProbe": (SystemProbe, {**_COMMON, "lane": "lane"}),
    "BackpressureMonitor": (BackpressureMonitor, {
        **_COMMON, "engageMin": "engage_min",
    }),
    "TagQuotaAbuse": (TagQuotaAbuse, {
        **_COMMON, "tag": "tag", "quota": "quota",
        "clients": "clients", "duration": "duration",
    }),
    "CrossTenantProbe": (CrossTenantProbe, {**_COMMON, "prefix": "prefix"}),
}
