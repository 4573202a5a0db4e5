"""Nemesis campaigns: cross-subsystem fault orchestration (sim/nemesis.py,
sim/campaigns.py) and the graceful-degradation fixes the campaigns forced.

Two layers under test:

1. The four ROADMAP campaigns as the fast battery — each TOML spec from
   tests/specs/campaigns/ runs end-to-end at a fixed seed under a
   per-spec wall-clock budget, gated on its exact oracles (byte parity,
   conservation sums, admission bounds, bounded lane p99 — never
   "didn't crash"), plus bit-identical seed replay.

2. Regression tests for the campaign-found defects, pinned at the
   subsystem that was fixed: heal_all leaving region partitions/clogs
   behind, tag quotas dying with the ratekeeper generation, tagged GRV
   admission ungated on a fresh proxy, system lane riding the throttled
   default bucket, the ratekeeper missing sub-poll queue spikes, and the
   consistency checker's probe path crashing on a mid-probe shard move.
"""

import json
import os
import time

import pytest

from foundationdb_tpu.client.ryw import open_database
from foundationdb_tpu.runtime.flow import Loop
from foundationdb_tpu.runtime.ratekeeper import Ratekeeper
from foundationdb_tpu.sim.campaigns import load_campaigns, run_campaign
from foundationdb_tpu.sim.cluster import SimCluster
from foundationdb_tpu.sim.nemesis import NEMESIS_REGISTRY
from foundationdb_tpu.sim.network import SimNetwork

CAMPAIGN_DIR = os.path.join(os.path.dirname(__file__), "specs", "campaigns")
CAMPAIGN_SPECS = sorted(
    f for f in os.listdir(CAMPAIGN_DIR) if f.endswith(".toml"))

# Per-spec wall-clock budget for the fast battery (the virtual-time
# budget lives in each TOML): observed 5-11s/run on this container; a
# blowout here means a campaign regressed into the slow battery.
FAST_WALL_BUDGET_S = 120.0


def _fail_text(result: dict) -> str:
    return "\n".join(
        f"[{f['check']}]\n{f['error']}" for f in result["failures"])


class TestCampaignBattery:
    """The four cross-subsystem campaigns, promoted into the fast
    `-m 'not slow'` battery (ROADMAP: adversarial sim campaigns)."""

    @pytest.mark.parametrize("spec_file", CAMPAIGN_SPECS)
    def test_campaign_green(self, spec_file):
        t0 = time.perf_counter()
        results = run_campaign(os.path.join(CAMPAIGN_DIR, spec_file), seed=0)
        wall = time.perf_counter() - t0
        assert results
        for r in results:
            assert r["ok"], f"{spec_file} seed=0:\n{_fail_text(r)}"
            # Exact gates actually ran (no vacuous pass).
            assert r["checks"], f"{spec_file}: no checks evaluated"
        assert wall < FAST_WALL_BUDGET_S, (
            f"{spec_file}: {wall:.0f}s blew the fast-battery budget")

    def test_all_four_roadmap_compositions_present(self):
        titles = set()
        for f in CAMPAIGN_SPECS:
            for spec in load_campaigns(os.path.join(CAMPAIGN_DIR, f)):
                titles.add(spec.title)
        assert {"ConsistencyVsResharding", "DRFailoverMidRepair",
                "LaneStarvationHotStorm", "QuotaAbuseUnderKills"} <= titles

    def test_seed_replays_bit_identically(self):
        """The acceptance contract: (spec, seed) is the whole schedule.
        Two fresh runs at one seed must produce byte-identical result
        records (counters, events, virtual timings, gate details)."""
        path = os.path.join(CAMPAIGN_DIR, "DRFailoverMidRepair.toml")
        a = run_campaign(path, seed=3)
        b = run_campaign(path, seed=3)
        assert (json.dumps(a, sort_keys=True, default=str)
                == json.dumps(b, sort_keys=True, default=str))

    def test_failing_seed_replays_bit_identically(self):
        """A FAILURE replays exactly too — the failing gate, counters and
        traceback text all come out byte-identical from the replay line's
        (spec, seed) pair."""
        spec = """
[[campaign]]
title = 'VacuousGate'
budget = 120.0

[campaign.cluster]
tlogs = 2
storages = 2

[[campaign.workload]]
testName = 'Cycle'
nodeCount = 6
transactionCount = 8
clientCount = 2

[campaign.checks]
ackedMin = 999999
"""
        a = run_campaign(spec, seed=7)
        b = run_campaign(spec, seed=7)
        assert not a[0]["ok"]
        assert (json.dumps(a, sort_keys=True, default=str)
                == json.dumps(b, sort_keys=True, default=str))

    def test_typoed_schedule_keys_rejected(self):
        """A typo'd knob (`afterAck` for `afterAcked`) must be a parse
        error, not a silently-untested composition."""
        base = """
[[campaign]]
title = 'T'
[[campaign.workload]]
testName = 'Cycle'
%s
[[campaign.action]]
name = 'DeviceStall'
%s
"""
        with pytest.raises(ValueError, match="afterAck"):
            load_campaigns(base % ("", "afterAck = 80"))
        with pytest.raises(ValueError, match="nodeCont"):
            load_campaigns(base % ("nodeCont = 5", ""))

    def test_registry_keys_map_to_constructor_params(self):
        """Every TOML key in every registry mapping must name a real
        constructor parameter — a typo would otherwise surface only as a
        TypeError deep inside a campaign run."""
        import inspect

        for name, (cls, mapping) in NEMESIS_REGISTRY.items():
            params = set()
            for klass in cls.__mro__:
                if klass is object:
                    continue
                params |= set(inspect.signature(klass.__init__).parameters)
            for toml_key, kwarg in mapping.items():
                assert kwarg in params, (
                    f"{name}: TOML key {toml_key!r} maps to unknown "
                    f"kwarg {kwarg!r}")


# ---------------------------------------------------------------------------
# Campaign-found defect regressions
# ---------------------------------------------------------------------------


class TestHealAllClearsEverything:
    """Satellite: heal_all cleared pair partitions and clogs but left
    region partitions standing — the campaign quiesce path then audited a
    still-severed region (campaign find)."""

    def test_heal_all_clears_pairs_clogs_and_region_partitions(self):
        loop = Loop(seed=1)
        net = SimNetwork(loop)
        net.partition("a", "b")
        net.clog("a", "c", factor=10.0, duration=60.0)
        net.partition_region("pri/")
        assert net._partitions and net._clogs and net._partitioned_regions
        net.heal_all()
        assert not net._partitions
        assert not net._clogs
        assert not net._partitioned_regions

    def test_heal_all_leaves_dead_regions_to_heal_region(self):
        """Dead regions are NOT link faults: their processes are down and
        need the heal_region reboot path, so heal_all must not silently
        'heal' them into a half-alive state."""
        loop = Loop(seed=1)
        net = SimNetwork(loop)
        net.fail_region("pri/")
        net.heal_all()
        assert net.region_dead("pri/")

    def test_reset_faults_is_the_quiesce_contract(self):
        loop = Loop(seed=1)
        net = SimNetwork(loop)
        net.partition("a", "b")
        net.partition_region("pri/")
        net.reset_faults()
        assert not net._partitions and not net._partitioned_regions


class TestQuotaSurvivesRecovery:
    """Campaign find (QuotaAbuseUnderKills): a kill-triggered recovery
    recruited a fresh Ratekeeper with an EMPTY tag_quotas dict — every
    operator quota silently evaporated at each generation change. Fix:
    the cluster shares one quota dict across generations."""

    def test_tag_quota_survives_generation_change(self):
        loop = Loop(seed=11)
        c = SimCluster(loop=loop, seed=11, n_tlogs=2, n_storages=2)
        db = open_database(c)

        async def main():
            async def w(tr):
                tr.set(b"q/seed", b"v")

            await db.run(w)
            await c.ratekeeper_ep.set_tag_quota("abuser", 7.0)
            rk_before = c.ratekeeper
            assert rk_before.tag_quotas == {"abuser": 7.0}

            c.net.kill("tlog0")  # force a full recovery
            deadline = loop.now + 60
            while ((c.controller.generation.epoch < 2
                    or c.controller._recovering) and loop.now < deadline):
                await loop.sleep(0.1)
            assert c.controller.generation.epoch >= 2

            rk_after = c.ratekeeper
            assert rk_after is not rk_before  # a real re-recruitment
            assert rk_after.tag_quotas == {"abuser": 7.0}
            # And the new generation ENFORCES it: rates carry the tag.
            rates = await rk_after.get_rates()
            assert rates["tag_rates"] == {"abuser": 7.0}
            return "ok"

        assert loop.run(main(), timeout=120) == "ok"


class _FakeSequencer:
    async def get_live_committed_version(self):
        return 42


class TestFreshProxyTagDeferral:
    """Campaign find (QuotaAbuseUnderKills): a freshly recruited GRV
    proxy admitted TAGGED traffic through its initial token burst before
    it had ever seen tag rates — one free, quota-bypassing burst per
    recovery. Fix: tagged admission defers until the first rate poll."""

    @staticmethod
    def _proxy(loop, rk):
        from foundationdb_tpu.runtime.grv_proxy import GrvProxy

        return GrvProxy(loop, _FakeSequencer(), rk)

    def test_tagged_held_until_rates_seen_untagged_flows(self):
        from foundationdb_tpu.core.errors import FdbError  # noqa: F401

        loop = Loop(seed=0)
        state = {"ready": False}

        class LateRk:
            async def get_rates(self, poller_id=None, grvs_served=None):
                if not state["ready"]:
                    raise RuntimeError("ratekeeper unreachable (recovery)")
                return {"tps_limit": 1e6, "batch_tps_limit": 1e6,
                        "tag_rates": {"abuser": 200.0}}

        proxy = self._proxy(loop, LateRk())
        got = {}

        async def main():
            loop.spawn(proxy.run(), name="grv")

            async def tagged():
                got["tagged_at"] = None
                await proxy.get_read_version("default", ["abuser"])
                got["tagged_at"] = loop.now

            loop.spawn(tagged(), name="tagged")
            await loop.sleep(0.3)
            # Initial burst tokens exist, but no rates seen → still held.
            assert got["tagged_at"] is None
            assert proxy.tag_throttled > 0
            state["ready"] = True  # ratekeeper reachable now
            await loop.sleep(0.3)
            assert got["tagged_at"] is not None  # admitted after the poll
            return "ok"

        assert loop.run(main(), timeout=30) == "ok"


class TestSystemLaneBypass:
    """Campaign find (LaneStarvationHotStorm): system-priority txns rode
    the default GRV bucket, so resolver-queue backpressure starved the
    system lane behind the very storm it outranks. Fix: a system queue at
    the proxy, admitted unconditionally, and the client passes its
    priority through instead of folding system into default."""

    def test_system_admitted_while_default_throttled_to_zero(self):
        loop = Loop(seed=0)

        class ZeroRk:  # backpressure clamped everything
            async def get_rates(self, poller_id=None, grvs_served=None):
                return {"tps_limit": 0.0, "batch_tps_limit": 0.0}

        from foundationdb_tpu.runtime.grv_proxy import GrvProxy

        proxy = GrvProxy(loop, _FakeSequencer(), ZeroRk())
        proxy._tokens = proxy._batch_tokens = 0.0  # burst already spent
        got = {}

        async def main():
            loop.spawn(proxy.run(), name="grv")

            async def req(lane):
                got[lane] = await proxy.get_read_version(lane)

            loop.spawn(req("default"), name="d")
            loop.spawn(req("batch"), name="b")
            loop.spawn(req("system"), name="s")
            await loop.sleep(0.4)
            return dict(got)

        out = loop.run(main(), timeout=30)
        assert out.get("system") == 42  # bypassed the clamp
        assert "default" not in out and "batch" not in out  # still queued

    def test_client_priority_passes_through_to_grv(self):
        """The client half: priority_system_immediate must reach the
        proxy AS 'system' (it was silently mapped onto 'default')."""
        loop = Loop(seed=3)
        c = SimCluster(loop=loop, seed=3, n_tlogs=1, n_storages=1)
        db = open_database(c)
        seen = []
        for p in c.grv_proxies:
            orig = p.get_read_version

            def spy(priority="default", tags=None, _orig=orig):
                seen.append(priority)
                return _orig(priority, tags)

            p.get_read_version = spy

        async def main():
            async def body(tr):
                tr.set_option("priority_system_immediate")
                tr.set(b"sys/k", b"v")

            await db.run(body)
            return "ok"

        assert loop.run(main(), timeout=60) == "ok"
        assert "system" in seen


class TestDepthHighWater:
    """Campaign find (LaneStarvationHotStorm): a queue spike that built
    and drained between two 0.1s ratekeeper polls never engaged
    backpressure (true depth 25, ratekeeper saw 8). Fix: the scheduler
    keeps a rolling high-water the ratekeeper reads instead."""

    def test_high_water_outlives_a_drained_spike(self):
        from foundationdb_tpu.sched.resolver_queue import ResolveScheduler

        loop = Loop(seed=5)
        sched = ResolveScheduler(loop, budget_s=0.05)

        async def slow_dispatch(group):
            await loop.sleep(0.001)

        sched.attach(slow_dispatch)

        async def main():
            for i in range(24):
                sched.enqueue(("e", i))
            peak = sched.queue_depth
            # Drain fully, then read AFTER the spike is gone.
            while sched.queue_depth:
                await loop.sleep(0.01)
            assert sched.queue_depth == 0
            assert sched.depth_high_water() >= peak
            # The window expires: the high-water decays back down.
            await loop.sleep(ResolveScheduler.HW_WINDOW_S + 0.2)
            assert sched.depth_high_water() == 0
            return "ok"

        assert loop.run(main(), timeout=30) == "ok"

    def test_resolver_metrics_export_high_water(self):
        loop = Loop(seed=6)
        c = SimCluster(loop=loop, seed=6, n_tlogs=1, n_storages=1)

        async def main():
            m = await c.resolver_eps[0].get_metrics()
            assert "queue_depth_hw" in m
            assert m["queue"]["depth_hw"] >= m["queue"]["depth"]
            return "ok"

        assert loop.run(main(), timeout=30) == "ok"


class TestBackpressureUnderCloggedNetwork:
    """Satellite: the ratekeeper's resolver_queue signal had only been
    tested against a healthy network. Here the links are clogged while a
    blind open-loop storm rides a device stall: the signal must ENGAGE
    (high-water crosses RQ_SOFT), report resolver_queue as the limiting
    reason, and the queues must fully DRAIN after the stall."""

    def test_signal_engages_and_drains_with_clogged_links(self):
        loop = Loop(seed=9)
        c = SimCluster(loop=loop, seed=9, n_tlogs=2, n_storages=2,
                       resolver_budget_s=0.04,
                       resolver_dispatch_cost_s=0.03)
        db = open_database(c)
        from foundationdb_tpu.sim.nemesis import _fault_procs

        observed = {"max_hw": 0, "reasons": set()}

        async def main():
            # Clog a handful of seeded links for the whole run — the
            # sched × network composition under test.
            procs = _fault_procs(c)
            rng = loop.rng
            for _ in range(4):
                a = procs[rng.randrange(len(procs))]
                b = procs[rng.randrange(len(procs))]
                if a != b:
                    c.net.clog(a, b, factor=20.0, duration=30.0)

            async def sampler():
                rk = c.ratekeeper
                while not observed.get("stop"):
                    observed["max_hw"] = max(observed["max_hw"],
                                             rk.worst_resolver_queue)
                    if rk.limiting_reason != "none":
                        observed["reasons"].add(rk.limiting_reason)
                    await loop.sleep(0.02)

            sam = loop.spawn(sampler(), name="sampler")

            async def one(seq):
                async def body(tr):
                    for j in range(16):
                        tr.set(b"bp/%05d/%02d" % (seq, j), b"")

                await db.run(body)

            # Open-loop blind arrivals; a 12x stall mid-stream collapses
            # dispatch capacity so the queue must absorb the backlog.
            # BULK transactions, sixteen sets each: a proxy keeps small
            # commits until its last batch is back from the resolvers
            # (CommitProxy._held, PR 39), so single sets ride a stall in
            # a few fuller batches and queue at the proxies; wide ones
            # leave at the plain cadence and pile up behind the stall.
            writers = []
            stall_at = 60
            for seq in range(240):
                writers.append(loop.spawn(one(seq), name=f"w{seq}"))
                if seq == stall_at:
                    for r in c.resolvers:
                        r.dispatch_cost_s *= 12.0
                if seq == stall_at + 120:
                    for r in c.resolvers:
                        r.dispatch_cost_s /= 12.0
                await loop.sleep(0.005 * (0.5 + rng.random()))
            for w in writers:
                await w
            # Quiesce: heal the network, let the queues drain.
            c.net.reset_faults()
            deadline = loop.now + 30
            while (any(r.sched.queue_depth for r in c.resolvers)
                   and loop.now < deadline):
                await loop.sleep(0.05)
            await loop.sleep(0.3)
            observed["stop"] = True
            await sam
            return "ok"

        assert loop.run(main(), timeout=600) == "ok"
        assert observed["max_hw"] >= Ratekeeper.RQ_SOFT, (
            f"backpressure never engaged under clog: max high-water "
            f"{observed['max_hw']} < {Ratekeeper.RQ_SOFT}")
        assert "resolver_queue" in observed["reasons"]
        assert all(r.sched.queue_depth == 0 for r in c.resolvers), (
            "resolver queues never drained after the stall")


class TestCheckerProbeMovedShard:
    """Campaign find (ConsistencyVsResharding): the checker's member
    PROBE crashed on wrong_shard_server when the team flipped between
    map resolution and the probe — the scan path tolerated moves, the
    probe path did not. Fix: re-resolve and retry, counted as a
    moved_rescan; forward progress resets the retry budget."""

    def test_probe_wrong_shard_server_reresolves_not_crashes(self):
        from foundationdb_tpu.consistency.checker import ConsistencyChecker
        from foundationdb_tpu.core.errors import WrongShardServer

        loop = Loop(seed=21)
        c = SimCluster(loop=loop, seed=21, n_storages=3, n_replicas=2,
                       n_tlogs=2)
        db = open_database(c)

        async def main():
            async def w(tr):
                for i in range(40):
                    tr.set(b"pm/%04d" % i, b"v%04d" % i)

            await db.run(w)
            checker = ConsistencyChecker(c, db)
            orig = checker._probe_members
            tripped = {"n": 0}

            async def flaky_probe(*a, **kw):
                if tripped["n"] == 0:
                    tripped["n"] += 1
                    raise WrongShardServer("team flipped mid-probe")
                return await orig(*a, **kw)

            checker._probe_members = flaky_probe
            report = await checker.run()
            assert tripped["n"] == 1  # the fault actually fired
            assert report["status"] == "consistent"
            assert report["moved_rescans"] >= 1
            return "ok"

        assert loop.run(main(), timeout=600) == "ok"

    def test_probe_move_storm_exhausts_only_without_progress(self):
        """A probe that NEVER stops moving must still fail crisply after
        MAX_SHARD_RETRIES (wedge detection survives the fix)."""
        from foundationdb_tpu.consistency.checker import (
            ConsistencyChecker,
            ConsistencyCheckError,
        )
        from foundationdb_tpu.core.errors import WrongShardServer

        loop = Loop(seed=22)
        c = SimCluster(loop=loop, seed=22, n_storages=3, n_replicas=2,
                       n_tlogs=2)
        db = open_database(c)

        async def main():
            async def w(tr):
                tr.set(b"pw/0", b"v")

            await db.run(w)
            checker = ConsistencyChecker(c, db)

            async def always_moving(*a, **kw):
                raise WrongShardServer("permanent churn")

            checker._probe_members = always_moving
            with pytest.raises(ConsistencyCheckError):
                await checker.run()
            return "ok"

        assert loop.run(main(), timeout=600) == "ok"


class TestBlindStormConservation:
    """The lane-flood traffic shape: blind unique-key SETs stay exactly
    countable (count(keys) == acked) — the exactness contract that lets
    campaign 3 gate on conservation while flooding at client rate."""

    def test_blind_write_storm_verifies_exact(self):
        from foundationdb_tpu.sim.nemesis import NemesisContext, WriteStorm

        loop = Loop(seed=33)
        c = SimCluster(loop=loop, seed=33, n_tlogs=2, n_storages=2)
        db = open_database(c)
        ctx = NemesisContext(cluster=c, db=db)
        storm = WriteStorm(prefix="bl/", txns=24, clients=4, blind=True,
                           open_loop=True, arrival_s=0.004)

        async def main():
            await storm.fire(ctx)
            await storm.verify(ctx, db)  # raises on any lost write
            return ctx.counters.get("acked", 0)

        assert loop.run(main(), timeout=120) == 24
