"""Change feeds, the watch limit, and multi-signal ratekeeper admission.

Reference behaviors under test: storageserver.actor.cpp change feeds
(capture, clip, atomic normalization, pop/destroy semantics), the
too_many_watches limit (error 1032), Ratekeeper.actor.cpp's multi-signal
rate computation with the default/batch priority split, and the GRV proxy
lane behavior under a throttled batch budget.
"""

import pytest

from foundationdb_tpu.core.errors import (
    ChangeFeedCancelled,
    ChangeFeedPopped,
    TooManyWatches,
)
from foundationdb_tpu.core.mutations import Mutation, MutationType as M
from foundationdb_tpu.runtime.flow import Loop
from foundationdb_tpu.runtime.grv_proxy import PRIORITY_BATCH, GrvProxy
from foundationdb_tpu.runtime.ratekeeper import Ratekeeper
from foundationdb_tpu.runtime.storage import StorageServer


def make_ss():
    loop = Loop(seed=0)
    return loop, StorageServer(loop, tag=0, tlog_ep=None)


class TestChangeFeeds:
    def test_capture_clip_and_read(self):
        _loop, ss = make_ss()
        ss.register_change_feed(b"f", b"b", b"d")
        ss._apply(1, [Mutation(M.SET_VALUE, b"a", b"0")])  # outside
        ss._apply(2, [Mutation(M.SET_VALUE, b"b", b"1")])  # inside
        ss._apply(3, [Mutation(M.CLEAR_RANGE, b"a", b"z")])  # clipped
        got = ss.read_change_feed(b"f", 0)
        assert got == [
            (2, Mutation(M.SET_VALUE, b"b", b"1")),
            (3, Mutation(M.CLEAR_RANGE, b"b", b"d")),
        ]
        # Version-window reads.
        assert ss.read_change_feed(b"f", 3) == [
            (3, Mutation(M.CLEAR_RANGE, b"b", b"d"))
        ]
        assert ss.read_change_feed(b"f", 0, end_version=3) == [
            (2, Mutation(M.SET_VALUE, b"b", b"1"))
        ]

    def test_atomic_ops_normalize_to_set(self):
        _loop, ss = make_ss()
        ss.register_change_feed(b"f", b"", b"\xff")
        ss._apply(1, [Mutation(M.SET_VALUE, b"k", (5).to_bytes(8, "little"))])
        ss._apply(2, [Mutation(M.ADD, b"k", (3).to_bytes(8, "little"))])
        got = ss.read_change_feed(b"f", 2)
        assert got == [
            (2, Mutation(M.SET_VALUE, b"k", (8).to_bytes(8, "little")))
        ]

    def test_pop_and_popped_error(self):
        _loop, ss = make_ss()
        ss.register_change_feed(b"f", b"", b"\xff")
        ss._apply(1, [Mutation(M.SET_VALUE, b"k", b"1")])
        ss._apply(2, [Mutation(M.SET_VALUE, b"k", b"2")])
        ss.pop_change_feed(b"f", 2)
        assert ss.read_change_feed(b"f", 2) == [
            (2, Mutation(M.SET_VALUE, b"k", b"2"))
        ]
        with pytest.raises(ChangeFeedPopped):
            ss.read_change_feed(b"f", 1)

    def test_stop_and_destroy(self):
        loop, ss = make_ss()
        ss.register_change_feed(b"f", b"", b"\xff")
        ss._apply(1, [Mutation(M.SET_VALUE, b"k", b"1")])
        ss.stop_change_feed(b"f")
        ss._apply(2, [Mutation(M.SET_VALUE, b"k", b"2")])
        assert len(ss.read_change_feed(b"f", 0)) == 1  # capture stopped
        ss.destroy_change_feed(b"f")
        with pytest.raises(ChangeFeedCancelled):
            ss.read_change_feed(b"f", 0)

    def test_wait_wakes_on_capture(self):
        loop, ss = make_ss()
        ss.register_change_feed(b"f", b"", b"\xff")

        async def main():
            async def writer():
                await loop.sleep(0.01)
                ss._apply(5, [Mutation(M.SET_VALUE, b"k", b"v")])

            loop.spawn(writer(), name="writer")
            v = await ss.wait_change_feed(b"f", 0)
            assert v == 5
            return "ok"

        assert loop.run(main(), timeout=10) == "ok"

    def test_stop_wakes_waiter(self):
        loop, ss = make_ss()
        ss.register_change_feed(b"f", b"", b"\xff")

        async def main():
            async def stopper():
                await loop.sleep(0.01)
                ss.stop_change_feed(b"f")

            loop.spawn(stopper(), name="stopper")
            with pytest.raises(ChangeFeedCancelled):
                await ss.wait_change_feed(b"f", 0)
            return "ok"

        assert loop.run(main(), timeout=10) == "ok"

    def test_out_of_order_capture_sorts(self):
        """fetch_keys replay captures at older versions than live traffic
        already captured — reads must still come back version-ordered."""
        _loop, ss = make_ss()
        ss.register_change_feed(b"f", b"", b"\xff")
        ss._feed_capture(5, Mutation(M.SET_VALUE, b"k", b"new"))
        ss._feed_capture(3, Mutation(M.SET_VALUE, b"k", b"replayed"))
        got = ss.read_change_feed(b"f", 0, end_version=100)
        assert [v for v, _m in got] == [3, 5]

    def test_destroy_wakes_waiter(self):
        loop, ss = make_ss()
        ss.register_change_feed(b"f", b"", b"\xff")

        async def main():
            async def killer():
                await loop.sleep(0.01)
                ss.destroy_change_feed(b"f")

            loop.spawn(killer(), name="killer")
            with pytest.raises(ChangeFeedCancelled):
                await ss.wait_change_feed(b"f", 0)
            return "ok"

        assert loop.run(main(), timeout=10) == "ok"


class TestWatchLimit:
    def test_too_many_watches(self, monkeypatch):
        loop, ss = make_ss()
        monkeypatch.setattr(StorageServer, "MAX_WATCHES", 3)

        async def main():
            for i in range(3):
                loop.spawn(ss.watch(b"k%d" % i, None), name=f"w{i}")
            await loop.sleep(0.001)  # let the watches arm
            with pytest.raises(TooManyWatches):
                await ss.watch(b"k9", None)
            # Firing one frees a slot.
            ss._apply(1, [Mutation(M.SET_VALUE, b"k0", b"v")])
            loop.spawn(ss.watch(b"k9", None), name="w9")
            await loop.sleep(0.001)
            return "ok"

        assert loop.run(main(), timeout=10) == "ok"


class FakeStorage:
    """Endpoint-shaped fake: metrics() returns a Future (all_of's contract)."""

    def __init__(self):
        self.loop = None  # attached by run_rk
        self.m = {
            "tag": 0, "durable_version": 0, "version_lag": 0,
            "durability_lag": 0, "queue_bytes": 0, "keys": 0,
        }

    def metrics(self):
        async def get():
            return dict(self.m)

        return self.loop.spawn(get(), name="fake_storage.metrics")


class FakeTlog:
    def __init__(self):
        self.loop = None
        self.queue_bytes = 0

    def metrics(self):
        async def get():
            return {"version": 0, "queue_bytes": self.queue_bytes,
                    "queue_entries": 0}

        return self.loop.spawn(get(), name="fake_tlog.metrics")


class TestRatekeeperSignals:
    def run_rk(self, storage, tlog):
        loop = Loop(seed=0)
        storage.loop = tlog.loop = loop
        rk = Ratekeeper(loop, [storage], [tlog])

        async def main():
            loop.spawn(rk.run(), name="rk")
            await loop.sleep(0.5)
            return await rk.get_rates()

        return loop.run(main(), timeout=10), rk

    def test_healthy_full_rate(self):
        rates, rk = self.run_rk(FakeStorage(), FakeTlog())
        assert rates["tps_limit"] == Ratekeeper.BASE_TPS
        assert rates["batch_tps_limit"] == Ratekeeper.BASE_TPS
        assert rates["limiting_reason"] == "none"

    def test_storage_queue_throttles_batch_first(self):
        s = FakeStorage()
        s.m["queue_bytes"] = int(Ratekeeper.SQ_SOFT * 0.75)  # over batch soft
        rates, _ = self.run_rk(s, FakeTlog())
        assert rates["tps_limit"] == Ratekeeper.BASE_TPS  # default untouched
        assert rates["batch_tps_limit"] < Ratekeeper.BASE_TPS

    def test_tlog_queue_kills_rate(self):
        t = FakeTlog()
        t.queue_bytes = Ratekeeper.TQ_HARD
        rates, _ = self.run_rk(FakeStorage(), t)
        assert rates["tps_limit"] == 0.0
        assert rates["limiting_reason"] == "tlog_queue"

    def test_durability_lag_signal(self):
        s = FakeStorage()
        s.m["durability_lag"] = Ratekeeper.DLAG_HARD
        rates, _ = self.run_rk(s, FakeTlog())
        assert rates["tps_limit"] == 0.0
        assert rates["limiting_reason"] == "durability_lag"


class FakeSequencer:
    async def get_live_committed_version(self):
        return 42


class FakeRatekeeper:
    def __init__(self, tps, batch_tps):
        self.tps, self.batch_tps = tps, batch_tps

    async def get_rates(self, poller_id=None, grvs_served=None):
        return {"tps_limit": self.tps, "batch_tps_limit": self.batch_tps}


class TestGrvPriorityLanes:
    def test_batch_lane_starves_while_default_serves(self):
        loop = Loop(seed=0)
        proxy = GrvProxy(loop, FakeSequencer(), FakeRatekeeper(1e6, 0.0))
        proxy._tokens = proxy._batch_tokens = 0.0  # force bucket refill path

        async def main():
            loop.spawn(proxy.run(), name="grv")
            got = {}

            async def batch_req():
                got["batch"] = await proxy.get_read_version(PRIORITY_BATCH)

            loop.spawn(batch_req(), name="batch")
            got["default"] = await proxy.get_read_version()
            await loop.sleep(0.2)
            return got

        got = loop.run(main(), timeout=10)
        assert got["default"] == 42
        assert "batch" not in got  # zero batch budget → still queued


class TestTagThrottling:
    def test_hot_tag_capped_while_others_flow(self):
        """Per-tag quotas (reference: TagThrottle enforced at the GRV
        proxy): a quota'd hot tag is admitted at ~its tps while untagged
        traffic flows unthrottled through the same proxy."""
        loop = Loop(seed=0)

        class RkWithTags(FakeRatekeeper):
            async def get_rates(self, poller_id=None, grvs_served=None):
                r = await super().get_rates()
                r["tag_rates"] = {"hot": 10.0}
                return r

        proxy = GrvProxy(loop, FakeSequencer(), RkWithTags(1e6, 1e6))
        served = {"hot": 0, "plain": 0}

        async def client(tag, n):
            for _ in range(n):
                await proxy.get_read_version(
                    "default", [tag] if tag else None
                )
                served[tag or "plain"] += 1

        async def main():
            loop.spawn(proxy.run(), name="grv")
            await loop.sleep(0.15)  # poller fetched tag rates
            h = loop.spawn(client("hot", 200), name="hot")
            p = loop.spawn(client(None, 200), name="plain")
            await loop.sleep(2.0)
            h.cancel()
            _ = p
            return dict(served)

        got = loop.run(main(), timeout=60)
        # Untagged: all 200 long before the deadline. Hot: ~10 tps * 2s,
        # give slack for refill granularity.
        assert got["plain"] == 200, got
        assert got["hot"] <= 30, got
        assert got["hot"] >= 5, got  # but not starved entirely
        assert proxy.tag_throttled > 0

    def test_quota_cleared_restores_flow(self):
        loop = Loop(seed=0)

        class ToggleRk(FakeRatekeeper):
            tag_rates = {"hot": 5.0}

            async def get_rates(self, poller_id=None, grvs_served=None):
                r = await super().get_rates()
                r["tag_rates"] = dict(self.tag_rates)
                return r

        rk = ToggleRk(1e6, 1e6)
        proxy = GrvProxy(loop, FakeSequencer(), rk)

        async def main():
            loop.spawn(proxy.run(), name="grv")
            await loop.sleep(0.15)
            t0 = loop.now
            await proxy.get_read_version("default", ["hot"])
            throttled_wait = loop.now - t0
            assert throttled_wait > 0.05  # had to wait for the bucket
            rk.tag_rates = {}  # quota cleared (ThrottleApi off)
            await loop.sleep(0.15)  # poller refresh
            t1 = loop.now
            for _ in range(20):
                await proxy.get_read_version("default", ["hot"])
            assert loop.now - t1 < 0.5  # unlimited again
            return "ok"

        assert loop.run(main(), timeout=60) == "ok"

    def test_ratekeeper_tag_quota_api(self):
        loop = Loop(seed=0)
        rk = Ratekeeper(loop, [], [])

        async def main():
            await rk.set_tag_quota("hot", 25.0)
            rates = await rk.get_rates()
            assert rates["tag_rates"] == {"hot": 25.0}
            await rk.set_tag_quota("hot", None)
            rates = await rk.get_rates()
            assert rates["tag_rates"] == {}
            return "ok"

        assert loop.run(main(), timeout=10) == "ok"


class TestCalibration:
    def test_budget_converges_to_measured_capacity(self):
        """Saturation (VERDICT r2 item 8 done-criterion): a cluster whose
        roles service only ~500 txns/s must see the ratekeeper budget
        converge near 500 — derived from MEASURED throughput — instead of
        sitting at the 200k default ceiling."""
        loop = Loop(seed=0)
        CAPACITY = 500.0

        class World:
            """Closed loop: admission at tps_limit, service at CAPACITY;
            the excess piles into the storage queue."""

            def __init__(self):
                self.committed = 0.0
                self.queue_bytes = 0.0

            def step(self, tps_limit, dt):
                admitted = tps_limit * dt
                serviced = min(admitted, CAPACITY * dt)
                self.committed += serviced
                self.queue_bytes = max(
                    0.0, self.queue_bytes + (admitted - serviced) * 100
                )

        world = World()

        class SatStorage:
            def metrics(self):
                async def get():
                    return {"version_lag": 0, "durability_lag": 0,
                            "queue_bytes": int(world.queue_bytes)}

                return loop.spawn(get(), name="sat_storage.metrics")

        class SatProxy:
            def get_metrics(self):
                async def get():
                    # Admission above capacity piles a commit backlog at
                    # the proxy, the admission-limited indicator.
                    backlog = int(max(0.0, rk.tps_limit - CAPACITY))
                    return {"txns_committed": int(world.committed),
                            "queued": backlog}

                return loop.spawn(get(), name="sat_proxy.metrics")

        rk = Ratekeeper(loop, [SatStorage()], [], proxy_eps=[SatProxy()])

        async def driver():
            while True:
                world.step(rk.tps_limit, 0.05)
                await loop.sleep(0.05)

        async def main():
            loop.spawn(rk.run(), name="rk")
            loop.spawn(driver(), name="world")
            await loop.sleep(30.0)
            return await rk.get_rates()

        rates = loop.run(main(), timeout=600)
        # The ceiling left the 200k constant and tracks measurement.
        assert rates["base_tps"] < 5_000, rates
        assert rates["measured_tps"] == pytest.approx(CAPACITY, rel=0.5)
        # Budget sits near true capacity: admitted ~= serviced, so the
        # queue stays bounded instead of growing forever.
        assert rates["tps_limit"] == pytest.approx(CAPACITY, rel=1.0)
        assert rates["tps_limit"] > 50

    def test_healthy_cluster_probes_ceiling_upward(self):
        """A cluster running at the ceiling with clean signals gets MORE
        budget (the probe), so an undersized default cannot cap a fast
        cluster forever."""
        loop = Loop(seed=0)
        committed = {"n": 0.0}

        class FastProxy:
            def get_metrics(self):
                async def get():
                    return {"txns_committed": int(committed["n"])}

                return loop.spawn(get(), name="fast_proxy.metrics")

        class CleanStorage:
            def metrics(self):
                async def get():
                    return {"version_lag": 0, "durability_lag": 0,
                            "queue_bytes": 0}

                return loop.spawn(get(), name="clean_storage.metrics")

        rk = Ratekeeper(loop, [CleanStorage()], [], proxy_eps=[FastProxy()])
        rk.base_tps = 1_000.0  # undersized default

        async def driver():
            while True:
                committed["n"] += rk.tps_limit * 0.05  # always at the limit
                await loop.sleep(0.05)

        async def main():
            loop.spawn(rk.run(), name="rk")
            loop.spawn(driver(), name="world")
            await loop.sleep(10.0)
            return await rk.get_rates()

        rates = loop.run(main(), timeout=600)
        assert rates["base_tps"] > 2_000.0, rates  # probed well past start

    def test_background_blip_does_not_collapse_ceiling(self):
        """A soft-threshold signal WITHOUT proxy backlog (a DD move, a
        backup) must not clamp the ceiling to the (low) demand level
        (code review r3): demand is not capacity."""
        loop = Loop(seed=0)
        committed = {"n": 0.0}

        class IdleProxy:
            def get_metrics(self):
                async def get():
                    return {"txns_committed": int(committed["n"]),
                            "queued": 0}

                return loop.spawn(get(), name="idle_proxy.metrics")

        class BlippyStorage:
            def __init__(self):
                self.queue_bytes = 0

            def metrics(self):
                async def get():
                    return {"version_lag": 0, "durability_lag": 0,
                            "queue_bytes": self.queue_bytes}

                return loop.spawn(get(), name="blippy.metrics")

        s = BlippyStorage()
        rk = Ratekeeper(loop, [s], [], proxy_eps=[IdleProxy()])

        async def main():
            loop.spawn(rk.run(), name="rk")

            async def demand():
                while True:
                    committed["n"] += 1000 * 0.05  # 1k tps of demand
                    await loop.sleep(0.05)

            loop.spawn(demand(), name="demand")
            await loop.sleep(1.0)
            s.queue_bytes = int(Ratekeeper.SQ_SOFT * 2)  # the blip
            await loop.sleep(1.0)
            s.queue_bytes = 0
            await loop.sleep(0.5)
            return await rk.get_rates()

        rates = loop.run(main(), timeout=600)
        # Ceiling survives the blip near its starting point (not ~1.1k).
        assert rates["base_tps"] > 0.5 * Ratekeeper.BASE_TPS, rates

    def test_proxy_outage_does_not_freeze_signal_throttling(self):
        """An unreachable commit proxy skips calibration but must NOT stop
        the queue/lag signals from updating the limits (code review r3)."""
        loop = Loop(seed=0)

        class DeadProxy:
            def get_metrics(self):
                async def get():
                    raise RuntimeError("unreachable stand-in")

                return loop.spawn(get(), name="dead_proxy.metrics")

        s = FakeStorage()
        s.loop = loop
        rk = Ratekeeper(loop, [s], [], proxy_eps=[DeadProxy()])

        async def main():
            loop.spawn(rk.run(), name="rk")
            await loop.sleep(0.5)
            assert (await rk.get_rates())["tps_limit"] == Ratekeeper.BASE_TPS
            s.m["queue_bytes"] = Ratekeeper.SQ_HARD  # saturate the signal
            await loop.sleep(0.5)
            return await rk.get_rates()

        rates = loop.run(main(), timeout=600)
        assert rates["tps_limit"] == 0.0, rates  # throttling still reacts


# -- the GRV budget, realised as stated and raised by what it is spent on -----


class SlowSequencer:
    """Answers in `seconds` of loop time: with the proxy's BATCH_INTERVAL
    that is what a loop iteration takes."""

    def __init__(self, loop, seconds):
        self.loop, self.seconds = loop, seconds

    async def get_live_committed_version(self):
        if self.seconds:
            await self.loop.sleep(self.seconds)
        return 42


class SaturatedRatekeeper(FakeRatekeeper):
    async def get_rates(self, poller_id=None, grvs_served=None):
        r = await super().get_rates()
        r["admission_saturation"] = 1.0
        return r


def _grants_over_budget(lane, sequencer_s, ratekeeper, seconds=10.0,
                        rate=500.0, tag=None):
    """Demand well above `rate` on one lane for `seconds` of loop time.
    → read versions granted over rate x seconds."""
    loop = Loop(seed=0)
    proxy = GrvProxy(loop, SlowSequencer(loop, sequencer_s), ratekeeper)
    proxy._tokens = proxy._batch_tokens = 0.0  # no boot bucket to live on
    served = [0]

    async def client():
        while True:
            await proxy.get_read_version(lane, [tag] if tag else None)
            served[0] += 1

    async def main():
        loop.spawn(proxy.run(), name="grv")
        await loop.sleep(0.15)  # the first rate poll has landed
        for _ in range(32):
            loop.spawn(client(), name="client")
        t0, n0 = loop.now, served[0]
        await loop.sleep(seconds)
        return (served[0] - n0) / (rate * (loop.now - t0))

    return loop.run(main(), timeout=600)


class TestGrvBudgetByElapsedTime:
    @pytest.mark.parametrize("lane", ["default", PRIORITY_BATCH])
    @pytest.mark.parametrize("sequencer_s", [0.0, 0.001, 0.003])
    def test_grants_its_rate_whatever_an_iteration_takes(self, lane,
                                                         sequencer_s):
        """A refill of one BATCH_INTERVAL an ITERATION granted 1 / (1 +
        sequencer_s / BATCH_INTERVAL) of the budget: a half at 1 ms, the
        deployed cluster's two thirds at ~0.5 ms."""
        share = _grants_over_budget(lane, sequencer_s,
                                    FakeRatekeeper(500.0, 500.0))
        assert share == pytest.approx(1.0, abs=0.05), share

    def test_tag_bucket_grants_its_quota_whatever_an_iteration_takes(self):
        class RkWithTag(FakeRatekeeper):
            async def get_rates(self, poller_id=None, grvs_served=None):
                r = await super().get_rates()
                r["tag_rates"] = {"hot": 50.0}
                return r

        share = _grants_over_budget("default", 0.001, RkWithTag(1e6, 1e6),
                                    rate=50.0, tag="hot")
        assert share == pytest.approx(1.0, abs=0.05), share

    def test_a_deferred_interval_still_accrues_nothing(self):
        """Under admission saturation every other interval is deferred:
        no grant, and its tokens are NOT carried to the next."""
        share = _grants_over_budget("default", 0.0,
                                    SaturatedRatekeeper(500.0, 500.0))
        assert share == pytest.approx(0.5, abs=0.05), share

    def test_the_poll_reports_what_was_granted(self):
        loop = Loop(seed=0)
        reports = []

        class Rk(FakeRatekeeper):
            async def get_rates(self, poller_id=None, grvs_served=None):
                reports.append((poller_id, grvs_served))
                return await super().get_rates()

        proxy = GrvProxy(loop, FakeSequencer(), Rk(1e6, 1e6))

        async def main():
            loop.spawn(proxy.run(), name="grv")
            for _ in range(7):
                await proxy.get_read_version()
            for _ in range(3):
                await proxy.get_read_version("batch")
            for _ in range(5):  # spends no budget: not the ceiling's use
                await proxy.get_read_version("system")
            await loop.sleep(0.25)

        loop.run(main(), timeout=60)
        assert proxy.grvs_served == 15
        assert reports[0] == (proxy.poller_id, 0)
        assert reports[-1] == (proxy.poller_id, 10)


def _ceiling_after(commit_share, grv_share, seconds=10.0, ceiling=1_000.0):
    """A healthy cluster (clean signals, no backlog) whose clients commit
    at `commit_share` of the ceiling it starts with and are granted read
    versions at `grv_share` of it, by two GRV proxies. → get_rates()."""
    loop = Loop(seed=0)
    committed = {"n": 0.0}

    class Proxy:
        def get_metrics(self):
            async def get():
                return {"txns_committed": int(committed["n"]), "queued": 0}

            return loop.spawn(get(), name="proxy.metrics")

    class CleanStorage:
        def metrics(self):
            async def get():
                return {"version_lag": 0, "durability_lag": 0,
                        "queue_bytes": 0}

            return loop.spawn(get(), name="clean_storage.metrics")

    rk = Ratekeeper(loop, [CleanStorage()], [], proxy_eps=[Proxy()])
    rk.base_tps = ceiling  # as a bulk load leaves it

    async def grv_proxy(poller_id):
        served = 0.0
        while True:
            await rk.get_rates(poller_id, int(served))
            await loop.sleep(0.1)
            served += grv_share * ceiling / 2 * 0.1

    async def main():
        loop.spawn(rk.run(), name="rk")
        loop.spawn(grv_proxy("grv-a"), name="grv-a")
        loop.spawn(grv_proxy("grv-b"), name="grv-b")
        t = 0.0
        while t < seconds:
            committed["n"] += commit_share * ceiling * 0.05
            await loop.sleep(0.05)
            t += 0.05
        return await rk.get_rates()

    return loop.run(main(), timeout=600)


class TestCeilingFollowsReadVersions:
    def test_workload_f_gets_its_budget_back(self):
        """Commits at 0.4 of the ceiling, read versions at 0.9 of it: the
        budget is spent on read versions, so that is what must pass 0.7
        of the ceiling for a healthy cluster to be given more. Before,
        only commits counted and the ceiling stood where a bulk load had
        left it. The probe stops once the use is under 0.7 of it."""
        rates = _ceiling_after(0.4, 0.9)
        assert rates["grv_tps"] == pytest.approx(900.0, rel=0.05), rates
        assert rates["measured_tps"] == pytest.approx(400.0, rel=0.05), rates
        assert rates["ceiling_probes"] >= 5, rates
        assert 900.0 / 0.7 <= rates["base_tps"] <= 900.0 / 0.7 * 1.06, rates
        assert rates["tps_limit"] == rates["base_tps"]

    @pytest.mark.parametrize("commit_share, grv_share", [
        (0.0, 0.0),     # nobody there
        (0.1, 0.2),     # idle
        (0.3, 0.65),    # busy, and under 0.7 in both
    ])
    def test_a_cluster_that_does_not_spend_it_is_not_given_more(
            self, commit_share, grv_share):
        rates = _ceiling_after(commit_share, grv_share)
        assert rates["ceiling_probes"] == 0, rates
        assert rates["base_tps"] == 1_000.0, rates

    def test_commits_alone_still_probe(self):
        """A blind-write load asks few read versions: the commit rate
        passes 0.7 of the ceiling and probes it, as before."""
        rates = _ceiling_after(0.9, 0.1)
        assert rates["ceiling_probes"] >= 5, rates
        assert rates["base_tps"] >= 900.0 / 0.7, rates

    def test_a_poller_that_went_silent_stops_counting(self):
        """A retired GRV proxy's last rate ages out with its lease."""
        loop = Loop(seed=0)
        rk = Ratekeeper(loop, [], [])

        async def main():
            await rk.get_rates("grv-a", 0)
            await loop.sleep(0.1)
            await rk.get_rates("grv-a", 100)
            assert rk._poller_grvs["grv-a"] == (100, pytest.approx(1000.0))
            # a count under the last (a restarted proxy) only baselines
            await loop.sleep(0.1)
            await rk.get_rates("grv-a", 10)
            assert rk._poller_grvs["grv-a"] == (10, pytest.approx(1000.0))
            await loop.sleep(Ratekeeper.POLLER_TTL + 0.1)
            await rk.get_rates("grv-b", 0)
            return dict(rk._poller_grvs)

        assert loop.run(main(), timeout=60) == {"grv-b": (0, 0.0)}
