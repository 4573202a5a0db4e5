"""End-to-end commit pipeline: GRV → commit → resolve → tlog → storage reads.

Mirrors the reference's simulation smoke workloads (Cycle/SerializabilityTest
style): real role actors over the sim network, verdict semantics and
read-at-version checked at the client boundary.
"""

import pytest

from foundationdb_tpu.core.errors import FutureVersion, NotCommitted
from foundationdb_tpu.core.mutations import Mutation, MutationType as M
from foundationdb_tpu.core.types import KeyRange, single_key_range
from foundationdb_tpu.runtime.commit_proxy import CommitRequest
from foundationdb_tpu.runtime.flow import all_of
from foundationdb_tpu.sim.cluster import SimCluster


def set_req(rv, key, value, reads=()):
    return CommitRequest(
        read_version=rv,
        mutations=[Mutation(M.SET_VALUE, key, value)],
        read_ranges=[single_key_range(k) for k in reads],
        write_ranges=[single_key_range(key)],
    )


class TestCommitPipeline:
    def test_commit_then_read(self):
        c = SimCluster(seed=1)
        proxy, grv = c.commit_proxy_eps[0], c.grv_proxy_eps[0]

        async def main():
            rv = await grv.get_read_version()
            res = await proxy.commit(set_req(rv, b"apple", b"1"))
            assert res.version > rv
            rv2 = await grv.get_read_version()
            assert rv2 >= res.version  # GRV sees the committed batch
            got = await c.storage_ep_for_key(b"apple").get(b"apple", rv2)
            assert got == b"1"
            # A read at the OLD snapshot must not see the write.
            old = await c.storage_ep_for_key(b"apple").get(b"apple", rv)
            assert old is None
            return "ok"

        assert c.loop.run(main(), timeout=60) == "ok"

    def test_write_write_no_conflict_read_write_conflicts(self):
        c = SimCluster(seed=2)
        proxy, grv = c.commit_proxy_eps[0], c.grv_proxy_eps[0]

        async def main():
            rv = await grv.get_read_version()
            await proxy.commit(set_req(rv, b"k", b"a"))
            # Blind write at the stale snapshot: no read ranges → commits.
            await proxy.commit(set_req(rv, b"k", b"b"))
            # Read-modify-write at the stale snapshot: conflicts.
            with pytest.raises(NotCommitted):
                await proxy.commit(set_req(rv, b"k", b"c", reads=[b"k"]))
            return "ok"

        assert c.loop.run(main(), timeout=60) == "ok"

    def test_batch_order_intra_batch_conflict(self):
        c = SimCluster(seed=3)
        proxy, grv = c.commit_proxy_eps[0], c.grv_proxy_eps[0]

        async def main():
            rv = await grv.get_read_version()
            # Same batch (enqueued back-to-back on the proxy object, so the
            # batcher drains both together): txn0 writes k, txn1 reads k at
            # the same snapshot → txn1 must lose to the earlier-accepted txn0.
            cp = c.commit_proxies[0]
            t0 = c.loop.spawn(cp.commit(set_req(rv, b"k", b"x")))
            t1 = c.loop.spawn(cp.commit(set_req(rv, b"other", b"y", reads=[b"k"])))
            r0 = await t0
            with pytest.raises(NotCommitted):
                await t1
            assert r0.version > rv
            return "ok"

        assert c.loop.run(main(), timeout=60) == "ok"

    def test_atomic_add_applied_at_storage(self):
        c = SimCluster(seed=4)
        proxy, grv = c.commit_proxy_eps[0], c.grv_proxy_eps[0]

        async def add(key, n):
            rv = await grv.get_read_version()
            return await proxy.commit(
                CommitRequest(
                    read_version=rv,
                    mutations=[Mutation(M.ADD, key, n.to_bytes(8, "little"))],
                    write_ranges=[single_key_range(key)],
                )
            )

        async def main():
            await all_of([c.loop.spawn(add(b"ctr", i)) for i in (1, 2, 3, 4)])
            rv = await grv.get_read_version()
            got = await c.storage_ep_for_key(b"ctr").get(b"ctr", rv)
            assert int.from_bytes(got, "little") == 10
            return "ok"

        assert c.loop.run(main(), timeout=60) == "ok"

    def test_clear_range_spanning_storage_shards(self):
        c = SimCluster(seed=5, n_storages=4)
        proxy, grv = c.commit_proxy_eps[0], c.grv_proxy_eps[0]

        async def main():
            rv = await grv.get_read_version()
            keys = [b"\x10a", b"\x50b", b"\x90c", b"\xd0d"]  # one per shard
            for k in keys:
                await proxy.commit(set_req(rv, k, b"v"))
            rv2 = await grv.get_read_version()
            for k in keys:
                assert await c.storage_ep_for_key(k).get(k, rv2) == b"v"
            res = await proxy.commit(
                CommitRequest(
                    read_version=rv2,
                    mutations=[Mutation(M.CLEAR_RANGE, b"\x20", b"\xff")],
                    write_ranges=[KeyRange(b"\x20", b"\xff")],
                )
            )
            rv3 = await grv.get_read_version()
            assert rv3 >= res.version
            assert await c.storage_ep_for_key(keys[0]).get(keys[0], rv3) == b"v"
            for k in keys[1:]:
                assert await c.storage_ep_for_key(k).get(k, rv3) is None
            return "ok"

        assert c.loop.run(main(), timeout=60) == "ok"

    def test_multi_resolver_parity(self):
        """4-resolver keyspace split must produce the same verdicts as 1."""

        def run(n_resolvers):
            c = SimCluster(seed=7, n_resolvers=n_resolvers)
            # Enqueue on the proxy object directly with one shared GRV per
            # wave: batch composition and order are then independent of
            # network latency draws, so the two topologies see identical
            # batches and must emit identical verdicts.
            proxy, grv = c.commit_proxies[0], c.grv_proxy_eps[0]
            outcomes = []

            def mk_req(i, rv):
                # Ranges stay within one 64-wide resolver shard: single-shard
                # txns have exact verdict parity across topologies (cross-shard
                # txns can over-abort with multiple resolvers, as in the
                # reference — see CommitProxy._resolve).
                lo = bytes([16 * (i % 8)])
                hi = bytes([16 * (i % 8), 8])
                return CommitRequest(
                    read_version=rv if i % 3 else max(0, rv - 10_000_000),
                    mutations=[Mutation(M.SET_VALUE, lo + b"k", b"v")],
                    read_ranges=[KeyRange(lo, hi)] if i % 2 else [],
                    write_ranges=[single_key_range(lo + b"k")],
                )

            async def one(i, rv):
                try:
                    await proxy.commit(mk_req(i, rv))
                    outcomes.append((i, "ok"))
                except Exception as e:
                    outcomes.append((i, type(e).__name__))

            async def main():
                # Two waves so wave 2's stale readers race wave 1's writes.
                for lo_i, hi_i in ((0, 8), (8, 16)):
                    rv = await grv.get_read_version()
                    await all_of(
                        [c.loop.spawn(one(i, rv)) for i in range(lo_i, hi_i)]
                    )

            c.loop.run(main(), timeout=120)
            return sorted(outcomes)

        assert run(1) == run(4)

    def test_versionstamped_key(self):
        import struct

        c = SimCluster(seed=8)
        proxy, grv = c.commit_proxy_eps[0], c.grv_proxy_eps[0]

        async def main():
            rv = await grv.get_read_version()
            key_tmpl = b"log/" + b"\x00" * 10 + struct.pack("<I", 4)
            res = await proxy.commit(
                CommitRequest(
                    read_version=rv,
                    mutations=[Mutation(M.SET_VERSIONSTAMPED_KEY, key_tmpl, b"entry")],
                    write_ranges=[KeyRange(b"log/", b"log0")],
                )
            )
            rv2 = await grv.get_read_version()
            from foundationdb_tpu.core.mutations import make_versionstamp

            expect_key = b"log/" + make_versionstamp(res.version, res.batch_order)
            got = await c.storage_ep_for_key(b"log/").get_range(b"log/", b"log0", rv2)
            assert got == [(expect_key, b"entry")]
            return "ok"

        assert c.loop.run(main(), timeout=60) == "ok"

    def test_storage_lag_future_version(self):
        c = SimCluster(seed=9)

        async def main():
            # A read version far beyond anything committed times out waiting.
            with pytest.raises(FutureVersion):
                await c.storage_eps[0].get(b"x", 10**12)
            return "ok"

        assert c.loop.run(main(), timeout=60) == "ok"

    def test_tlog_keeps_entries_for_lagging_tag(self):
        """Trimming must respect tags that have never popped (slow/new
        storage), not just the min over tags that did."""
        from foundationdb_tpu.runtime.flow import Loop
        from foundationdb_tpu.runtime.tlog import TLog

        loop = Loop()
        tlog = TLog(loop)

        async def main():
            await tlog.push(0, 10, {0: [Mutation(M.SET_VALUE, b"a", b"1")],
                                    1: [Mutation(M.SET_VALUE, b"b", b"2")]})
            await tlog.push(10, 20, {0: [Mutation(M.SET_VALUE, b"c", b"3")]})
            await tlog.pop(0, 20)  # tag 1 never popped
            entries, _end, _kc = await tlog.peek(1, 1)
            assert [v for v, _m in entries] == [10], entries
            # Duplicate push (retransmit) of an already-durable batch re-acks.
            assert await tlog.push(10, 20, {}) == 20
            return "ok"

        assert loop.run(main(), timeout=10) == "ok"

    def test_partition_heal_chain_liveness(self):
        """A proxy↔resolver partition during a batch must not wedge the
        version chain once healed: proxies retransmit, resolvers replay."""
        c = SimCluster(seed=11)
        proxy, grv = c.commit_proxy_eps[0], c.grv_proxy_eps[0]

        async def main():
            rv = await grv.get_read_version()
            await proxy.commit(set_req(rv, b"a", b"1"))
            c.net.partition("commit_proxy0", "resolver0")

            async def heal_later():
                await c.loop.sleep(2.0)
                c.net.heal("commit_proxy0", "resolver0")

            c.loop.spawn(heal_later())
            rv2 = await grv.get_read_version()
            res = await proxy.commit(set_req(rv2, b"b", b"2"))  # rides retry
            # Chain is live after heal: later commits flow normally.
            rv3 = await grv.get_read_version()
            assert rv3 >= res.version
            await proxy.commit(set_req(rv3, b"c", b"3"))
            rv4 = await grv.get_read_version()
            for k, v in ((b"a", b"1"), (b"b", b"2"), (b"c", b"3")):
                assert await c.storage_ep_for_key(k).get(k, rv4) == v
            return "ok"

        assert c.loop.run(main(), timeout=120) == "ok"

    def test_throughput_many_txns(self):
        # timekeeper off: the assertion counts EXACT committed txns.
        c = SimCluster(seed=10, n_resolvers=2, n_storages=2,
                       timekeeper=False)
        proxy, grv = c.commit_proxy_eps[0], c.grv_proxy_eps[0]
        N = 300

        async def writer(i):
            rv = await grv.get_read_version()
            k = b"u%03d" % i
            await proxy.commit(set_req(rv, k, b"v%d" % i))

        async def main():
            await all_of([c.loop.spawn(writer(i)) for i in range(N)])
            rv = await grv.get_read_version()
            rows = []
            for r, ep in c.storage_eps_for_range(b"u", b"v"):
                rows += await ep.get_range(r.begin, r.end, rv)
            assert len(rows) == N
            return c.commit_proxies[0].txns_committed

        committed = c.loop.run(main(), timeout=300)
        assert committed == N


# -- the known-committed bound told at the acknowledgement --------------------
# (TLog.advance_known_committed, CommitProxy._notify_committed: a tlog learns
# that version V is durable on every tlog when V's last acknowledgement
# lands, not from the NEXT batch's push one pipeline turn later.)


def _pushed_tlog(epoch=0, versions=(10, 20, 30), known_committed=0):
    """A tlog holding `versions`, pushed as a proxy does: each push carries
    a bound, here always `known_committed`, so nothing is known committed
    but what the test then says."""
    from foundationdb_tpu.runtime.flow import Loop
    from foundationdb_tpu.runtime.tlog import TLog

    loop = Loop(seed=0)
    tlog = TLog(loop, epoch=epoch)

    async def fill():
        prev = 0
        for v in versions:
            await tlog.push(prev, v, {0: [Mutation(M.SET_VALUE, b"k", b"v")]},
                            known_committed, epoch=epoch or None)
            prev = v

    loop.run(fill(), timeout=10)
    return loop, tlog


class TestKnownCommittedBound:
    @pytest.mark.parametrize("told, bound", [
        ([10], 10),             # the plain case
        ([20, 10], 20),         # monotone: an older word moves nothing back
        ([20, 20], 20),         # told twice: once
        ([99], 30),             # never above what the tlog holds
        ([0], 0),               # nothing to say
    ])
    def test_bound_is_monotone_and_under_what_the_tlog_holds(self, told,
                                                             bound):
        loop, tlog = _pushed_tlog()
        assert tlog.known_committed == 0

        async def main():
            return [await tlog.advance_known_committed(v) for v in told]

        answers = loop.run(main(), timeout=10)
        assert tlog.known_committed == bound == answers[-1]
        assert answers == sorted(answers)
        # a peek hands the bound to the storages
        _e, _end, kc = loop.run(tlog.peek(0, 1), timeout=10)
        assert kc == bound
        m = loop.run(tlog.metrics(), timeout=10)
        assert m["kc_advances_by_push"] == 0
        assert m["kc_advances_by_notify"] == (1 if bound else 0)

    @pytest.mark.parametrize("fence", ["locked", "older_epoch",
                                       "newer_epoch"])
    def test_bound_is_fenced_like_a_push(self, fence):
        from foundationdb_tpu.runtime.tlog import TLogLocked

        loop, tlog = _pushed_tlog(epoch=5)
        epoch = {"locked": 5, "older_epoch": 4, "newer_epoch": 6}[fence]

        async def main():
            assert await tlog.advance_known_committed(10, 5) == 10
            if fence == "locked":
                await tlog.lock()
            with pytest.raises(TLogLocked):
                await tlog.advance_known_committed(20, epoch)
            # the same word is refused of a push, by the same rule
            with pytest.raises(TLogLocked):
                await tlog.push(30, 40, {}, 20, epoch=epoch)

        loop.run(main(), timeout=10)
        assert tlog.known_committed == 10

    def test_counters_say_which_path_moved_the_bound_first(self):
        loop, tlog = _pushed_tlog()

        async def main():
            await tlog.advance_known_committed(10)         # notify first
            await tlog.push(30, 40, {}, 10)                # push: old news
            await tlog.push(40, 50, {}, 30)                # push first
            await tlog.advance_known_committed(30)         # notify: old news
            return await tlog.metrics()

        m = loop.run(main(), timeout=10)
        assert (m["kc_advances_by_notify"], m["kc_advances_by_push"]) == (1, 1)


def _commit_then_read(c, key, value, drop_notify=False):
    """One commit on an otherwise idle cluster, then a read at a read
    version taken after the acknowledgement. → (value read, the read's
    seconds, what moved the tlog's bound up to the commit)."""
    proxy, grv = c.commit_proxy_eps[0], c.grv_proxy_eps[0]
    if drop_notify:
        # the buggify site, always firing: dropped or late
        c.loop.buggify = lambda site, *_a, **_k: \
            site == "commit_proxy.lose_commit_notify"

    async def main():
        rv = await grv.get_read_version()
        res = await proxy.commit(set_req(rv, key, value))
        rv2 = await grv.get_read_version()
        assert rv2 >= res.version
        t0 = c.loop.now
        got = await c.storage_ep_for_key(key).get(key, rv2)
        return got, c.loop.now - t0, res.version

    got, waited, version = c.loop.run(main(), timeout=60)
    assert c.tlogs[0].known_committed >= version
    return got, waited


class TestCommitNotify:
    def test_idle_cluster_serves_the_read_after_one_pull(self):
        """No later push: before, the storage waited for the proxies' next
        EMPTY batch to carry the bound, IDLE_BATCH_INTERVAL away."""
        from foundationdb_tpu.runtime.commit_proxy import CommitProxy

        c = SimCluster(seed=21, timekeeper=False)
        pushes = []
        push = c.tlogs[0].push

        async def counted(prev, version, *a, **k):
            pushes.append(version)
            return await push(prev, version, *a, **k)

        c.tlogs[0].push = counted
        got, waited = _commit_then_read(c, b"apple", b"1")
        assert got == b"1"
        assert waited < CommitProxy.IDLE_BATCH_INTERVAL / 10, waited
        assert len(pushes) == 1  # the commit's own; no batch came after
        assert c.tlogs[0].kc_advances_by_notify == 1
        assert c.tlogs[0].kc_advances_by_push == 0
        assert c.commit_proxies[0].commit_notifies_sent == 1

    def test_a_dropped_notification_changes_nothing_but_the_wait(self):
        from foundationdb_tpu.runtime.commit_proxy import CommitProxy

        waits = {}
        for drop in (False, True):
            c = SimCluster(seed=22, timekeeper=False)
            values = []
            for i in range(6):
                got, waited = _commit_then_read(
                    c, b"k%d" % i, b"v%d" % i, drop_notify=drop)
                values.append(got)
                waits[drop] = max(waits.get(drop, 0.0), waited)
            assert values == [b"v%d" % i for i in range(6)]
        # told: at once; dropped or late: the next push's bound, an idle
        # batch away at the most (and one more for the one after)
        assert waits[False] < CommitProxy.IDLE_BATCH_INTERVAL / 10
        assert waits[False] < waits[True] \
            <= 2 * CommitProxy.IDLE_BATCH_INTERVAL + 0.1

    def test_bound_is_sent_only_after_every_tlog_acknowledged(self):
        """Two tlogs, one slow to acknowledge: at the moment either hears
        a bound, BOTH hold it; while the slow one's acknowledgement is
        out, nobody has been told the version."""
        c = SimCluster(seed=23, n_tlogs=2, timekeeper=False)
        told = []
        for t in c.tlogs:
            def hear(version, epoch=None, t=t, inner=t.advance_known_committed):
                told.append((version,
                             min(x._last_appended for x in c.tlogs)))
                return inner(version, epoch)
            t.advance_known_committed = hear
        slow = c.tlogs[1]
        push = slow.push

        async def late_ack(prev, version, *a, **k):
            out = await push(prev, version, *a, **k)
            assert all(v < version for v, _held in told), (version, told)
            await c.loop.sleep(0.03)  # durable here, the ack still out
            assert all(v < version for v, _held in told), (version, told)
            return out

        slow.push = late_ack
        proxy, grv = c.commit_proxy_eps[0], c.grv_proxy_eps[0]

        async def main():
            for i in range(5):
                rv = await grv.get_read_version()
                await proxy.commit(set_req(rv, b"k%d" % i, b"v"))
            await c.loop.sleep(0.1)

        c.loop.run(main(), timeout=60)
        assert len(told) >= 10  # five batches, two tlogs
        assert all(version <= held for version, held in told), told

    def test_a_proxy_that_misses_one_acknowledgement_tells_nobody(self):
        """A fenced proxy (one of its tlogs locked by a recovery it has not
        heard of) appends to the other tlog and never sends the bound, so
        no storage applies that unacknowledged suffix."""
        from foundationdb_tpu.core.errors import CommitUnknownResult

        c = SimCluster(seed=24, n_tlogs=2, timekeeper=False)
        proxy, grv = c.commit_proxy_eps[0], c.grv_proxy_eps[0]
        c.commit_proxies[0].controller = None  # no recovery: stay fenced

        async def main():
            rv = await grv.get_read_version()
            res = await proxy.commit(set_req(rv, b"a", b"1"))
            await c.loop.sleep(0.05)
            sent = c.commit_proxies[0].commit_notifies_sent
            c.tlogs[1].locked = True
            with pytest.raises(CommitUnknownResult):
                await proxy.commit(set_req(rv, b"b", b"2"))
            await c.loop.sleep(0.5)
            return res.version, sent

        acked, sent = c.loop.run(main(), timeout=120)
        open_log = c.tlogs[0]
        assert open_log._last_appended > acked       # the fork is there
        assert open_log.known_committed <= acked     # and not committed
        assert c.commit_proxies[0].commit_notifies_sent == sent
        assert all(s.known_committed <= acked for s in c.storages)
        assert all(s.map.latest(b"b") is None for s in c.storages)


class TestSelfClockedBatch:
    """A resolver takes one batch at a time and a batch costs it much the
    same whatever it holds (sim: `resolver_dispatch_cost_s` a batch). A
    proxy that forms a batch every BATCH_INTERVAL whatever is outstanding
    queues near-empty batches behind each other; one that waits for its
    last batch to come back from the resolvers sends full ones."""

    COST_S = 0.015

    def _closed_loop(self, n_clients, seconds=3.0, n_proxies=2):
        c = SimCluster(seed=31, n_proxies=n_proxies, timekeeper=False,
                       resolver_dispatch_cost_s=self.COST_S)
        latencies = []

        async def client(i):
            proxy = c.commit_proxy_eps[i % n_proxies]
            grv = c.grv_proxy_eps[i % n_proxies]
            await c.loop.sleep(c.loop.rng.uniform(0, 0.05))  # spread out
            n = 0
            while True:
                rv = await grv.get_read_version()
                t0 = c.loop.now
                await proxy.commit(set_req(rv, b"c%03d" % i, b"%d" % n))
                latencies.append(c.loop.now - t0)
                n += 1

        async def main():
            for i in range(n_clients):
                c.loop.spawn(client(i), name=f"client{i}")
            await c.loop.sleep(seconds)

        c.loop.run(main(), timeout=300)
        r = c.resolvers[0]
        latencies.sort()
        return (r.txns_resolved / r.batches_resolved,
                len(latencies) / seconds, latencies[len(latencies) // 2])

    def test_commits_that_arrive_while_a_batch_is_out_ride_one_batch(self):
        fill, per_s, p50 = self._closed_loop(n_clients=32)
        # two proxies, one batch each at the resolver: a proxy's cycle is
        # two brackets, and the 16 clients it serves ride every batch
        assert fill >= 6, (fill, per_s, p50)
        assert p50 <= 4 * self.COST_S, (fill, per_s, p50)
        # the resolver's bracket a batch is the rate's only limit: it
        # stays busy (one batch in work, the other proxy's waiting)
        assert per_s >= 0.8 * fill / self.COST_S, (fill, per_s, p50)

    def test_one_client_is_not_held_back(self):
        """Nothing out at the resolvers: the batch leaves at the next
        BATCH_INTERVAL, as before."""
        from foundationdb_tpu.runtime.commit_proxy import CommitProxy

        fill, per_s, p50 = self._closed_loop(n_clients=1, n_proxies=1)
        assert fill <= 1.0
        assert p50 <= self.COST_S + CommitProxy.BATCH_INTERVAL + 0.015, p50

    def _warm(self, cost_s):
        """A cluster whose proxy has seen one batch take `cost_s` and more
        to resolve, and has another out at the resolver now."""
        c = SimCluster(seed=32, timekeeper=False,
                       resolver_dispatch_cost_s=cost_s)
        proxy, grv = c.commit_proxy_eps[0], c.grv_proxy_eps[0]

        async def warm():
            rv = await grv.get_read_version()
            await proxy.commit(set_req(rv, b"warm", b"1"))
            c.loop.spawn(proxy.commit(set_req(rv, b"out", b"1")), name="out")
            await c.loop.sleep(0.01)
            return rv

        rv = c.loop.run(warm(), timeout=60)
        cp = c.commit_proxies[0]
        assert cp._resolve_s >= cost_s and len(cp._inflight) == 1
        return c, cp, proxy, rv

    @pytest.mark.parametrize("n_commits, n_mutations, leaves", [
        (1, 1, False),                  # a small commit rides the next batch
        (5, 1, False),
        (3, 0, False),                  # conflict ranges alone: as one set
        (8, 2, False),                  # two sets each: half the interval
        (1, 100, True),                 # a bulk transaction is not held
        (8, 20, True),                  # nor a queue of wide ones
        (512, 1, True),                 # nor a full batch by count
    ])
    def test_what_is_held_while_a_batch_is_out(self, n_commits, n_mutations,
                                               leaves):
        """The wait is for company: a queue averaging w mutations a commit
        is kept 1/w of what the last batch took to resolve."""
        c, cp, proxy, rv = self._warm(cost_s=0.2)

        def req(i):
            return CommitRequest(
                read_version=rv, read_ranges=[],
                mutations=[Mutation(M.SET_VALUE, b"q%04d.%03d" % (i, j), b"v")
                           for j in range(n_mutations)],
                write_ranges=[single_key_range(b"q%04d.%03d" % (i, j))
                              for j in range(n_mutations)])

        async def main():
            asked = [c.loop.spawn(proxy.commit(req(i)), name=f"w{i}")
                     for i in range(n_commits)]
            await c.loop.sleep(0.02)  # a tenth of what the last batch took
            state = len(cp._inflight), len(cp._queue), cp._queued_mutations
            for a in asked:
                await a  # held or not, every commit is answered
            return state

        out, queued, queued_mutations = c.loop.run(main(), timeout=120)
        if leaves:
            # (a burst the network spreads over two ticks is two batches)
            assert out >= 2 and (queued, queued_mutations) == (0, 0)
        else:
            assert (out, queued, queued_mutations) == (
                1, n_commits, n_commits * n_mutations)
        assert cp._queued_mutations == 0

    def test_a_system_commit_leaves_on_the_next_tick(self):
        """The lanes' promise stands while a batch is out: a system
        transaction is never queued behind more than the window already
        forming, and it takes the default lane's queue along."""
        c, cp, proxy, rv = self._warm(cost_s=0.2)

        async def main():
            small = c.loop.spawn(proxy.commit(set_req(rv, b"small", b"v")),
                                 name="small")
            await c.loop.sleep(0.01)
            held = len(cp._queue)
            sys_req = set_req(rv, b"sys", b"v")
            sys_req.priority = "system"
            t0 = c.loop.now
            system = c.loop.spawn(proxy.commit(sys_req), name="system")
            while not cp._queue.depths()["system"]:
                await c.loop.sleep(0.0005)  # on its way to the proxy
            while len(cp._queue):
                await c.loop.sleep(0.0005)
            waited = c.loop.now - t0
            await small
            await system
            return held, waited

        held, waited = c.loop.run(main(), timeout=120)
        assert held == 1                   # the small commit was waiting
        # the wire and one tick, not what the last batch took (0.2 s)
        assert waited <= 0.005 + 2 * cp.BATCH_INTERVAL, waited

    def test_a_due_shaped_lane_is_not_held(self):
        """SHAPE_WINDOW_S bounds what shaping adds to a commit: a lane
        whose head has parked its window flushes whatever is out."""
        c, cp, proxy, rv = self._warm(cost_s=0.2)

        async def main():
            queued = c.loop.spawn(proxy.commit(set_req(rv, b"q", b"v")),
                                  name="queued")
            while not len(cp._queue):
                await c.loop.sleep(0.0005)
            assert cp._held(0.0)
            cp._shaped_since = c.loop.now - cp.SHAPE_WINDOW_S
            cp._shaped = [object()]
            due = cp._held(0.0)
            cp._shaped = []
            await queued
            return due

        assert c.loop.run(main(), timeout=120) is False

    def test_a_stalled_resolver_does_not_freeze_the_proxy(self):
        """The interval is what the LAST batch took, not the wait for the
        one that is out: when the resolver stalls, batches keep leaving
        at the old interval and queue at the RESOLVER, whose depth is
        what the ratekeeper's resolver_queue signal reads."""
        c, cp, proxy, rv = self._warm(cost_s=0.02)
        deepest = [0]

        async def main():
            for r in c.resolvers:
                r.dispatch_cost_s *= 50.0  # a second a batch
            for i in range(40):
                c.loop.spawn(proxy.commit(set_req(rv, b"s%02d" % i, b"v")),
                             name=f"w{i}")
                await c.loop.sleep(0.01)
                deepest[0] = max(deepest[0], len(cp._inflight))

        c.loop.run(main(), timeout=120)
        assert deepest[0] >= 5, deepest

    def test_a_stall_is_not_waited_out_twice(self):
        """A batch that took a second to resolve (a resolver that stalled
        and came back) does not set the interval to a second: the next
        commit leaves the proxy's queue within the idle cadence. (What it
        then waits for is the resolver's own backlog.)"""
        from foundationdb_tpu.runtime.commit_proxy import CommitProxy

        c, cp, proxy, rv = self._warm(cost_s=0.02)

        async def main():
            for r in c.resolvers:
                r.dispatch_cost_s *= 50.0  # a second a batch
            await proxy.commit(set_req(rv, b"slow", b"v"))
            for r in c.resolvers:
                r.dispatch_cost_s /= 50.0
            assert cp._resolve_s == CommitProxy.IDLE_BATCH_INTERVAL
            t0 = c.loop.now
            after = c.loop.spawn(proxy.commit(set_req(rv, b"after", b"v")),
                                 name="after")
            while not len(cp._queue):
                await c.loop.sleep(0.001)  # on its way to the proxy
            while len(cp._queue):
                await c.loop.sleep(0.001)
            held = c.loop.now - t0
            await after
            return held

        held = c.loop.run(main(), timeout=120)
        assert held <= CommitProxy.IDLE_BATCH_INTERVAL + 0.01, held
