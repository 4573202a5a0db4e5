"""Recovery + cluster controller: failure detection, epoch handoff, salvage.

Mirrors the reference's simulation recovery coverage (machine kills under
workloads with a durability oracle): committed data must survive any
generation-role failure, clients must ride through via their retry loop,
and the version sequence must stay collision-free across epochs."""

import pytest

from foundationdb_tpu.client.ryw import open_database
from foundationdb_tpu.core.errors import TransactionTooOld
from foundationdb_tpu.runtime.sequencer import EPOCH_VERSION_JUMP
from foundationdb_tpu.sim.cluster import SimCluster


def make_db(seed=0, **kw):
    # Replicated defaults (VERDICT r2 item 3): recovery must hold with
    # k=2 storage teams, not just the single-replica special case.
    kw.setdefault("n_storages", 2)
    kw.setdefault("n_replicas", 2)
    c = SimCluster(seed=seed, **kw)
    return c, open_database(c)


def run(c, coro, timeout=600):
    return c.loop.run(coro, timeout=timeout)


async def wait_for_epoch(c, epoch, interval=0.25):
    while c.controller.generation.epoch < epoch:
        await c.loop.sleep(interval)


class TestRecovery:
    @pytest.mark.parametrize(
        ("victim", "seed"),
        [("master", 101), ("commit_proxy0", 102), ("resolver0", 103), ("grv_proxy0", 104)],
    )
    def test_role_kill_recovers_and_data_survives(self, victim, seed):
        # Fixed seeds (not hash(victim): PYTHONHASHSEED would make the
        # fault-injection history differ run to run).
        c, db = make_db(seed=seed)

        async def main():
            committed = []

            async def put(i):
                async def body(tr):
                    tr.set(b"k%03d" % i, b"v%03d" % i)

                await db.run(body)
                committed.append(i)

            for i in range(10):
                await put(i)
            c.net.kill(victim)
            await wait_for_epoch(c, 2)
            assert c.controller.generation.epoch == 2
            # Cluster accepts commits again; acked pre-kill data survived.
            for i in range(10, 15):
                await put(i)

            async def check(tr):
                for i in committed:
                    assert await tr.get(b"k%03d" % i) == b"v%03d" % i

            await db.run(check)
            assert len(committed) == 15
            return "ok"

        assert run(c, main()) == "ok"

    def test_tlog_kill_salvages_unpulled_entries(self):
        """Entries durable on the tlogs but not yet pulled by storage must
        survive a tlog loss: recovery salvages them from a surviving
        replica and seeds the next generation's tlogs."""
        c, db = make_db(seed=42, n_tlogs=2)

        async def main():
            # Stall storage pulls (partition BOTH storages from the pull
            # tlog), then commit: acked writes now live only on tlogs.
            c.net.partition("storage0", "tlog0")
            c.net.partition("storage1", "tlog0")

            async def body(tr):
                tr.set(b"salvage-me", b"precious")

            await db.run(body)
            # Kill the pull tlog; the survivor (tlog1) carries the chain.
            c.net.kill("tlog0")
            await wait_for_epoch(c, 2)

            # New generation: storage re-pointed to tlog0.e2 (fresh process,
            # not partitioned) seeded with the salvaged suffix.
            async def check(tr):
                assert await tr.get(b"salvage-me") == b"precious"

            await db.run(check)
            return "ok"

        assert run(c, main()) == "ok"

    def test_versions_jump_across_epochs(self):
        c, db = make_db(seed=7)

        async def main():
            async def body(tr):
                tr.set(b"a", b"1")

            await db.run(body)
            v1 = c.sequencer.last_handed_out
            c.net.kill("master")
            await wait_for_epoch(c, 2)
            rv = c.controller.generation.recovery_version

            async def body2(tr):
                tr.set(b"b", b"2")

            await db.run(body2)
            tr = db.transaction()
            v2 = await tr.get_read_version()
            assert rv >= v1  # recovery version dominates everything acked
            assert v2 >= rv + EPOCH_VERSION_JUMP  # epoch gap: no collisions
            return "ok"

        assert run(c, main()) == "ok"

    def test_pre_recovery_read_version_stays_consistent_then_ages_out(self):
        """A read version from before recovery must never observe torn or
        post-recovery state: while still inside the (known-committed-bounded)
        MVCC window it reads the consistent old snapshot; once the floor
        catches up past it, reads fail TransactionTooOld — never b"2" or
        None."""
        c, db = make_db(seed=8)

        async def main():
            async def body(tr):
                tr.set(b"x", b"1")

            await db.run(body)
            tr_old = db.transaction()
            old_version = await tr_old.get_read_version()
            c.net.kill("master")
            await wait_for_epoch(c, 2)

            async def body2(tr):
                tr.set(b"x", b"2")

            # Two commits: the second's tlog push piggybacks the first's
            # known-committed version, releasing the storage GC floor.
            await db.run(body2)
            await db.run(body2)
            await c.loop.sleep(0.1)  # let storage apply + advance its floor

            tr = db.transaction()
            tr.set_read_version(old_version)
            try:
                v = await tr.get(b"x")
                assert v == b"1", v  # the old snapshot, nothing newer
            except TransactionTooOld:
                pass  # aged out — equally correct
            # By now the floor is past the old version: must be TooOld.
            tr2 = db.transaction()
            tr2.set_read_version(old_version)
            with pytest.raises(TransactionTooOld):
                await tr2.get(b"x")
            return "ok"

        assert run(c, main()) == "ok"

    def test_client_info_refresh(self):
        c, db = make_db(seed=9)

        async def main():
            old_eps = tuple(db.commit_proxies)
            c.net.kill("master")
            await wait_for_epoch(c, 2)

            async def body(tr):
                tr.set(b"post", b"recovery")

            await db.run(body)  # retry loop refreshes endpoints internally
            assert db.epoch == 2
            assert tuple(db.commit_proxies) != old_eps
            info = await c.controller_ep.get_client_info()
            assert info.epoch == 2
            return "ok"

        assert run(c, main()) == "ok"

    def test_concurrent_load_through_recovery(self):
        """Writers running WHILE the sequencer dies: every acked write is
        readable afterwards (durability), every retry path converges."""
        c, db = make_db(seed=10)

        async def main():
            acked = []

            async def writer(i):
                # Stagger so the stream straddles the kill + recovery window.
                await c.loop.sleep(i * 0.1)

                async def body(tr):
                    tr.set(b"w%03d" % i, b"v")

                await db.run(body)
                acked.append(i)

            from foundationdb_tpu.runtime.flow import all_of

            tasks = [c.loop.spawn(writer(i)) for i in range(30)]

            async def killer():
                await c.loop.sleep(0.5)
                c.net.kill("master")

            k = c.loop.spawn(killer())
            await all_of(tasks + [k])
            await wait_for_epoch(c, 2)
            assert c.controller.generation.epoch >= 2
            assert len(acked) == 30

            async def check(tr):
                for i in acked:
                    assert await tr.get(b"w%03d" % i) == b"v"

            await db.run(check)
            return "ok"

        assert run(c, main()) == "ok"

    def test_double_recovery(self):
        """Two successive kills → two epochs; data survives both."""
        c, db = make_db(seed=11)

        async def main():
            async def put(k, v):
                async def body(tr):
                    tr.set(k, v)

                await db.run(body)

            await put(b"a", b"1")
            c.net.kill("master")
            await wait_for_epoch(c, 2)
            await put(b"b", b"2")
            c.net.kill("master.e2")
            await wait_for_epoch(c, 3)
            await put(b"c", b"3")

            async def check(tr):
                assert await tr.get(b"a") == b"1"
                assert await tr.get(b"b") == b"2"
                assert await tr.get(b"c") == b"3"

            await db.run(check)
            return "ok"

        assert run(c, main()) == "ok"

    def test_unacked_write_rolls_back_with_lost_tlog(self):
        """A write durable on only one tlog (push to the other stalled, so
        never acked) must never surface: the pull loop's known-committed
        fence keeps it OUT of storage state entirely (it sits in the tlog
        beyond kc), and after the holding tlog dies, recovery derives its
        version from the survivor — the orphan is gone for good."""
        c, db = make_db(seed=13, n_tlogs=2)

        async def main():
            # Push to tlog1 stalls (proxy partition) → commit never acks,
            # but tlog0 has the entry and storage pulls it.
            c.net.partition("commit_proxy0", "tlog1")

            orphan_acked = []

            async def orphan():
                # No retry: a retry would legitimately re-commit through the
                # NEW generation, hiding the rollback under test.
                tr = db.transaction()
                tr.set(b"orphan", b"torn")
                try:
                    await tr.commit()
                    orphan_acked.append(True)
                except Exception:
                    pass  # commit_unknown_result — expected

            t = c.loop.spawn(orphan())
            await c.loop.sleep(0.5)
            # The entry is durable on tlog0 and peeked by storage's pull
            # loop, but the known-committed fence must keep the unacked
            # write out of applied state.
            assert c.storages[c.storage_map.tag_for_key(b"orphan")].map.latest(
                b"orphan"
            ) is None
            c.net.kill("tlog0")
            # Keep the partition until recovery locks tlog1 — otherwise the
            # stalled push retry could land, making the orphan durable.
            await wait_for_epoch(c, 2)
            c.net.heal("commit_proxy0", "tlog1")
            await t
            assert not orphan_acked

            async def check(tr):
                # The surviving tlog never held orphan@v: rolled back.
                assert await tr.get(b"orphan") is None
                tr.set(b"fresh", b"write")

            await db.run(check)

            async def check2(tr):
                assert await tr.get(b"fresh") == b"write"

            await db.run(check2)
            return "ok"

        assert run(c, main()) == "ok"

    def test_wedged_version_chain_forces_recovery(self):
        """A proxy↔tlog partition that outlives push retries leaves a gap in
        the tlog version chain: later batches park forever, and no process
        is dead so heartbeats see nothing. The commit proxy's wedge watchdog
        must request recovery, and commits must flow again WITHOUT the
        partition ever healing (new generation, new process names)."""
        c, db = make_db(seed=16)

        async def main():
            async def body(tr):
                tr.set(b"before", b"1")

            await db.run(body)
            c.net.partition("commit_proxy0", "tlog0")  # held forever

            async def body2(tr):
                tr.set(b"during", b"2")

            # Rides through: first attempts fail/wedge, watchdog forces
            # recovery, retry lands on the new generation's proxies.
            await db.run(body2)
            assert c.controller.generation.epoch >= 2

            async def check(tr):
                assert await tr.get(b"before") == b"1"
                assert await tr.get(b"during") == b"2"

            await db.run(check)
            return "ok"

        assert run(c, main()) == "ok"

    def test_gc_preserves_acked_value_under_unacked_suffix(self):
        """MVCC GC must not advance past known-committed: an unacked write
        pulled from one tlog can sit on storage for > the MVCC window (its
        push to the other tlog stalled); GC collapsing the chain onto it
        would make recovery's rollback erase the ACKED value underneath."""
        c, db = make_db(seed=15, n_tlogs=2)

        async def main():
            async def body(tr):
                tr.set(b"k", b"acked")

            await db.run(body)  # durable on both tlogs
            c.net.partition("commit_proxy0", "tlog1")
            # Disable the proxy's wedge watchdog: this test needs the wedge
            # to persist until the tlog DIES, so recovery happens with only
            # the stale replica tlog1 reachable (a CC partition would not
            # do — the controller's own failed pings would trigger recovery).
            c.commit_proxies[0].controller = None

            async def orphan():
                tr = db.transaction()
                tr.set(b"k", b"unacked")
                try:
                    await tr.commit()
                except Exception:
                    pass

            t = c.loop.spawn(orphan())

            # Background commit attempts keep the version clock + tlog0 chain
            # advancing well past the 5M-version MVCC window while every ack
            # stalls on the partition.
            async def churn():
                for _ in range(12):
                    tr = db.transaction()
                    tr.set(b"other", b"x")
                    try:
                        await tr.commit()
                    except Exception:
                        pass

            t2 = c.loop.spawn(churn())
            await c.loop.sleep(10.0)  # > MVCC window; GC cycles run
            c.net.kill("tlog0")
            await wait_for_epoch(c, 2)
            c.net.heal("commit_proxy0", "tlog1")
            await t
            await t2

            async def check(tr):
                # Rolled back to the acked value — not None, not "unacked".
                assert await tr.get(b"k") == b"acked"

            await db.run(check)
            return "ok"

        assert run(c, main()) == "ok"

    def test_tlog_trims_after_recovery(self):
        """Post-recovery tlogs must not grow without bound: cold tags pop on
        every version advance, raising the trim floor past the salvage seed."""
        c, db = make_db(seed=14)

        async def main():
            async def put(i):
                async def body(tr):
                    tr.set(b"t%03d" % i, b"v")

                await db.run(body)

            for i in range(20):
                await put(i)
            c.net.kill("master")
            await wait_for_epoch(c, 2)
            for i in range(20, 40):
                await put(i)
            await c.loop.sleep(1.0)  # let pulls/pops drain
            assert len(c.tlogs[0]._log) < 10  # trimmed, not 40+ entries
            return "ok"

        assert run(c, main()) == "ok"

    def test_recovery_stalls_until_tlog_reachable(self):
        """With every tlog dead, recovery must WAIT (unknown durable suffix),
        then complete once a tlog rejoins via partition heal."""
        c, db = make_db(seed=12)

        async def main():
            async def body(tr):
                tr.set(b"k", b"v")

            await db.run(body)
            # Partition the controller from the tlog (so recovery's lock RPC
            # fails) and kill the master (so recovery starts).
            c.net.partition("cluster_controller", "tlog0")
            c.net.kill("master")
            await c.loop.sleep(5.0)
            assert c.controller.generation.epoch == 1  # still stalled
            c.net.heal("cluster_controller", "tlog0")
            await wait_for_epoch(c, 2)

            async def check(tr):
                assert await tr.get(b"k") == b"v"

            await db.run(check)
            return "ok"

        assert run(c, main()) == "ok"


class TestRetiredProxy:
    """A recovery retires a proxy by cancelling its batch loop and answering
    what it holds. That was the QUEUE alone: a batch the loop had taken out
    and was waiting on the sequencer for, and a batch in _process waiting
    on a resolver or tlog of the old generation that never replies,
    belonged to nobody, and their clients waited for ever over a healthy
    connection (PR 28: test_sigkill_tlogs_mid_push_salvages_acked hung in
    1 of 2 whole runs and 4 of ~370 runs under load, the cluster healthy
    and idle behind it)."""

    class Unanswering:
        """A peer whose replies to `silent` never come."""

        def __init__(self, *silent):
            from foundationdb_tpu.runtime.flow import Promise

            self.silent = silent
            self.asked = Promise()
            self._never = Promise()

        def __getattr__(self, method):
            def call(*_a, **_kw):
                if method in self.silent:
                    self.asked.send(None)
                    return self._never.future
                return self._answer({"get_commit_version": (0, 1)}[method])

            return call

        @staticmethod
        async def _answer(value):
            return value

    @pytest.mark.parametrize("held", ["by_the_batcher", "in_process", "grv"])
    def test_what_a_retired_proxy_holds_is_answered(self, held):
        from foundationdb_tpu.core.errors import (
            CommitUnknownResult, ProcessKilled)
        from foundationdb_tpu.runtime.commit_proxy import (
            CommitProxy, CommitRequest)
        from foundationdb_tpu.runtime.flow import Loop
        from foundationdb_tpu.runtime.grv_proxy import GrvProxy
        from foundationdb_tpu.runtime.shardmap import KeyShardMap

        loop = Loop(seed=0)
        one = KeyShardMap.uniform(1)
        if held == "grv":
            peer = self.Unanswering("get_live_committed_version")
            proxy = GrvProxy(loop, peer)
            call, want = proxy.get_read_version(), ProcessKilled
        else:
            peer = self.Unanswering(
                "get_commit_version" if held == "by_the_batcher"
                else "resolve")
            proxy = CommitProxy(loop, peer, [peer], one, [], one)
            call = proxy.commit(CommitRequest(read_version=0))
            want = (ProcessKilled if held == "by_the_batcher"
                    else CommitUnknownResult)

        async def main():
            asked = loop.spawn(call, name="client")
            batcher = loop.spawn(proxy.run(), name="batcher")
            await peer.asked.future  # the request is out of the queue now
            batcher.cancel()  # what Worker.recruit_proxy / stand_down do
            if held != "grv":
                proxy.retire("proxy retired by recovery")
            with pytest.raises(want):
                await asked
            return "answered"

        assert loop.run(main(), timeout=60) == "answered"
