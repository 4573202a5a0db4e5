"""Consistency subsystem: replica byte-parity audit (consistency/).

Reference: fdbserver/workloads/ConsistencyCheck.actor.cpp. The contract
under test: the checker walks the shard map at one read version, compares
every replica of every team through each member's OWN serve path, paces
its chunks, survives concurrent data movement, and reports any seeded
divergence with the exact shard and first divergent key — while a green
run reports zero divergences.
"""

import pytest

from foundationdb_tpu.client.ryw import open_database
from foundationdb_tpu.consistency.checker import ConsistencyChecker
from foundationdb_tpu.consistency.scanner import (
    Divergence,
    RangeScanner,
    RatekeeperPacer,
    first_divergence,
    printable,
    rolling_checksum,
)
from foundationdb_tpu.runtime.flow import Loop
from foundationdb_tpu.sim.cluster import SimCluster


def make_replicated(seed=7, **kw):
    loop = Loop(seed=seed)
    kw.setdefault("n_storages", 3)
    kw.setdefault("n_replicas", 2)
    kw.setdefault("n_tlogs", 2)
    c = SimCluster(loop=loop, seed=seed, **kw)
    return loop, c, open_database(c)


async def put(db, kvs):
    async def body(tr):
        for k, v in kvs:
            tr.set(k, v)

    await db.run(body)


async def catch_up(loop, c):
    """Wait until every replica's pull loop applied the committed prefix —
    corruption must be seeded into an entry that actually EXISTS."""
    target = await c.sequencer.get_live_committed_version()
    deadline = loop.now + 30
    while loop.now < deadline and not all(
            s._version >= target for s in c.storages):
        await loop.sleep(0.05)
    assert all(s._version >= target for s in c.storages)


def corrupt_replica(cluster, key: bytes, replica_index: int = 1) -> int:
    """Flip one byte of `key`'s live value in ONE team member's store,
    BEHIND the serve path (the versioned map its reads serve from) — a
    torn sector / bad apply the audit must catch. Returns the tag."""
    shard = cluster.storage_map.shard_for_key(key)
    tag = shard.team[replica_index % len(shard.team)]
    chain = cluster.storages[tag].map._chains[key]
    v, val = chain[-1]
    chain[-1] = (v, bytes([val[0] ^ 0x01]) + val[1:])
    return tag


class TestScanner:
    """Pure scanner mechanics on synthetic members (no cluster)."""

    @staticmethod
    def member(name, rows):
        async def read(begin, end, _version, limit):
            return [r for r in rows if begin <= r[0] < end][:limit]

        return (name, read)

    def test_chunking_walks_whole_range(self):
        loop = Loop(seed=1)
        rows = [(b"k%03d" % i, b"v" * 10) for i in range(50)]
        sc = RangeScanner(loop, [self.member("a", rows),
                                 self.member("b", rows)],
                          chunk_bytes=64, max_rows=8)
        res = loop.run(sc.scan(b"", b"\xff", 1))
        assert res.chunks > 1  # bounded chunks, not one giant read
        assert not res.divergences
        # Both sides' rows counted: reference + 1 other member.
        assert res.rows_compared == 2 * len(rows)

    def test_exact_first_divergent_key_and_kinds(self):
        a = [(b"a", b"1"), (b"b", b"2"), (b"c", b"3")]
        assert first_divergence(a, a) is None
        assert first_divergence(a, [(b"a", b"1"), (b"b", b"X"), (b"c", b"3")]) \
            == (b"b", "value_mismatch")
        assert first_divergence(a, [(b"a", b"1"), (b"c", b"3")]) \
            == (b"b", "missing_row")
        assert first_divergence(a, a + [(b"d", b"4")]) == (b"d", "extra_row")
        assert rolling_checksum(a) != rolling_checksum(a[:2])

    def test_scanner_reports_divergence_in_right_chunk(self):
        loop = Loop(seed=2)
        rows = [(b"k%03d" % i, b"val%03d" % i) for i in range(40)]
        bad = list(rows)
        bad[31] = (bad[31][0], b"CORRUPT")
        sc = RangeScanner(loop, [self.member("good", rows),
                                 self.member("bad", bad)],
                          chunk_bytes=128, max_rows=8)
        res = loop.run(sc.scan(b"", b"\xff", 1))
        (d,) = res.divergences
        assert d.first_divergent_key == b"k031"
        assert d.kind == "value_mismatch"
        assert d.begin <= b"k031" < d.end  # exact chunk range named
        assert d.member == "bad" and d.reference == "good"

    def test_pacer_throttles_harder_when_ratekeeper_degraded(self):
        loop = Loop(seed=3)

        class FakeRK:
            def __init__(self, reason):
                self.reason = reason

            async def get_rates(self):
                return {"limiting_reason": self.reason}

        async def one(reason):
            p = RatekeeperPacer(loop, FakeRK(reason), bytes_per_s=1024)
            return await p.pace(1024)

        healthy = loop.run(one("none"))
        degraded = loop.run(one("storage_queue"))
        assert healthy == pytest.approx(1.0)
        assert degraded == pytest.approx(RatekeeperPacer.DEGRADED_BACKOFF)

    def test_divergence_json_is_printable(self):
        d = Divergence(begin=b"\x00a", end=b"\xffz", kind="value_mismatch",
                       first_divergent_key=b"k\x01", reference="a",
                       member="b", checksums={"a": 1, "b": 2})
        j = d.to_json()
        assert j["first_divergent_key"] == "k\\x01"
        assert printable(b"\\") == "\\x5c"


class TestChecker:
    def test_green_run_reports_zero_divergences(self):
        loop, c, db = make_replicated(seed=11)

        async def main():
            await put(db, [(b"g/%04d" % i, b"v%d" % i) for i in range(60)])
            report = await ConsistencyChecker(c, db).run()
            assert report["status"] == "consistent"
            assert report["divergences"] == []
            assert report["shards_checked"] == c.storage_map.n_shards
            # Every team member compared (2 replicas per shard).
            assert report["replicas_compared"] == 2 * c.storage_map.n_shards
            assert report["rows_compared"] > 0
            assert report["bytes_compared"] > 0
            assert report["paced_s"] > 0  # the audit actually paced itself
            return "ok"

        assert loop.run(main(), timeout=600) == "ok"

    def test_seeded_corruption_reports_exact_shard_and_key(self):
        """Satellite done-criterion: one flipped byte in one replica's
        store, behind the serve path → the report names the exact shard
        and a key range pinning the corrupted key; a green rerun after
        repair reports zero divergences."""
        loop, c, db = make_replicated(seed=13)
        key = b"sc/0042"

        async def main():
            await put(db, [(b"sc/%04d" % i, b"val%04d" % i)
                           for i in range(80)])
            await catch_up(loop, c)
            tag = corrupt_replica(c, key)
            shard = c.storage_map.shard_for_key(key)
            report = await ConsistencyChecker(c, db).run()
            assert report["status"] == "divergent"
            (d,) = report["divergences"]
            assert d["first_divergent_key"] == printable(key)
            assert d["kind"] == "value_mismatch"
            assert d["shard_begin"] == printable(shard.range.begin)
            assert d["shard_end"] == printable(shard.range.end)
            assert d["member"] == f"storage{tag}"
            assert tag in d["team"]
            # The named chunk range pins the key exactly.
            assert d["range_begin"] <= printable(key)
            # Trace surface: one event per divergence.
            assert any(
                r["Type"] == "ConsistencyCheckDivergence"
                for r in loop.tracer.recent()
            )
            # "Repair" the replica (write the true value back through the
            # normal path) → green again.
            await put(db, [(key, b"fixed")])
            report2 = await ConsistencyChecker(c, db).run()
            assert report2["status"] == "consistent"
            assert report2["divergences"] == []
            return "ok"

        assert loop.run(main(), timeout=600) == "ok"

    def test_missing_row_on_one_replica_detected(self):
        loop, c, db = make_replicated(seed=17)
        key = b"mr/0007"

        async def main():
            await put(db, [(b"mr/%04d" % i, b"x") for i in range(20)])
            await catch_up(loop, c)
            shard = c.storage_map.shard_for_key(key)
            tag = shard.team[1]
            # Drop the row entirely from one replica's store.
            c.storages[tag].map.purge_range(key, key + b"\x00")
            report = await ConsistencyChecker(c, db).run()
            assert report["status"] == "divergent"
            (d,) = report["divergences"]
            assert d["first_divergent_key"] == printable(key)
            assert d["kind"] == "missing_row"
            return "ok"

        assert loop.run(main(), timeout=600) == "ok"

    def test_tolerates_concurrent_data_movement(self):
        """The audit races a shard move (dual-tag fetch + map flip) and
        must still complete green: wrong_shard_server answers re-resolve
        the team from the live map, never surface as divergence."""
        loop, c, db = make_replicated(seed=19, data_distribution=True)

        async def main():
            await put(db, [(b"mv/%04d" % i, b"v%d" % i) for i in range(80)])
            shard = c.storage_map.shards[0]
            dst = tuple(t for t in range(3) if t != shard.team[0])[:2]

            async def mover():
                await c.data_distributor.move_shard(
                    shard.range.begin, shard.range.end, dst)

            mt = loop.spawn(mover(), name="test.move")
            report = await ConsistencyChecker(c, db).run()
            await mt
            assert report["status"] == "consistent", report["divergences"]
            # And a second pass over the settled map is green too.
            report2 = await ConsistencyChecker(c, db).run()
            assert report2["status"] == "consistent"
            return "ok"

        assert loop.run(main(), timeout=600) == "ok"

    def test_dead_replica_reported_unreachable_not_divergent(self):
        loop, c, db = make_replicated(seed=23)

        async def main():
            await put(db, [(b"dr/%04d" % i, b"v") for i in range(20)])
            c.net.kill("storage2")
            report = await ConsistencyChecker(c, db).run()
            assert report["status"] == "incomplete"
            assert report["divergences"] == []
            assert any(u["member"] == "storage2"
                       for u in report["unreachable"])
            return "ok"

        assert loop.run(main(), timeout=600) == "ok"

    def test_replica_dying_mid_scan_reported_not_crashed(self):
        """A member that dies AFTER the pre-scan probe (mid-chunk-walk)
        must land in `unreachable` with the survivors finishing the shard
        — the audit reports, it never crashes (review finding)."""
        from foundationdb_tpu.consistency.scanner import RatekeeperPacer

        loop, c, db = make_replicated(seed=37)

        async def main():
            await put(db, [(b"md/%04d" % i, b"v" * 8) for i in range(60)])
            await catch_up(loop, c)
            # Tiny chunks + slow pacing: each shard takes many chunks and
            # real virtual time, so the kill lands mid-scan.
            pacer = RatekeeperPacer(loop, None, bytes_per_s=256)

            async def killer():
                await loop.sleep(0.3)
                c.net.kill("storage1")

            kt = loop.spawn(killer(), name="test.kill")
            checker = ConsistencyChecker(c, db, chunk_bytes=32, max_rows=4,
                                         pacer=pacer)
            report = await checker.run()
            await kt
            assert report["status"] == "incomplete", report
            assert report["divergences"] == []
            assert any(u["member"] in ("storage1", "team")
                       for u in report["unreachable"]), report["unreachable"]
            return "ok"

        assert loop.run(main(), timeout=600) == "ok"

    def test_dr_never_drained_reports_incomplete(self):
        """A requested DR audit whose secondary never drains must NOT
        read as consistent: the operator asked for the secondary to be
        checked and it wasn't (review finding)."""
        from foundationdb_tpu.runtime.dr import DRAgent

        loop = Loop(seed=43)
        src = SimCluster(loop=loop, seed=43, n_storages=2)
        dst = SimCluster(loop=loop, seed=143, n_storages=2,
                         process_prefix="dst.")
        from foundationdb_tpu.client.ryw import open_database as od
        src_db, dst_db = od(src), od(dst)

        async def main():
            agent = DRAgent(src, src_db, dst_db)
            await agent.start()
            # Wedge the puller, then commit more: the stream can never
            # drain to any fresh audit version.
            agent.backup._worker.stop()
            await put(src_db, [(b"wd/%02d" % i, b"x") for i in range(10)])
            report = await ConsistencyChecker(src, src_db, dr=agent).run()
            assert report["dr"]["checked"] is False
            assert report["status"] == "incomplete", report["status"]
            agent._task.cancel()  # wedged worker: abort() would hang
            return "ok"

        assert loop.run(main(), timeout=600) == "ok"

    def test_status_json_carries_consistency_section(self):
        from foundationdb_tpu.runtime.status import fetch_status

        loop, c, db = make_replicated(seed=29)

        async def main():
            doc0 = await fetch_status(c)
            assert doc0["workload"]["consistency"]["status"] == "never_run"
            await put(db, [(b"st/a", b"1"), (b"st/b", b"2")])
            await ConsistencyChecker(c, db).run()
            doc = await fetch_status(c)
            sect = doc["workload"]["consistency"]
            assert sect["status"] == "consistent"
            assert sect["shards_checked"] == c.storage_map.n_shards
            assert sect["divergences"] == 0
            return "ok"

        assert loop.run(main(), timeout=600) == "ok"

    def test_workload_fails_on_seeded_corruption(self):
        """The sim-battery surface: ConsistencyCheckWorkload.check raises
        WorkloadFailed when a replica diverges (guards against a vacuous
        green in the spec battery)."""
        from foundationdb_tpu.sim.workloads import (
            ConsistencyCheckWorkload,
            WorkloadFailed,
        )

        loop, c, db = make_replicated(seed=31)
        w = ConsistencyCheckWorkload(seed=31, n_keys=16, n_txns=8)

        async def main():
            await w.run(db, c)
            await w.check(db)  # green first
            await catch_up(loop, c)
            # Corrupt one of the workload's own (user-keyspace) keys.
            shard = c.storage_map.shard_for_key(b"ccheck/")
            keys = c.storages[shard.team[0]].map.range_keys(
                b"ccheck/", b"ccheck0")
            corrupt_replica(c, keys[0])
            with pytest.raises(WorkloadFailed):
                await w.check(db)
            return "ok"

        assert loop.run(main(), timeout=600) == "ok"


def test_selfcheck_main_green(capsys):
    """python -m foundationdb_tpu.consistency: the CI stage —
    one JSON line, exit 0 on a consistent audit."""
    import json

    from foundationdb_tpu.consistency.__main__ import main

    rc = main(["--seed", "5", "--keys", "24", "--txns", "10"])
    assert rc == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["metric"] == "consistency_check"
    assert rec["status"] == "consistent"
    assert rec["shards_checked"] > 0
