"""Device-vectorized read path + packed watch fan-out (foundationdb_tpu/reads/).

Reference behaviors under test: batched point/range reads byte-identical
to the sequential VersionedMap oracle on every arm, the storage-side
deadline coalescer merging concurrent scalar reads, the packed watch
registry's fire-set exactness vs the dict oracle (storageserver.actor.cpp
watch contract: spurious fires legal, missed fires are the bug),
O(log n + hits) watch cancellation on shard moves, spurious fires on
rolled-back unacked writes, client get_multi / RYW overlay semantics,
status-JSON and doctor read-plane attribution.
"""

import random

import pytest

from foundationdb_tpu.core.errors import (
    FutureVersion,
    TooManyWatches,
    WrongShardServer,
)
from foundationdb_tpu.core.mutations import Mutation, MutationType as M
from foundationdb_tpu.reads.coalescer import ReadBrain
from foundationdb_tpu.reads.read_set import TPUReadSet
from foundationdb_tpu.reads.watches import WatchIndex
from foundationdb_tpu.runtime.flow import Loop, all_of
from foundationdb_tpu.runtime.storage import StorageServer


def make_ss(seed=0):
    loop = Loop(seed=seed)
    return loop, StorageServer(loop, tag=0, tlog_ep=None)


# ---------------------------------------------------------------------------
# TPUReadSet: batched reads vs the sequential oracle
# ---------------------------------------------------------------------------


def _loaded_ss(seed=0, n_keys=400, versions=4):
    loop, ss = make_ss(seed)
    rng = random.Random(seed)
    keys = sorted({bytes(rng.randrange(256) for _ in range(rng.randrange(1, 20)))
                   for _ in range(n_keys)})
    ss._apply(1, [Mutation(M.SET_VALUE, k, b"v1" + k[:4]) for k in keys])
    for v in range(2, versions + 1):
        ss._apply(v, [Mutation(M.SET_VALUE, rng.choice(keys), b"v%d" % v)
                      for _ in range(40)])
    return loop, ss, keys, rng


class TestTPUReadSet:
    @pytest.mark.parametrize("device", [False, True])
    def test_point_and_range_parity_vs_oracle(self, device):
        _loop, ss, keys, rng = _loaded_ss(seed=3)
        rs = TPUReadSet(ss.map, device=device)
        qkeys = [rng.choice(keys) for _ in range(50)] + [b"\x00missing", b"\xff"]
        qvers = [rng.randrange(1, 5) for _ in qkeys]
        got = rs.get_points(qkeys, qvers)
        want = [rs.oracle_get(k, v) for k, v in zip(qkeys, qvers)]
        assert got == want
        reqs = []
        for _ in range(20):
            a, b = sorted([rng.choice(keys), rng.choice(keys)])
            reqs.append((a, b + b"\x00", rng.randrange(1, 15),
                         rng.random() < 0.5, rng.randrange(1, 5)))
        got_r = rs.get_ranges(reqs)
        want_r = [rs.oracle_range(*r) for r in reqs]
        assert got_r == want_r

    def test_value_updates_never_repack_the_mirror(self):
        """The resident-dictionary economics: only KEY-SET changes rebuild
        the packed mirror; value updates ride the existing chains."""
        _loop, ss, keys, _rng = _loaded_ss(seed=5, n_keys=100)
        rs = ss.read_set
        assert rs.get_points([keys[0]], 1) == [rs.oracle_get(keys[0], 1)]
        assert rs.stats["rebuilds"] == 1
        ss._apply(10, [Mutation(M.SET_VALUE, keys[0], b"new")])
        assert rs.get_points([keys[0]], 10) == [b"new"]
        assert rs.stats["rebuilds"] == 1  # value update: no repack
        ss._apply(11, [Mutation(M.SET_VALUE, b"brand-new-key", b"x")])
        assert rs.get_points([b"brand-new-key"], 11) == [b"x"]
        assert rs.stats["rebuilds"] == 2  # key-set change: one repack

    def test_versions_resolve_like_versioned_map_at(self):
        loop, ss = make_ss()
        ss._apply(1, [Mutation(M.SET_VALUE, b"k", b"a")])
        ss._apply(3, [Mutation(M.SET_VALUE, b"k", b"b")])
        ss._apply(5, [Mutation(M.CLEAR_RANGE, b"k", b"k\x00")])
        rs = ss.read_set
        assert rs.get_points([b"k"] * 4, [1, 2, 3, 5]) == [
            b"a", b"a", b"b", None]


# ---------------------------------------------------------------------------
# The read coalescer
# ---------------------------------------------------------------------------


class TestReadBrain:
    def test_deadline_only_policy(self):
        brain = ReadBrain(budget_ms=50.0, max_window=8)
        assert brain.decide(0, 100.0) == 0
        # Below budget with room in the window: hold (amortize).
        assert brain.decide(3, 0.0) == 0
        # Window full: ship regardless of age.
        assert brain.decide(8, 0.0) == 8
        assert brain.decide(20, 0.0) == 8
        # Oldest request's budget (minus predicted dispatch cost) spent.
        assert brain.decide(3, 49.0) == 3
        # budget 0 = immediate mode.
        assert ReadBrain(budget_ms=0.0, max_window=8).decide(2, 0.0) == 2

    def test_concurrent_scalar_gets_merge_into_fewer_dispatches(self):
        loop, ss = make_ss()
        keys = [b"c/%03d" % i for i in range(16)]
        ss._apply(1, [Mutation(M.SET_VALUE, k, b"v" + k) for k in keys])
        ss._batch_scalar_reads = True
        ss._reads.brain.budget_ms = 5.0

        async def main():
            vals = await all_of(
                [loop.spawn(ss.get(k, 1), name=f"g{i}")
                 for i, k in enumerate(keys)])
            return vals

        vals = loop.run(main(), timeout=60)
        assert vals == [b"v" + k for k in keys]
        st = ss._reads.stats
        assert st["requests"] == 16
        assert st["dispatches"] < 16  # merged, not the per-key actor pattern
        assert ss._reads.reads_per_dispatch > 1.0

    def test_get_multi_rpc_matches_sequential_gets(self):
        loop, ss, keys, rng = _loaded_ss(seed=7)

        async def main():
            ks = [rng.choice(keys) for _ in range(24)] + [b"\x00nope"]
            got = await ss.get_multi(ks, 4)
            want = [await ss.get(k, 4) for k in ks]
            return got == want

        assert loop.run(main(), timeout=60)

    def test_batched_get_range_matches_unbatched(self):
        loop, ss, keys, _rng = _loaded_ss(seed=9)
        lo, hi = keys[10], keys[60]

        async def main():
            plain = await ss.get_range(lo, hi, 4, limit=20)
            ss._batch_scalar_reads = True
            batched = await ss.get_range(lo, hi, 4, limit=20)
            return plain == batched

        assert loop.run(main(), timeout=60)


# ---------------------------------------------------------------------------
# WatchIndex: packed fan-out parity + O(log n + hits) cancel
# ---------------------------------------------------------------------------


class _P:
    """Promise-shaped fire recorder."""

    def __init__(self, wid, log):
        self.wid, self.log = wid, log

    def send(self, version):
        self.log.append((self.wid, version))

    def fail(self, exc):
        self.log.append((self.wid, "fail"))


def _watch_trace(arm, seed=11, n_keys=60, rounds=25):
    """One deterministic add/sweep interleaving; returns the fire set."""
    rng = random.Random(seed)
    keys = [b"wt/%04d" % i for i in range(n_keys)]
    idx = WatchIndex(arm=arm)
    log: list = []
    model: dict = {}  # key -> list[(expect, wid)] — the dict oracle
    model_fires: list = []
    wid = 0
    for version in range(1, rounds + 1):
        for _ in range(rng.randrange(0, 6)):
            k = rng.choice(keys)
            expect = None if rng.random() < 0.3 else b"e%d" % rng.randrange(4)
            idx.add(k, expect, _P(wid, log))
            model.setdefault(k, []).append((expect, wid))
            wid += 1
        written = [(rng.choice(keys),
                    None if rng.random() < 0.2 else b"e%d" % rng.randrange(4))
                   for _ in range(rng.randrange(1, 8))]
        idx.sweep(version, written)
        final: dict = {}
        for k, v in written:
            final[k] = v
        for k, v in final.items():
            keep = []
            for expect, w in model.get(k, []):
                if v != expect:
                    model_fires.append((w, version))
                else:
                    keep.append((expect, w))
            if k in model:
                if keep:
                    model[k] = keep
                else:
                    del model[k]
    assert idx.count == sum(len(v) for v in model.values())
    return set(log), set(model_fires)


class TestWatchIndex:
    def test_fire_sets_identical_across_arms_and_vs_oracle(self):
        """The satellite exactness gate: packed and device sweeps fire
        EXACTLY the oracle's (watch, version) set — no extra spurious
        fires from the vectorized probe, none missed."""
        for seed in (11, 12, 13):
            fires0, want = _watch_trace("0", seed=seed)
            fires1, want1 = _watch_trace("1", seed=seed)
            assert want == want1
            assert fires0 == fires1 == want
        # Device arm (eager jax dispatch per sweep — one seed keeps the
        # tier-1 clock honest; bench_watch_parity covers it again).
        firesd, wantd = _watch_trace("device", seed=11, rounds=12)
        fires1, want1 = _watch_trace("1", seed=11, rounds=12)
        assert wantd == want1
        assert firesd == fires1 == wantd

    def test_same_version_rewrite_back_does_not_fire(self):
        """Per-version FINAL-value compare: an A→B→A rewrite inside one
        version leaves the watch armed (allowed by the contract, and
        pinned so every arm agrees)."""
        log: list = []
        idx = WatchIndex(arm="1")
        idx.add(b"k", b"a", _P(0, log))
        assert idx.sweep(7, [(b"k", b"b"), (b"k", b"a")]) == 0
        assert log == [] and idx.count == 1
        assert idx.sweep(8, [(b"k", b"b")]) == 1
        assert log == [(0, 8)] and idx.count == 0

    def test_cancel_range_is_log_n_plus_hits(self):
        """The shard-move satellite: cancelling a 10-key range out of
        4000 armed watches scans the hit run only — the seed scanned
        every armed watch."""
        log: list = []
        idx = WatchIndex(arm="1")
        for i in range(4000):
            idx.add(b"ck/%05d" % i, None, _P(i, log))
        idx.sweep(1, [(b"zz-absent", b"x")])  # consolidates the index
        assert not idx._pending
        idx.stats["cancel_scanned"] = 0
        out = idx.cancel_range(b"ck/00100", b"ck/00110")
        assert sorted(k for k, _e, _p in out) == [
            b"ck/%05d" % i for i in range(100, 110)]
        assert idx.stats["cancel_scanned"] == 10  # hits only, not 4000
        assert idx.count == 3990

    def test_cancel_right_after_add_burst_scans_only_the_tail(self):
        """No hidden consolidate inside cancel: a burst of adds since the
        last sweep costs the cancel only the pending-tail scan."""
        log: list = []
        idx = WatchIndex(arm="1")
        for i in range(2000):
            idx.add(b"ck/%05d" % i, None, _P(i, log))
        idx.sweep(1, [(b"zz-absent", b"x")])
        for i in range(2000, 2030):  # unconsolidated tail
            idx.add(b"ck/%05d" % i, None, _P(i, log))
        idx.stats["cancel_scanned"] = 0
        out = idx.cancel_range(b"ck/02010", b"ck/02020")
        assert len(out) == 10
        assert idx.stats["cancel_scanned"] <= 30  # tail-bounded, not 2030

    def test_host_arm_consolidates_pending_on_sweep(self):
        """Review fix: the host arm must fold the pending tail into the
        sorted index on sweep too, or cancel_range's tail scan degrades
        to O(all adds ever)."""
        log: list = []
        idx = WatchIndex(arm="0")
        for i in range(1000):
            idx.add(b"hk/%04d" % i, None, _P(i, log))
        idx.sweep(1, [(b"zz-absent", b"x")])
        assert not idx._pending
        assert len(idx._sorted) == 1000
        idx.stats["cancel_scanned"] = 0
        out = idx.cancel_range(b"hk/0100", b"hk/0110")
        assert len(out) == 10
        assert idx.stats["cancel_scanned"] == 10  # hits only, not 1000

    def test_cancel_range_accounting_over_pending_tail(self):
        """Review fix: pending-tail cancels have no _sorted rows — they
        must not inflate the tombstone count, and the cancelled keys must
        not linger in _pending to be merged later as uncounted rows."""
        log: list = []
        idx = WatchIndex(arm="1")
        for i in range(100):
            idx.add(b"pk/%03d" % i, None, _P(i, log))
        idx.sweep(1, [(b"zz-absent", b"x")])  # consolidates 0..99
        for i in range(100, 120):
            idx.add(b"pk/%03d" % i, None, _P(i, log))  # pending tail
        out = idx.cancel_range(b"pk/100", b"pk/120")
        assert len(out) == 20
        assert idx._dead == 0  # no _sorted row died
        assert all(not (b"pk/100" <= k < b"pk/120") for k in idx._pending)
        idx._consolidate()  # must not resurrect cancelled keys
        assert all(not (b"pk/100" <= k < b"pk/120") for k in idx._sorted)
        # Consolidated-row cancels count exactly the rows tombstoned.
        out2 = idx.cancel_range(b"pk/000", b"pk/010")
        assert len(out2) == 10
        assert idx._dead == 10

    def test_shard_move_fails_in_range_watches_only(self):
        loop, ss = make_ss()
        ss.init_served([(b"", b"\xff")])
        ss._apply(1, [Mutation(M.SET_VALUE, b"m/1", b"a"),
                      Mutation(M.SET_VALUE, b"z/1", b"a")])

        async def main():
            t_in = loop.spawn(ss.watch(b"m/1", b"a"), name="w_in")
            t_out = loop.spawn(ss.watch(b"z/1", b"a"), name="w_out")
            await loop.sleep(0.001)
            assert ss.watches.count == 2
            ss.end_serve(b"m/", b"m0", end_version=1)
            await loop.sleep(0.001)
            assert t_in.is_error()
            assert isinstance(t_in.exception(), WrongShardServer)
            assert ss.watches.count == 1
            ss._apply(2, [Mutation(M.SET_VALUE, b"z/1", b"b")])
            return await t_out

        assert loop.run(main(), timeout=10) == 2


# ---------------------------------------------------------------------------
# Storage watch contract under the packed registry
# ---------------------------------------------------------------------------


class TestStorageWatches:
    def test_too_many_watches_under_packed_registry(self, monkeypatch):
        loop, ss = make_ss()
        monkeypatch.setattr(StorageServer, "MAX_WATCHES", 3)
        assert isinstance(ss.watches, WatchIndex)

        async def main():
            for i in range(3):
                loop.spawn(ss.watch(b"k%d" % i, None), name=f"w{i}")
            await loop.sleep(0.001)
            with pytest.raises(TooManyWatches):
                await ss.watch(b"k9", None)
            assert ss._too_many_watches == 1
            # Firing one frees a slot.
            ss._apply(1, [Mutation(M.SET_VALUE, b"k0", b"v")])
            assert ss.watches.count == 2
            loop.spawn(ss.watch(b"k9", None), name="w9")
            await loop.sleep(0.001)
            assert ss.watches.count == 3
            return "ok"

        assert loop.run(main(), timeout=10) == "ok"

    def test_spurious_fire_on_rolled_back_unacked_write(self):
        """The reference contract: watches fire at APPLY time, before
        durability acks — a write recovery later rolls back still fires
        its watch (the client re-reads), and the rollback must not hang
        or double-fire anything."""
        loop, ss = make_ss()
        ss._apply(1, [Mutation(M.SET_VALUE, b"k", b"a")])
        ss.known_committed = 1

        async def main():
            t = loop.spawn(ss.watch(b"k", b"a"), name="w")
            await loop.sleep(0.001)
            # Applied but unacked (above known_committed): fires anyway.
            ss._apply(2, [Mutation(M.SET_VALUE, b"k", b"b")])
            fired_at = await t
            # Recovery rolls the suffix back: the fire was spurious.
            ss.recover_to(1, tlog_ep=None)
            assert ss.map.latest(b"k") == b"a"
            assert ss._version == 1
            return fired_at

        assert loop.run(main(), timeout=10) == 2
        assert ss.watches.stats["fired"] == 1
        assert ss.watches.count == 0


# ---------------------------------------------------------------------------
# Client surface: Transaction.get_multi and the RYW overlay
# ---------------------------------------------------------------------------


class TestClientGetMulti:
    def _db(self, seed=0):
        from foundationdb_tpu.client.ryw import open_database
        from foundationdb_tpu.sim.cluster import SimCluster

        c = SimCluster(seed=seed)
        return c, open_database(c)

    def test_get_multi_matches_sequential_gets(self):
        c, db = self._db(1)

        async def main():
            tr = db.transaction()
            for i in range(20):
                tr.set(b"gm/%02d" % i, b"v%02d" % i)
            await tr.commit()
            tr2 = db.transaction()
            ks = [b"gm/%02d" % i for i in range(20)] + [b"gm/absent"]
            batched = await tr2.get_multi(ks)
            single = [await tr2.get(k) for k in ks]
            return batched == single

        assert c.loop.run(main(), timeout=300)

    def test_get_multi_conflict_ranges_match_gets(self):
        c, db = self._db(2)

        async def main():
            tr = db.transaction()
            tr.set(b"a", b"0")
            tr.set(b"b", b"0")
            await tr.commit()
            t1 = db.transaction()
            await t1.get_multi([b"a", b"b"])
            t2 = db.transaction()
            await t2.get_multi([b"a", b"b"], snapshot=True)
            # Serializable get_multi owes the same conflict ranges as
            # the equivalent gets; snapshot owes none.
            return len(t1.read_ranges), len(t2.read_ranges)

        assert c.loop.run(main(), timeout=300) == (2, 0)

    def test_ryw_overlay_serves_pending_writes(self):
        c, db = self._db(3)

        async def main():
            tr = db.transaction()
            tr.set(b"b", b"committed")
            await tr.commit()
            tr2 = db.transaction()
            tr2.set(b"a", b"pending")
            got = await tr2.get_multi([b"a", b"b", b"c"])
            assert got == [b"pending", b"committed", None]
            tr2.clear(b"b")
            return await tr2.get_multi([b"a", b"b"])

        assert c.loop.run(main(), timeout=300) == [b"pending", None]

    def test_ryw_get_multi_duplicate_key_with_atomic_overlay(self):
        """Review fix: a key listed twice with a pending atomic-op
        overlay must resolve to the SAME folded value at every position
        (the first fold rewrites the overlay to "value"; the second
        occurrence used to get the raw storage base)."""
        c, db = self._db(5)

        async def main():
            tr = db.transaction()
            tr.set(b"ctr", (5).to_bytes(8, "little"))
            await tr.commit()
            tr2 = db.transaction()
            tr2.atomic_op(M.ADD, b"ctr", (1).to_bytes(8, "little"))
            got = await tr2.get_multi([b"ctr", b"x", b"ctr"])
            single = await tr2.get(b"ctr")
            return got, single

        got, single = c.loop.run(main(), timeout=300)
        want = (6).to_bytes(8, "little")
        assert got == [want, None, want]
        assert single == want

    def test_status_json_reads_section(self):
        from foundationdb_tpu.runtime.status import fetch_status

        c, db = self._db(4)

        async def main():
            tr = db.transaction()
            for i in range(12):
                tr.set(b"s/%02d" % i, b"v")
            await tr.commit()
            tr2 = db.transaction()
            await tr2.get_multi([b"s/%02d" % i for i in range(12)])
            return await fetch_status(c)

        doc = c.loop.run(main(), timeout=300)
        rd = doc["workload"]["reads"]
        assert rd["served"] >= 12
        assert rd["dispatches"] >= 1
        assert rd["per_dispatch"] >= 1.0
        for k in ("queue_depth", "occupancy", "watch_count",
                  "watch_fires", "too_many_watches"):
            assert k in rd


# ---------------------------------------------------------------------------
# Database.read_keys failover discipline
# ---------------------------------------------------------------------------


class _LaggingEp:
    """get_multi raises FutureVersion `behind` times, then serves."""

    def __init__(self, behind):
        self.behind = behind

    async def get_multi(self, keys, version, token=None):
        if self.behind > 0:
            self.behind -= 1
            raise FutureVersion("replica behind")
        return [b"v:" + k for k in keys]


class _MovedOnceEp:
    """get_multi raises WrongShardServer once, then serves."""

    def __init__(self):
        self.moved = False

    async def get_multi(self, keys, version, token=None):
        if not self.moved:
            self.moved = True
            raise WrongShardServer("shard moved")
        return [b"v:" + k for k in keys]


class _SplitMap:
    """Keys below b'm' team {0}, the rest team {1}."""

    def team_for_key(self, key):
        return [0] if key < b"m" else [1]


class TestReadKeysFailover:
    """Review fix: a lagging team's keys must retry or raise — NEVER
    fall out of the loop as a spurious None while another group's
    wrong_shard_server retry keeps the iteration going."""

    def _db(self, eps):
        from foundationdb_tpu.client.transaction import Database

        loop = Loop(seed=0)
        return loop, Database(loop, [], [], _SplitMap(), eps)

    def test_transient_lag_rides_the_retry_loop(self):
        loop, db = self._db([_LaggingEp(behind=1), _MovedOnceEp()])

        async def main():
            return await db.read_keys([b"a", b"z"], version=5)

        assert loop.run(main(), timeout=10) == [b"v:a", b"v:z"]

    def test_persistent_lag_raises_not_spurious_none(self):
        loop, db = self._db([_LaggingEp(behind=10_000), _MovedOnceEp()])

        async def main():
            with pytest.raises(FutureVersion):
                await db.read_keys([b"a", b"z"], version=5)
            return "ok"

        assert loop.run(main(), timeout=10) == "ok"


# ---------------------------------------------------------------------------
# Workloads driving the batched plane (YCSB, watch fan-out)
# ---------------------------------------------------------------------------


class TestReadWorkloads:
    def test_ycsb_and_watch_fanout_specs(self):
        from foundationdb_tpu.client.ryw import open_database
        from foundationdb_tpu.sim.cluster import SimCluster
        from foundationdb_tpu.sim.specs import run_spec

        c = SimCluster(seed=21, n_tlogs=2, n_storages=2)
        db = open_database(c)
        results = run_spec("""
[[test]]
testTitle = 'YCSBSmoke'
[[test.workload]]
testName = 'YCSB'
variant = 'B'
keyCount = 32
transactionCount = 16
clientCount = 2
batchSize = 4

[[test]]
testTitle = 'WatchFanOut'
[[test.workload]]
testName = 'WatchFanOut'
keyCount = 4
watchersPerKey = 3
""", c, db)
        assert len(results) == 2
        ycsb = results[0].metrics["ycsb"]
        assert ycsb.ops == 16
        fan = results[1].metrics["watch_fanout"]
        assert fan.extra["fan_out"] == 12

    def test_ycsb_variant_c_is_read_only(self):
        from foundationdb_tpu.sim.workloads import YCSBWorkload

        w = YCSBWorkload(variant="C")
        assert w.update_fraction == 0.0
        with pytest.raises(ValueError):
            YCSBWorkload(variant="A")


# ---------------------------------------------------------------------------
# Observability: doctor read-plane attribution
# ---------------------------------------------------------------------------


def _snap(t, committed, read_sums):
    m = {"commit_proxy.txns_committed": committed}
    for k, v in read_sums.items():
        m["obs.stage_sum_ms." + k] = v
    return {"kind": "snapshot", "t": t, "metrics": m}


class TestDoctorReadAttribution:
    def _ring(self):
        """Baseline goodput with a quiet read plane, then a goodput
        collapse with read_dispatch exploding — a read storm."""
        recs, committed, t = [], 0, 0.0
        rc = {"read_coalesce": 0.0, "read_pack": 0.0, "read_dispatch": 0.0}
        for _ in range(10):
            committed += 100
            rc["read_coalesce"] += 5.0
            rc["read_pack"] += 1.0
            rc["read_dispatch"] += 2.0
            recs.append(_snap(t, committed, rc))
            t += 1.0
        for _ in range(6):
            committed += 3
            rc["read_coalesce"] += 5.0
            rc["read_pack"] += 1.0
            rc["read_dispatch"] += 60.0
            recs.append(_snap(t, committed, rc))
            t += 1.0
        return recs

    def test_read_storm_attributed_to_read_dispatch(self):
        from foundationdb_tpu.obs.doctor import diagnose

        report = diagnose(self._ring())
        assert report["incidents"], "goodput collapse must open an incident"
        inc = report["incidents"][0]
        assert inc["sli"] == "goodput_tps"
        rs = inc["dominant_read_stage"]
        assert rs is not None and rs["stage"] == "read_dispatch"
        assert rs["share_during"] > rs["share_before"]
        assert rs["baseline_windows"] is True
        assert "read plane: read_dispatch" in inc["summary"]

    def test_quiet_read_plane_yields_none_not_zero(self):
        from foundationdb_tpu.obs.doctor import diagnose, dominant_read_stage

        recs, committed, t = [], 0, 0.0
        for _ in range(10):
            committed += 100
            recs.append(_snap(t, committed, {}))
            t += 1.0
        for _ in range(4):
            committed += 3
            recs.append(_snap(t, committed, {}))
            t += 1.0
        report = diagnose(recs)
        assert report["incidents"]
        assert report["incidents"][0]["dominant_read_stage"] is None
        assert dominant_read_stage(recs, 9.0, 13.0) is None

    def test_read_stage_metrics_documented(self):
        from foundationdb_tpu.obs.span import READ_STAGES

        assert set(READ_STAGES) == {
            "read_coalesce", "read_pack", "read_dispatch", "watch_sweep"}


# ---------------------------------------------------------------------------
# The selfcheck surface
# ---------------------------------------------------------------------------


class TestSelfcheck:
    @pytest.mark.slow
    def test_selfcheck_passes(self):
        from foundationdb_tpu.reads.__main__ import selfcheck

        rec = selfcheck(seed=1)
        assert rec["ok"], rec

    def test_watch_parity_bench(self):
        from foundationdb_tpu.reads.bench import bench_watch_parity

        assert bench_watch_parity(n_keys=40, versions=8, seed=5)
