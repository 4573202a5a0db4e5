"""Admission-time early conflict detection (foundationdb_tpu/admission).

Fast battery for the admission subsystem: filter semantics (aging by
version window, backend parity, delta feed), policy tiers (exact-shadow
pre-abort vs Bloom shaping, the system-lane bypass, the starvation
ceiling), the ORACLE-PARITY pre-abort honesty contract (every pre-aborted
txn is a true conflict loser — its confirming committed write really
exists in the resolve oracle's history, newer than the txn's snapshot),
shaped-lane behavior end to end in the sim cluster, the device-resident
(TPUConflictSet) feed across dictionary eviction, and the GRV/ratekeeper
saturation plumbing.
"""

from __future__ import annotations

import numpy as np
import pytest

from foundationdb_tpu.admission import (
    AdmissionPolicy,
    RecentWritesFilter,
    fingerprints,
    u64_cols_fingerprint,
)
from foundationdb_tpu.core.errors import AdmissionPreAborted, AdmissionShaped
from foundationdb_tpu.core.types import KeyRange, single_key_range
from foundationdb_tpu.runtime.flow import Loop
from foundationdb_tpu.runtime.grv_proxy import GrvProxy
from foundationdb_tpu.runtime.ratekeeper import Ratekeeper


def _mk_filter(**kw):
    kw.setdefault("bits_log2", 12)
    kw.setdefault("banks", 4)
    kw.setdefault("window_versions", 1000)
    return RecentWritesFilter(**kw)


class TestRecentWritesFilter:
    def test_point_hits_gate_on_read_version(self):
        f = _mk_filter()
        f.record([b"hot"], 100)
        # Older snapshot sees the newer write as a hit...
        assert f.probe_keys([b"hot"], 50).tolist() == [True]
        assert f.probe_exact(b"hot", 50) == 100
        # ...a snapshot at/after the write does not.
        assert f.probe_keys([b"hot"], 100).tolist() == [False]
        assert f.probe_exact(b"hot", 100) is None
        # Unrelated key: no hit (no collision at this fill level).
        assert f.probe_keys([b"cold"], 0).tolist() == [False]

    def test_aging_across_version_windows(self):
        """The saturation/aging satellite: banks rotate with the version
        stream (window/banks versions per bank) and a write eventually
        ages out of BOTH tiers."""
        f = _mk_filter()  # slice = 250 versions
        f.record([b"old"], 10)
        assert f.probe_keys([b"old"], 0).tolist() == [True]
        # Advance within the window: still present.
        f.record([b"mid"], 700)
        assert f.probe_keys([b"old"], 0).tolist() == [True]
        # Advance past the full window: the old bank was recycled.
        f.record([b"new"], 10 + 4 * 250 + 1)
        assert f.rotations >= 4
        assert f.probe_keys([b"old"], 0).tolist() == [False]
        assert f.probe_exact(b"old", 0) is None
        assert f.probe_keys([b"new"], 0).tolist() == [True]

    def test_saturation_rises_and_rotation_clears(self):
        f = _mk_filter(bits_log2=8)  # 256 slots: easy to fill
        assert f.saturation() == 0.0
        f.record([b"k%04d" % i for i in range(200)], 100)
        high = f.saturation()
        assert high > 0.5
        # A full window of rotations later the current bank is fresh.
        f.advance(100 + 4 * 250 + 1)
        assert f.saturation() == 0.0
        assert f.metrics()["recorded"] == 200

    def test_numpy_jax_backend_parity(self):
        """The device-resident banks must answer bit-identically to the
        host backend (same hashing, same bank schedule)."""
        rng = np.random.default_rng(7)
        keys = [b"k%06d" % rng.integers(0, 500) for _ in range(300)]
        versions = sorted(int(v) for v in rng.integers(0, 2000, 300))
        f_np = _mk_filter(window_versions=2000)
        f_jx = _mk_filter(window_versions=2000, backend="jax")
        for k, v in zip(keys, versions):
            f_np.record([k], v)
            f_jx.record([k], v)
        probes = [b"k%06d" % i for i in range(500)]
        for rv in (0, 500, 1500, 2500):
            a = f_np.probe_keys(probes, rv)
            b = f_jx.probe_keys(probes, rv)
            assert a.tolist() == b.tolist()
        assert f_np.rotations == f_jx.rotations

    def test_delta_feed_round_trip(self):
        """Resolver → proxy feed: applying a delta reproduces both tiers;
        double-feeding is idempotent; a laggard consumer only UNDER-
        detects (misses older entries), never over-claims."""
        src = _mk_filter()
        src.record([b"a", b"b"], 100)
        src.record([b"c"], 150)
        seq, entries = src.delta_since(0)
        assert seq == 3 and len(entries) == 3
        dst = _mk_filter()
        dst.apply_delta(entries)
        dst.apply_delta(entries)  # idempotent double-feed
        assert dst.probe_exact(b"a", 50) == 100
        assert dst.probe_exact(b"c", 100) == 150
        # Incremental: nothing new → empty delta.
        seq2, more = src.delta_since(seq)
        assert seq2 == seq and more == []

    def test_u64_fingerprint_matches_key_columns(self):
        """The device path fingerprints the resident mirror's u64 key
        columns; recording via raw keys and probing via columns must
        agree on the Bloom tier for the SAME fingerprint input."""
        f = _mk_filter()
        cols = np.array([[1, 2], [3, 4]], np.uint64)
        fps = u64_cols_fingerprint(cols)
        f.record_u64(fps, 100)
        assert f.probe_u64(fps, 50).tolist() == [True, True]
        assert f.probe_u64(u64_cols_fingerprint(
            np.array([[9, 9]], np.uint64)), 50).tolist() == [False]


class TestAdmissionPolicy:
    def test_system_priority_never_shaped_or_preaborted(self):
        f = _mk_filter()
        pol = AdmissionPolicy(filter=f, enabled=True)
        f.record([b"hot"], 100)
        for _ in range(20):
            d = pol.decide([single_key_range(b"hot")], 0, priority="system")
            assert d.action == "admit"
        assert pol.counters["system_bypass"] == 20
        assert pol.counters["system_shaped"] == 0
        assert pol.counters["preaborted"] == 0

    def test_preabort_requires_exact_confirmation(self):
        """A Bloom-tier hit WITHOUT shadow evidence may shape, never
        pre-abort (the honesty tier separation)."""
        f = _mk_filter()
        pol = AdmissionPolicy(filter=f, enabled=True)
        # Bloom-only feed (the device path): shadow stays empty.
        f.record_u64(fingerprints([b"hot"]), 100)
        d = pol.decide([single_key_range(b"hot")], 0)
        assert d.action == "shape"
        assert pol.counters["preaborted"] == 0
        # Shadow feed: now provable → pre-abort, with the evidence logged.
        f.record([b"hot"], 200)
        d = pol.decide([single_key_range(b"hot")], 50)
        assert d.action == "preabort" and d.confirm_version == 200
        assert pol.preabort_log == [(b"hot", 200, 50)]

    def test_preabort_ceiling_degrades_to_canonical_path(self):
        f = _mk_filter()
        pol = AdmissionPolicy(filter=f, enabled=True)
        f.record([b"hot"], 100)
        reads = [single_key_range(b"hot")]
        assert pol.decide(reads, 0, attempts=0).action == "preabort"
        d = pol.decide(reads, 0, attempts=AdmissionPolicy.PREABORT_CEILING)
        assert d.action == "admit"
        assert pol.counters["preabort_ceiling"] == 1

    def test_engage_release_episodes_have_hysteresis(self):
        """The obs flight recorder annotates admission engage/release
        EPISODES from these counter deltas: first intervention engages,
        only RELEASE_CLEAN consecutive clean admits release — a workload
        shaping one txn in fifty must not flap an episode per batch."""
        f = _mk_filter()
        pol = AdmissionPolicy(filter=f, enabled=True)
        f.record([b"hot"], 100)
        assert pol.counters["engage_events"] == 0 and not pol.engaged
        assert pol.decide([single_key_range(b"hot")], 0).action == "preabort"
        assert pol.counters["engage_events"] == 1 and pol.engaged
        # A second intervention does NOT count a second episode...
        assert pol.decide([single_key_range(b"hot")], 0).action == "preabort"
        assert pol.counters["engage_events"] == 1
        # ...and a below-threshold clean streak does not release, even
        # when an intervention interrupts it midway (streak resets).
        for _ in range(AdmissionPolicy.RELEASE_CLEAN - 1):
            assert pol.decide([single_key_range(b"cold")], 0).action == \
                "admit"
        assert pol.engaged and pol.counters["release_events"] == 0
        pol.decide([single_key_range(b"hot")], 0)  # streak resets
        for _ in range(AdmissionPolicy.RELEASE_CLEAN):
            pol.decide([single_key_range(b"cold")], 0)
        assert not pol.engaged
        assert pol.counters["release_events"] == 1
        assert pol.metrics()["engaged"] == 0  # rides the scrape plane

    def test_wide_ranges_never_preabort(self):
        """Un-enumerable range reads fall back to sketch shaping only."""
        f = _mk_filter()
        pol = AdmissionPolicy(filter=f, enabled=True)
        f.record([b"m"], 100)
        d = pol.decide([KeyRange(b"a", b"z")], 0)
        assert d.action == "admit"  # no sketch attached, no per-key probe
        assert pol.counters["preaborted"] == 0

    def test_disabled_policy_admits_everything(self):
        f = _mk_filter()
        pol = AdmissionPolicy(filter=f, enabled=False)
        f.record([b"hot"], 100)
        assert pol.decide([single_key_range(b"hot")], 0).action == "admit"
        assert pol.saturation() == 0.0


def _wrap_write_ledger(c) -> list:
    """Record every ACCEPTED write (begin, end, version) the resolve
    oracle ever admits — an un-GC'd shadow of the oracle history, so
    honesty checks stay exhaustive past the MVCC window."""
    from foundationdb_tpu.core.types import Verdict

    ledger: list = []
    for r in c.resolvers:
        orig = r.cs.resolve

        def traced(txns, cv, oldest=None, _orig=orig):
            vs = _orig(txns, cv, oldest)
            for t, v in zip(txns, vs):
                if v == Verdict.COMMITTED:
                    for w in t.write_ranges:
                        if not w.empty:
                            ledger.append(
                                (bytes(w.begin), bytes(w.end), int(cv)))
            return vs

        r.cs.resolve = traced
    return ledger


def _contended_cluster(seed: int, n_txns: int = 80, n_clients: int = 10,
                       n_keys: int = 6, ledger: bool = False):
    from foundationdb_tpu.client.ryw import open_database
    from foundationdb_tpu.sim.cluster import SimCluster
    from foundationdb_tpu.sim.workloads import ZipfRepairWorkload, run_workload

    c = SimCluster(seed=seed, engine="oracle-replay", admission=True)
    db = open_database(c)
    led = _wrap_write_ledger(c) if ledger else None
    w = ZipfRepairWorkload(seed=seed, n_keys=n_keys, n_txns=n_txns,
                           n_clients=n_clients, repair=False)
    metrics = c.loop.run(run_workload(c, db, w), timeout=3000)
    return c, db, metrics, led


class TestPreabortOracleHonesty:
    @pytest.mark.parametrize("seed", [3, 11])
    def test_every_preabort_is_a_true_conflict_loser(self, seed):
        """The randomized oracle-parity honesty gate (ISSUE satellite):
        for EVERY pre-aborted txn, the confirming committed write the
        policy logged must (a) be strictly newer than the txn's read
        version and (b) actually exist in the resolve oracle's write
        history covering that key — i.e. submitting the txn could only
        have returned CONFLICT. A resolve-level ledger shadows the
        oracle's accepted writes un-GC'd, so the check is exhaustive for
        the whole run, not just the MVCC window."""
        c, _db, metrics, ledger = _contended_cluster(seed, ledger=True)
        pol = c.commit_proxies[0].admission
        assert pol.counters["preaborted"] > 0, "vacuous: nothing pre-aborted"
        # Evidence complete: every pre-abort logged its proof.
        assert pol.counters["preaborted"] == len(pol.preabort_log)
        assert ledger, "write ledger empty — engine changed under test?"
        for key, confirm_v, read_v in pol.preabort_log:
            assert confirm_v > read_v, (key, confirm_v, read_v)
            assert any(
                b <= key < e and v == confirm_v
                for (b, e, v) in ledger
            ), f"pre-abort evidence {key!r}@{confirm_v} not in oracle history"
        # And the stream itself stayed serializable + conserved
        # (run_workload's check raised otherwise).
        assert metrics.ops == 80

    def test_preaborted_txns_eventually_commit(self):
        """Pre-abort is pacing, not denial: the workload's conservation
        check (sum == committed increments) plus full completion proves
        every pre-aborted txn eventually committed its increment."""
        c, _db, metrics, _ = _contended_cluster(19, n_txns=60, n_clients=8)
        assert metrics.ops == 60
        pol = c.commit_proxies[0].admission
        assert pol.counters["preaborted"] > 0


class TestShapedLane:
    def test_shaping_fires_and_outcomes_accounted(self):
        c, db, _metrics, _ = _contended_cluster(5, n_txns=100, n_clients=12)
        pol = c.commit_proxies[0].admission
        assert pol.counters["probes"] > 0
        assert pol.counters["shaped"] > 0, "shaped lane never used"
        # Outcome accounting: every shaped txn's verdict landed somewhere
        # (committed = measured false positive, conflicted = true
        # positive) or was pre-aborted at its flush recheck.
        outcomes = (pol.counters["shaped_committed"]
                    + pol.counters["shaped_conflicted"])
        assert 0 < outcomes <= pol.counters["shaped"]
        # The shaped lane drained (quiesce contract).
        assert len(c.commit_proxies[0]._shaped) == 0

    def test_status_json_admission_section(self):
        from foundationdb_tpu.runtime.status import fetch_status

        c, _db, _metrics, _ = _contended_cluster(5, n_txns=40, n_clients=6)
        doc = c.loop.run(fetch_status(c), timeout=60)
        adm = doc["workload"]["admission"]
        assert adm["enabled"] is True
        assert adm["probes"] > 0
        assert adm["preaborted"] >= 0 and adm["shaped"] >= 0
        assert adm["system_shaped"] == 0
        assert adm["filter_recorded"] > 0  # resolver feed ran
        assert "saturation" in adm and "shaped_depth" in adm

    def test_admission_no_shape_option_fails_fast(self):
        """A latency-sensitive client opts out of the shaped lane and
        gets the retryable AdmissionShaped error instead of a queue
        position."""
        from foundationdb_tpu.client.ryw import open_database
        from foundationdb_tpu.sim.cluster import SimCluster

        c = SimCluster(seed=1, engine="oracle", admission=True)
        db = open_database(c)
        pol = c.commit_proxies[0].admission
        # Bloom-only evidence: shapes (no exact proof → never pre-aborts).
        pol.filter.record_u64(fingerprints([b"hot"]), 10**9)

        async def attempt():
            tr = db.transaction()
            tr.set_option("admission_no_shape")
            await tr.get(b"hot")
            tr.set(b"other", b"v")
            await tr.commit()

        with pytest.raises(AdmissionShaped):
            c.loop.run(attempt(), timeout=60)
        assert pol.counters["no_shape_rejects"] == 1
        assert AdmissionShaped("x").retryable

    def test_preabort_error_carries_payload_and_is_retryable(self):
        e = AdmissionPreAborted("x", hot_ranges=[(b"a", b"b", 3.5)],
                                confirm_version=42)
        assert e.retryable
        assert e.confirm_version == 42
        assert e.hot_ranges == [(b"a", b"b", 3.5)]


def _dev_fp(cs, key: bytes) -> np.ndarray:
    """The DEVICE tier's fingerprint of a raw key: pack through the
    engine's codec into int32 rows, re-encode as the mirror's u64
    columns, and apply the shared column mix — the same pipeline
    _note_write_fps feeds from (a distinct domain from the host tier's
    raw-byte fingerprints, by design: device filters never see bytes)."""
    from foundationdb_tpu.models.conflict_set import _rows_to_u64

    rows, _ends = cs.codec.pack_ranges([(key, key + b"\x00")])
    return u64_cols_fingerprint(_rows_to_u64(np.asarray(rows, np.int32)))


class TestResidentEngineIntegration:
    """The device-resident feed (TPUConflictSet.attach_admission_filter):
    accepted write fingerprints enter the filter from the resident pack's
    u64 columns, and dictionary EVICTION must not lose admission memory
    (the filter is fingerprint-keyed, not rank-keyed)."""

    def _txn(self, write_key: bytes, rv: int = 0, read_key: bytes = b"r"):
        from foundationdb_tpu.core.types import TxnConflictInfo

        return TxnConflictInfo(
            read_ranges=[single_key_range(read_key)],
            write_ranges=[single_key_range(write_key)],
            read_version=rv,
        )

    def test_feed_and_eviction_interaction(self):
        from foundationdb_tpu.models.conflict_set import TPUConflictSet

        # Short MVCC window: churned keys expire as versions advance, so
        # the tiny dictionary recycles by EVICTION/repack (the
        # interaction under test) instead of hard-overflowing on live
        # keys.
        cs = TPUConflictSet(capacity=1 << 10, batch_size=16,
                            dict_capacity=96, dict_delta_slots=16,
                            window_versions=40)
        f = RecentWritesFilter(bits_log2=12, banks=4,
                               window_versions=10_000, backend="jax")
        cs.attach_admission_filter(f)
        v = 100
        cs.resolve([self._txn(b"hotkey", rv=v - 1)], v)
        assert f.probe_u64(_dev_fp(cs, b"hotkey"), v - 1).tolist() == [True]
        recorded_before = f.recorded
        # Churn enough unique keys through the tiny dictionary to force
        # eviction/full repacks of the resident mirror (fresh read
        # versions: the short MVCC window expires stale snapshots)...
        for i in range(12):
            v += 10
            cs.resolve(
                [self._txn(b"churn/%04d/%d" % (i, j), rv=v - 1)
                 for j in range(8)], v
            )
        assert cs.dict_stats["evictions"] + cs.dict_stats["full_repacks"] > 0
        # ...the filter kept every recent write regardless (fp-keyed:
        # dictionary eviction must not lose admission memory).
        assert f.recorded > recorded_before
        assert f.probe_u64(_dev_fp(cs, b"churn/0011/0"), v - 1).tolist() == [True]

    def test_rejected_writes_not_fed(self):
        """Only ACCEPTED write sets feed the filter: a conflicted txn's
        write fingerprint must not poison admission."""
        from foundationdb_tpu.core.types import TxnConflictInfo
        from foundationdb_tpu.models.conflict_set import TPUConflictSet

        cs = TPUConflictSet(capacity=1 << 10, batch_size=16)
        f = RecentWritesFilter(bits_log2=12, banks=4,
                               window_versions=10_000)
        cs.attach_admission_filter(f)
        cs.resolve([self._txn(b"winner")], 100)
        # Loser: reads `winner` at rv 50 < 100 → CONFLICT; writes `loser`.
        loser = TxnConflictInfo(
            read_ranges=[single_key_range(b"winner")],
            write_ranges=[single_key_range(b"loser")],
            read_version=50,
        )
        from foundationdb_tpu.core.types import Verdict

        assert cs.resolve([loser], 200) == [Verdict.CONFLICT]
        assert f.probe_u64(_dev_fp(cs, b"loser"), 0).tolist() == [False]
        assert f.probe_u64(_dev_fp(cs, b"winner"), 0).tolist() == [True]


class _FakeSequencer:
    async def get_live_committed_version(self):
        return 42


class _SatRk:
    def __init__(self, sat, tps=1e6):
        self.sat = sat
        self.tps = tps

    async def get_rates(self, poller_id=None, grvs_served=None):
        return {"tps_limit": self.tps, "batch_tps_limit": self.tps,
                "admission_saturation": self.sat}


class TestGrvDeferral:
    def test_saturation_defers_default_not_system(self):
        loop = Loop(seed=0)
        proxy = GrvProxy(loop, _FakeSequencer(), _SatRk(0.9))

        async def main():
            loop.spawn(proxy.run(), name="grv")
            await loop.sleep(0.15)  # poller picked the saturation up
            for _ in range(40):
                await proxy.get_read_version("system")
            for _ in range(40):
                await proxy.get_read_version()
            return proxy.admission_defer_ticks

        ticks = loop.run(main(), timeout=60)
        # Default grants sat out intervals; everything still served.
        assert ticks > 0
        assert proxy.grvs_served == 80

    def test_deferral_halves_sustained_rate(self):
        """Deferred intervals skip token ACCRUAL, not just admission —
        otherwise the next interval double-spends the accumulated budget
        and long-run intake is unchanged (review find). Sustained drain
        of an empty bucket must take ~2x longer under saturation."""
        def drain_time(sat: float) -> float:
            loop = Loop(seed=0)
            # tps 5000 → 5 tokens per 1ms interval: the refill rate, not
            # the bucket, paces the drain.
            proxy = GrvProxy(loop, _FakeSequencer(), _SatRk(sat, tps=5000))
            proxy._tokens = proxy._batch_tokens = 0.0  # force refill pacing

            async def main():
                loop.spawn(proxy.run(), name="grv")
                await loop.sleep(0.15)  # poller picked the saturation up
                t0 = loop.now
                for _ in range(30):
                    await proxy.get_read_version()
                return loop.now - t0

            return loop.run(main(), timeout=60)

        fast = drain_time(0.2)
        slow = drain_time(0.9)
        assert slow > 1.5 * fast, (fast, slow)

    def test_no_deferral_below_threshold(self):
        loop = Loop(seed=0)
        proxy = GrvProxy(loop, _FakeSequencer(), _SatRk(0.2))

        async def main():
            loop.spawn(proxy.run(), name="grv")
            await loop.sleep(0.15)
            for _ in range(20):
                await proxy.get_read_version()
            return proxy.admission_defer_ticks

        assert loop.run(main(), timeout=60) == 0


class TestRatekeeperSignal:
    def test_admission_saturation_throttles(self):
        loop = Loop(seed=0)
        rk = Ratekeeper(loop, [])
        rk.worst_admission_saturation = 0.0
        assert rk._scale(1.0) == 1.0
        mid = (Ratekeeper.AS_SOFT + Ratekeeper.AS_HARD) / 2
        rk.worst_admission_saturation = mid
        s = rk._scale(1.0)
        assert 0.0 < s < 1.0
        assert rk.limiting_reason == "admission_filter"
        rk.worst_admission_saturation = 1.0
        assert rk._scale(1.0) == 0.0
