"""Flight recorder + SLO tracker + incident doctor (ISSUE 15).

Covers the obs timeline plane end to end:

- the bounded on-disk ring (append, compaction bound, torn-tail load),
- derived annotations from the pure counter plane (ratekeeper limiting
  transitions, resolver-queue crossings, admission engage/release,
  reshard deltas, completed recoveries) plus listener suppression,
- scrape_gap records: a dead role under an ACTIVE poller is an explicit
  (role, reason, duration) record on the timeline, never a hole — the
  regression kills a sim role mid-poll,
- the SloTracker: warm-up honesty, interval-p99 quotability, incident
  merge (contiguous anomalous windows), burn accounting, and the
  baseline-poisoning guard,
- the doctor: deterministic reports over a synthetic ring (dominant
  stage, co-occurring annotations, per-fault attribution),
- status JSON ``workload.slo`` honesty flags, sim-cluster arming.
"""

import json

import pytest

from foundationdb_tpu.obs.recorder import (
    ANNOTATION_CLASSES,
    TRACE_CATALOG,
    FlightRecorder,
)
from foundationdb_tpu.obs.registry import (
    RECORDER_DOCUMENTED_COUNTERS,
    MetricsPoller,
    MetricsRegistry,
    scrape_sim,
)
from foundationdb_tpu.obs.slo import SloTracker, p99_from_bins


class FakeLoop:
    """now + attribute bag: enough for the recorder's non-async surface."""

    def __init__(self):
        self.now = 0.0


def mk_recorder(tmp_path, **kw) -> tuple[FakeLoop, FlightRecorder]:
    loop = FakeLoop()
    rec = FlightRecorder(loop, scrape=None,
                         path=str(tmp_path / "ring.jsonl"), **kw)
    return loop, rec


def reg_of(*adds) -> MetricsRegistry:
    reg = MetricsRegistry()
    for role, inst, metrics in adds:
        reg.add(role, inst, metrics)
    return reg


# ---------------------------------------------------------------------------
# the ring
# ---------------------------------------------------------------------------


class TestRing:
    def test_append_snapshot_and_annotation_records(self, tmp_path):
        loop, rec = mk_recorder(tmp_path)
        rec.observe_registry(reg_of(
            ("commit_proxy", "cp0", {"txns_committed": 10})))
        loop.now = 1.0
        rec.annotate("ChaosKill", cls="chaos_fault", action="kill",
                     target="tlog0")
        rec.observe_registry(reg_of(
            ("commit_proxy", "cp0", {"txns_committed": 30})))
        ring = FlightRecorder.load(rec.path)
        kinds = [r["kind"] for r in ring]
        assert kinds == ["snapshot", "annotation", "snapshot"]
        snap = ring[0]
        assert snap["seq"] == 0 and snap["t"] == 0.0
        assert snap["metrics"]["commit_proxy.txns_committed"] == 10
        # Recorder/slo self-metrics ride every snapshot (the documented
        # counter plane — the doctor gate audits these names).
        for name in RECORDER_DOCUMENTED_COUNTERS:
            assert name in snap["metrics"], name
        ann = ring[1]
        assert ann["cls"] == "chaos_fault" and ann["target"] == "tlog0"
        assert ann["cls"] in ANNOTATION_CLASSES
        assert ring[2]["seq"] == 1

    def test_compaction_bounds_the_file(self, tmp_path):
        loop, rec = mk_recorder(tmp_path, max_records=16)
        for i in range(200):
            loop.now = float(i)
            rec.annotate(f"E{i}", cls="load_phase", i=i)
            with open(rec.path, encoding="utf-8") as f:
                assert sum(1 for _ in f) < 2 * 16
        assert rec.counters["recorder_compactions"] > 0
        ring = FlightRecorder.load(rec.path)
        assert len(ring) <= 2 * 16 - 1
        # The tail survives compaction in order.
        assert ring[-1]["name"] == "E199"

    def test_rearm_over_existing_ring_keeps_history(self, tmp_path):
        """A recorder restarted over its own ring file (controller
        crash/restart — the exact incident it must survive) seeds the
        in-memory ring from the file tail, so the FIRST post-restart
        compaction cannot wipe the pre-restart history the retention
        bound still permits."""
        loop, rec = mk_recorder(tmp_path, max_records=16)
        for i in range(20):
            loop.now = float(i)
            rec.annotate(f"Old{i}", cls="load_phase")
        rec.close()
        loop2, rec2 = mk_recorder(tmp_path, max_records=16)
        assert len(rec2.ring) == 16  # seeded from the file tail
        # 12 appends push the 20-line file to the 2x32 compaction point;
        # the retention bound (16) at that instant still covers the last
        # 4 pre-restart records — they must survive the rewrite.
        for i in range(12):
            loop2.now = 100.0 + i
            rec2.annotate(f"New{i}", cls="load_phase")
        assert rec2.counters["recorder_compactions"] > 0
        names = [r["name"] for r in FlightRecorder.load(rec2.path)]
        assert names == [f"Old{i}" for i in range(16, 20)] + \
            [f"New{i}" for i in range(12)]

    def test_load_drops_torn_final_line(self, tmp_path):
        loop, rec = mk_recorder(tmp_path)
        rec.annotate("A", cls="load_phase")
        with open(rec.path, "a", encoding="utf-8") as f:
            f.write('{"kind": "annotation", "tru')  # writer died mid-append
        ring = FlightRecorder.load(rec.path)
        assert len(ring) == 1 and ring[0]["name"] == "A"
        assert FlightRecorder.load(str(tmp_path / "missing.jsonl")) == []


# ---------------------------------------------------------------------------
# derived annotations (the remote/pure-counter plane)
# ---------------------------------------------------------------------------


def anns_of(rec) -> list[dict]:
    return [r for r in FlightRecorder.load(rec.path)
            if r["kind"] == "annotation"]


class TestDerivedAnnotations:
    def test_ratekeeper_limit_transition(self, tmp_path):
        from foundationdb_tpu.runtime.ratekeeper import LIMIT_REASONS

        loop, rec = mk_recorder(tmp_path)
        rk = {"limiting_reason_code": 0, "limit_transitions": 0}
        rec.observe_registry(reg_of(("ratekeeper", "", dict(rk))))
        loop.now = 5.0
        rk = {"limiting_reason_code": LIMIT_REASONS.index("resolver_queue"),
              "limit_transitions": 1}
        rec.observe_registry(reg_of(("ratekeeper", "", dict(rk))))
        anns = anns_of(rec)
        assert len(anns) == 1
        a = anns[0]
        assert a["cls"] == "ratekeeper_limit"
        assert a["reason"] == "resolver_queue" and a["previous"] == "none"
        assert a["severity"] == "warn"
        # Engage AND release between two polls: endpoints identical, the
        # transition counter alone carries the flap through the plane.
        loop.now = 10.0
        rec.observe_registry(reg_of(("ratekeeper", "", {
            "limiting_reason_code": LIMIT_REASONS.index("resolver_queue"),
            "limit_transitions": 3})))
        assert len(anns_of(rec)) == 2
        assert anns_of(rec)[-1]["transitions"] == 2

    def test_resolver_queue_crossings(self, tmp_path):
        from foundationdb_tpu.runtime.ratekeeper import Ratekeeper

        loop, rec = mk_recorder(tmp_path)
        rec.observe_registry(reg_of(
            ("resolver", "resolver0", {"queue_depth_hw": 0})))
        loop.now = 5.0
        rec.observe_registry(reg_of(
            ("resolver", "resolver0",
             {"queue_depth_hw": Ratekeeper.RQ_HARD + 1})))
        loop.now = 10.0
        rec.observe_registry(reg_of(
            ("resolver", "resolver0", {"queue_depth_hw": 0})))
        names = [a["name"] for a in anns_of(rec)]
        assert names == ["ResolverQueueHard", "ResolverQueueRecovered"]
        assert anns_of(rec)[0]["cls"] == "resolver_queue"

    def test_admission_and_reshard_and_recovery_deltas(self, tmp_path):
        loop, rec = mk_recorder(tmp_path)
        base = {
            "commit_proxy": ("cp0", {"admission": {"engage_events": 0,
                                                   "release_events": 0}}),
            "resolver": ("resolver0", {"engine": {
                "auto_reshards": 0, "reshard_moved_shards": 0,
                "full_repacks": 0, "evictions": 0}}),
            "controller": ("", {"recovery_count": 0}),
        }
        rec.observe_registry(reg_of(
            *[(r, i, m) for r, (i, m) in base.items()]))
        loop.now = 5.0
        rec.observe_registry(reg_of(
            ("commit_proxy", "cp0", {"admission": {"engage_events": 1,
                                                   "release_events": 1}}),
            ("resolver", "resolver0", {"engine": {
                "auto_reshards": 2, "reshard_moved_shards": 6,
                "full_repacks": 0, "evictions": 0}}),
            ("controller", "", {"recovery_count": 1,
                                "recovery_total_s": 1.5}),
        ))
        by_cls = {a["cls"]: a for a in anns_of(rec)}
        assert set(by_cls) == {"admission_filter", "reshard", "recovery"}
        assert by_cls["reshard"]["reshards"] == 2
        assert by_cls["reshard"]["moved_shards"] == 6
        assert by_cls["recovery"]["recoveries"] == 1
        # Both engage and release happened in the interval — engage is
        # ringed first; the release annotation follows.
        rel = [a for a in anns_of(rec)
               if a["name"] == "AdmissionFilterReleased"]
        assert len(rel) == 1

    def test_listener_suppresses_derived_double_annotation(self, tmp_path):
        loop, rec = mk_recorder(tmp_path)
        rec.observe_registry(reg_of(("controller", "", {
            "recovery_count": 0})))
        # A loop-local trace listener already annotated this recovery
        # with its exact emit time...
        loop.now = 4.0
        rec._on_trace({"Type": "MasterRecoveryTriggered", "Time": 4.0,
                       "Severity": 30, "Process": "master"})
        loop.now = 5.0
        rec.observe_registry(reg_of(("controller", "", {
            "recovery_count": 1})))
        recovery_anns = [a for a in anns_of(rec) if a["cls"] == "recovery"]
        # ...so the counter-delta plane must NOT ring a second one.
        assert len(recovery_anns) == 1
        assert recovery_anns[0]["name"] == "MasterRecoveryTriggered"
        assert "MasterRecoveryTriggered" in TRACE_CATALOG


# ---------------------------------------------------------------------------
# scrape gaps (satellite: dead roles are records, not holes)
# ---------------------------------------------------------------------------


class TestScrapeGaps:
    def test_gap_duration_measured_from_last_answer(self, tmp_path):
        loop, rec = mk_recorder(tmp_path)
        ok = reg_of(("storage", "storage0", {"reads": 1}))
        rec.observe_registry(ok)
        loop.now = 7.0
        bad = MetricsRegistry()
        bad.note_gap("storage", "storage0", "ProcessKilled")
        rec.observe_registry(bad)
        gaps = [r for r in FlightRecorder.load(rec.path)
                if r["kind"] == "gap"]
        assert len(gaps) == 1
        g = gaps[0]
        assert (g["role"], g["instance"]) == ("storage", "storage0")
        assert g["reason"] == "ProcessKilled"
        assert g["duration_s"] == pytest.approx(7.0)
        assert rec.counters["recorder_scrape_gaps"] == 1

    def test_poller_emits_gap_when_role_killed_mid_run(self, tmp_path):
        """THE regression: kill a sim role under an ACTIVE MetricsPoller
        — the JSONL series must carry explicit scrape_gap records for
        the dead role (previously the probe failure was swallowed and
        the role silently vanished from the snapshots)."""
        from foundationdb_tpu.sim.cluster import SimCluster

        c = SimCluster(seed=3, n_storages=2, engine="oracle")
        path = str(tmp_path / "metrics.jsonl")
        victim = c.storage_eps[0].process
        poller = MetricsPoller(c.loop, lambda: scrape_sim(c), path,
                               interval_s=0.05)

        async def main():
            task = c.loop.spawn(poller.run(), name="poller.run")
            await c.loop.sleep(0.12)  # clean snapshots first
            c.loop.kill_process(victim)
            # A probe of a dead sim process fails only after the network's
            # FAILURE_DETECTION_DELAY (1.0 virtual seconds) — give the
            # poller several post-kill rounds of that.
            await c.loop.sleep(4.0)
            task.cancel()

        c.loop.run(main(), timeout=600)
        lines = [json.loads(ln) for ln in
                 open(path, encoding="utf-8").read().splitlines()]
        gaps = [r for r in lines if r.get("metric") == "scrape_gap"]
        snaps = [r for r in lines if r.get("metric") == "obs_scrape"]
        assert poller.snapshots_written == len(snaps) >= 4
        assert gaps, "killed role produced no scrape_gap records"
        assert {g["role"] for g in gaps} == {"storage"}
        assert all(g["instance"] == victim for g in gaps)
        assert all(g["reason"] for g in gaps)
        # One gap per affected probe per snapshot while the outage lasts,
        # with the outage duration growing monotonically.
        durs = [g["duration_s"] for g in gaps]
        assert durs == sorted(durs) and durs[-1] > durs[0]
        # The OTHER storage kept answering: present in post-kill snapshots.
        last = snaps[-1]["metrics"]
        assert "storage.reads" in last or any(
            k.startswith("storage.") for k in last)


# ---------------------------------------------------------------------------
# SLO tracker
# ---------------------------------------------------------------------------


def goodput_agg(committed: int, extra: "dict | None" = None) -> dict:
    agg = {"commit_proxy.txns_committed": committed}
    if extra:
        agg.update(extra)
    return agg


class TestSloTracker:
    def test_no_anomaly_before_warmup(self):
        tr = SloTracker()
        t, committed = 0.0, 0
        opened = []
        for i in range(SloTracker.WARMUP_WINDOWS):
            # Wildly swinging goodput — but no baseline exists yet, so
            # claiming an anomaly would be dishonest.
            committed += 1000 if i % 2 else 1
            t += 1.0
            opened += tr.observe(t, goodput_agg(committed))
        assert opened == []
        assert tr.counters["slo_incidents"] == 0
        assert not tr.status()["warmed_up"] or opened == []

    def test_goodput_drop_opens_and_merges_one_incident(self):
        tr = SloTracker()
        t, committed = 0.0, 0
        for _ in range(10):  # steady 100 tps baseline
            committed += 100
            t += 1.0
            assert tr.observe(t, goodput_agg(committed)) == []
        assert tr.warmed_up
        baseline_len = len(tr._baseline["goodput_tps"])
        opened = []
        for _ in range(4):  # incident: 3 tps
            committed += 3
            t += 1.0
            opened += tr.observe(t, goodput_agg(committed))
        # ONE incident opened, contiguous windows merged into it.
        assert len(opened) == 1 and opened[0]["sli"] == "goodput_tps"
        assert tr.counters["slo_incidents"] == 1
        assert tr.incidents[-1]["windows"] == 4
        # Baseline-poisoning guard: anomalous windows never feed it.
        assert len(tr._baseline["goodput_tps"]) == baseline_len
        # Recovery closes the incident; a LATER drop opens a NEW one.
        for _ in range(3):
            committed += 100
            t += 1.0
            tr.observe(t, goodput_agg(committed))
        assert tr.status()["open_incidents"] == []
        committed += 3
        t += 1.0
        assert len(tr.observe(t, goodput_agg(committed))) == 1
        assert tr.counters["slo_incidents"] == 2

    def test_p99_quotability_honesty(self):
        tr = SloTracker()
        # 10 samples < MIN_P99_SAMPLES: the window must refuse to quote.
        t = 1.0
        tr.observe(t, goodput_agg(0, {"obs.e2e_bins.b10": 0}))
        t = 2.0
        tr.observe(t, goodput_agg(10, {"obs.e2e_bins.b10": 10}))
        win = tr.windows[-1]
        assert win["e2e_samples"] == 10
        assert win["p99_quotable"] is False and win["commit_p99_ms"] is None
        assert tr.counters["slo_insufficient_windows"] == 1
        # Enough samples: quotable, conservative upper-edge value.
        t = 3.0
        tr.observe(t, goodput_agg(60, {"obs.e2e_bins.b10": 60}))
        win = tr.windows[-1]
        assert win["p99_quotable"] is True
        assert win["commit_p99_ms"] == p99_from_bins({10: 50})

    def test_burn_accounting_and_status_doc(self):
        tr = SloTracker({"commit_p99_ms": 0.001})  # impossible objective
        t, committed = 0.0, 0
        for _ in range(6):
            committed += 50
            t += 1.0
            tr.observe(t, goodput_agg(
                committed, {"obs.e2e_bins.b20": committed}))
        st = tr.status()
        burn = st["burn"]["commit_p99_ms"]
        assert burn["violating"] == burn["windows"] >= 5
        assert burn["burn_rate"] > 1.0
        assert tr.counters["slo_burn_violations"] >= 5
        for honesty in ("warmed_up", "insufficient_p99_windows",
                        "objectives", "incidents"):
            assert honesty in st

    def test_unknown_frac_objective(self):
        tr = SloTracker()
        # Pre-warm-up violations never open an incident ("no anomaly
        # before WARMUP_WINDOWS" holds for EVERY SLI, absolute bound or
        # not)...
        tr.observe(1.0, goodput_agg(0, {"client.commit_unknowns": 0,
                                        "client.commits_acked": 0}))
        opened = tr.observe(2.0, goodput_agg(
            100, {"client.commit_unknowns": 10,
                  "client.commits_acked": 90}))
        assert tr.windows[-1]["unknown_frac"] == pytest.approx(0.1)
        assert opened == []
        # ...after warm-up the absolute bound fires without any
        # baseline-relative judgement.
        t, unknowns, acked = 2.0, 10, 90
        for _ in range(SloTracker.WARMUP_WINDOWS):
            t += 1.0
            acked += 100
            tr.observe(t, goodput_agg(
                int(acked * 1.1), {"client.commit_unknowns": unknowns,
                                   "client.commits_acked": acked}))
        assert tr.warmed_up
        t += 1.0
        unknowns += 10
        acked += 90
        opened = tr.observe(t, goodput_agg(
            int(acked * 1.1), {"client.commit_unknowns": unknowns,
                               "client.commits_acked": acked}))
        assert [o["sli"] for o in opened] == ["unknown_frac"]
        # Below the outcome floor the SLI is unquotable — honest None,
        # no anomaly, no burn: 1 unknown among 3 outcomes is noise.
        t += 1.0
        opened = tr.observe(t, goodput_agg(
            int(acked * 1.1) + 110,  # goodput stays normal — the SLI
            {"client.commit_unknowns": unknowns + 1,  # under test is
             "client.commits_acked": acked + 2}))     # unknown_frac
        win = tr.windows[-1]
        assert win["client_outcomes"] == 3
        assert win["unknown_frac"] is None
        assert opened == []
        # No client counters at all -> honest None, not a fake zero.
        tr2 = SloTracker()
        tr2.observe(1.0, goodput_agg(0))
        tr2.observe(2.0, goodput_agg(10))
        assert tr2.windows[-1]["unknown_frac"] is None

    def test_metrics_names_are_the_documented_set(self):
        assert {f"slo.{k}" for k in SloTracker().metrics()} == {
            c for c in RECORDER_DOCUMENTED_COUNTERS if c.startswith("slo.")}


# ---------------------------------------------------------------------------
# the doctor
# ---------------------------------------------------------------------------


def synth_ring(fault_t: float = 10.2, heal_t: float = 19.5,
               with_recovery: bool = True) -> list[dict]:
    """30s of 1Hz snapshots: 100 tps goodput, except 3 tps in [10, 20)
    while resolve_wait's share of e2e latency jumps from ~45% to ~90%.
    A chaos kill/heal pair brackets the incident; a recovery lands
    inside it."""
    records: list[dict] = []
    committed, rw, td, e2e = 0, 0.0, 0.0, 0.0
    for t in range(31):
        incident = 10 <= t < 20
        committed += 3 if incident else 100
        rw += 50.0 if incident else 5.0
        td += 5.0
        e2e += (50.0 if incident else 5.0) + 5.0 + 1.0
        records.append({"kind": "snapshot", "t": float(t), "seq": t,
                        "metrics": {
                            "commit_proxy.txns_committed": committed,
                            "obs.stage_sum_ms.resolve_wait": round(rw, 3),
                            "obs.stage_sum_ms.tlog_durable": round(td, 3),
                            "obs.e2e_sum_ms": round(e2e, 3),
                        }})
    records.append({"kind": "annotation", "t": fault_t, "name": "ChaosKill",
                    "cls": "chaos_fault", "severity": "warn",
                    "action": "kill", "target": "tlog0"})
    if with_recovery:
        records.append({"kind": "annotation", "t": 12.4,
                        "name": "RecoveryCompleted", "cls": "recovery",
                        "severity": "warn", "salvage_s": 1.4})
    records.append({"kind": "annotation", "t": heal_t, "name": "ChaosHeal",
                    "cls": "chaos_heal", "severity": "info",
                    "action": "restart", "target": "tlog0"})
    return sorted(records, key=lambda r: r["t"])


class TestDoctor:
    def test_diagnose_attributes_stage_and_annotations(self):
        from foundationdb_tpu.obs.doctor import diagnose

        report = diagnose(synth_ring())
        assert report["incidents"], "goodput collapse not detected"
        inc = report["incidents"][0]
        assert inc["sli"] == "goodput_tps"
        assert 9.0 <= inc["window"][0] <= 11.0
        stage = inc["dominant_stage"]
        assert stage["stage"] == "resolve_wait"
        assert stage["share_during"] > stage["share_before"]
        assert {"chaos_fault", "recovery"} <= set(
            inc["annotation_classes"])
        # The one-line verdict names the stage and the co-occurrences.
        assert "resolve_wait" in inc["summary"]
        assert "chaos_fault" in inc["summary"]
        assert "salvage 1.4s" in inc["summary"]

    def test_diagnose_is_deterministic(self):
        from foundationdb_tpu.obs.doctor import diagnose

        ring = synth_ring()
        assert json.dumps(diagnose(ring), sort_keys=True) == \
            json.dumps(diagnose(ring), sort_keys=True)

    def test_sub_stages_never_win_dominant_stage(self):
        """SUB_STAGES (device_dispatch, tlog_fsync, wave_*) nest inside
        TXN_STAGES and tick on batch-weighted sampling — counting them
        as share-of-e2e candidates lets them 'win' with shares above
        100% and name a sub-stage as the dominant commit-path stage."""
        from foundationdb_tpu.obs.doctor import diagnose, dominant_stage

        ring = synth_ring()
        for r in ring:
            if r["kind"] == "snapshot":
                # A sub-stage whose weighted sum grows 10x faster than
                # any commit-path stage.
                r["metrics"]["obs.stage_sum_ms.device_dispatch"] = \
                    10.0 * r["metrics"]["obs.stage_sum_ms.resolve_wait"]
        snaps = [r for r in ring if r["kind"] == "snapshot"]
        stage = dominant_stage(snaps, 10.0, 20.0)
        assert stage["stage"] == "resolve_wait"
        assert stage["share_during"] <= 1.0
        inc = diagnose(ring)["incidents"][0]
        assert inc["dominant_stage"]["stage"] == "resolve_wait"

    def test_missing_stage_attribution_is_explicit(self):
        from foundationdb_tpu.obs.doctor import diagnose

        ring = [{**r, "metrics": {
            k: v for k, v in r["metrics"].items()
            if not k.startswith("obs.")}}
            if r["kind"] == "snapshot" else r for r in synth_ring()]
        inc = diagnose(ring)["incidents"][0]
        assert inc["dominant_stage"] is None  # honesty, not a fake stage
        assert "no stage attribution" in inc["summary"]

    def test_attribute_faults_expected_class(self):
        from foundationdb_tpu.obs.doctor import attribute_faults

        faults = attribute_faults(synth_ring())
        assert len(faults) == 1
        f = faults[0]
        assert (f["action"], f["target"]) == ("kill", "tlog0")
        assert f["healed"] is True
        assert f["expected_class"] == "recovery"
        assert f["attributed"] is True
        # No recovery inside the window -> attribution honestly fails.
        bad = attribute_faults(synth_ring(with_recovery=False))
        assert bad[0]["attributed"] is False

    def test_unhealed_fault_uses_grace_window(self):
        from foundationdb_tpu.obs.doctor import attribute_faults

        ring = [r for r in synth_ring() if r.get("cls") != "chaos_heal"]
        f = attribute_faults(ring, grace_s=20.0)[0]
        assert f["healed"] is False
        assert f["window"][1] == pytest.approx(f["t"] + 20.0)
        assert f["attributed"] is True  # recovery@12.4 inside the grace


# ---------------------------------------------------------------------------
# arming: sim cluster + status JSON
# ---------------------------------------------------------------------------


class TestArming:
    def test_sim_cluster_rings_snapshots_and_status_slo(self, tmp_path):
        from foundationdb_tpu.obs.selfcheck import _drive
        from foundationdb_tpu.runtime.status import fetch_status
        from foundationdb_tpu.sim.cluster import SimCluster

        ring = str(tmp_path / "ring.jsonl")
        c = SimCluster(seed=5, n_storages=2, engine="oracle", obs=True,
                       obs_sample_every=4, recorder_path=ring,
                       recorder_interval_s=0.05)
        _drive(c, 96)
        records = FlightRecorder.load(ring)
        snaps = [r for r in records if r["kind"] == "snapshot"]
        assert len(snaps) >= 2
        agg = snaps[-1]["metrics"]
        # The ratekeeper's numeric reason twin reaches the ring.
        assert "ratekeeper.limiting_reason_code" in agg
        assert "ratekeeper.limit_transitions" in agg
        # Stage sums + e2e bins ride the snapshots (the doctor's food).
        assert any(k.startswith("obs.stage_sum_ms.") for k in agg)
        assert any(k.startswith("obs.e2e_bins.") for k in agg)
        st = c.loop.run(fetch_status(c), timeout=600)
        slo = st["workload"]["slo"]
        assert slo["enabled"] is True
        for honesty in ("warmed_up", "insufficient_p99_windows", "burn",
                        "objectives"):
            assert honesty in slo
        assert slo["windows"] >= 1
        c.flight_recorder.close()
        assert getattr(c.loop, "flight_recorder", None) is None

    def test_status_slo_disabled_without_recorder(self):
        from foundationdb_tpu.runtime.status import fetch_status
        from foundationdb_tpu.sim.cluster import SimCluster

        c = SimCluster(seed=5, n_storages=2, engine="oracle")
        st = c.loop.run(fetch_status(c), timeout=600)
        assert st["workload"]["slo"] == {"enabled": False}
