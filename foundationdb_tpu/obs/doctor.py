"""Incident doctor: deterministic root-cause reports from a flight ring.

``diagnose()`` ingests a flight-recorder ring (obs/recorder.py JSONL)
and answers "why did the SLO burn" without any hand-joining:

1. re-runs the SloTracker over the ring's snapshots (pure function of
   the ring — same ring, same report, byte for byte) to find anomaly
   incident windows;
2. for each incident, attributes the **dominant stage**: the commit-path
   stage whose share of end-to-end latency GREW most inside the window,
   computed from the snapshots' cumulative per-stage sums
   (obs.stage_sum_ms.*, diffed at the window edges against the
   pre-window baseline);
3. collects the **co-occurring annotations** (recovery stages, chaos
   fault/heal stamps, ratekeeper limiting transitions, resolver-queue
   crossings, admission engage/release, reshards, scrape gaps) inside
   the slack-padded window;
4. emits one machine-readable verdict per incident plus a one-line
   human summary ("goodput 3.1 vs baseline 77.2 tps in [11.0,16.0]s:
   dominant stage resolve_wait (12%→64%); co-occurring: recovery
   RecoveryCompleted@12.4 (salvage 1.4s), chaos_fault kill tlog0@11.2").

``attribute_faults()`` is the chaos cross-check: every injected fault
window (chaos_fault → matching chaos_heal annotation, grace-padded)
must contain an annotation of its EXPECTED class — a kill/partition/
pause that the cluster survived shows up as a recovery. ``run_doctor_
gate()`` runs the seeded mini-chaos script with the recorder armed and
gates exactly that, as one JSON line.

Surfaces: ``cli doctor RING.jsonl``, ``python -m foundationdb_tpu.obs
--doctor RING.jsonl`` and ``--doctor-gate``.
"""

from __future__ import annotations

import json

from foundationdb_tpu.obs.recorder import FlightRecorder
from foundationdb_tpu.obs.slo import SloTracker

#: chaos action -> annotation class its window MUST contain (the chaos
#: battery already gates that kills produce recoveries; the doctor's
#: job is attributing them to the right window on the timeline).
EXPECTED_FAULT_CLASS = {
    "kill": "recovery",
    "partition": "recovery",
    "pause": "recovery",
}

#: padding around windows when matching annotations: detection latency
#: plus scrape cadence mean an effect can land a few seconds after its
#: cause was stamped.
SLACK_S = 5.0


def split_ring(records: list[dict]) -> tuple[list, list, list]:
    """(snapshots, annotations, gaps) in ring order."""
    snaps = [r for r in records if r.get("kind") == "snapshot"]
    anns = [r for r in records if r.get("kind") == "annotation"]
    gaps = [r for r in records if r.get("kind") == "gap"]
    return snaps, anns, gaps


# -- dominant-stage attribution ------------------------------------------------


def _stage_sums(snap: dict) -> tuple[dict[str, float], float]:
    """({stage: cumulative sum_ms}, cumulative e2e sum_ms) of one
    snapshot's aggregated metrics. TXN_STAGES only: those partition the
    e2e time (the reconciliation identity), so their sums are shares of
    the same denominator — SUB_STAGES (device_dispatch, tlog_fsync,
    wave_*) nest INSIDE them and tick on their own batch-weighted
    sampling, so counting them here can "win" with a share far above
    100% and name a sub-stage as the dominant commit-path stage."""
    from foundationdb_tpu.obs.span import TXN_STAGES

    pref = "obs.stage_sum_ms."
    m = snap.get("metrics") or {}
    return ({k[len(pref):]: float(v) for k, v in m.items()
             if k.startswith(pref) and k[len(pref):] in TXN_STAGES},
            float(m.get("obs.e2e_sum_ms", 0.0)))


def _read_stage_sums(snap: dict) -> tuple[dict[str, float], float]:
    """({stage: cumulative sum_ms}, total read-plane sum_ms) of one
    snapshot. READ_STAGES live OUTSIDE the txn reconciliation identity
    (reads never enter the commit pipeline), so they get their own
    denominator: the total time the read plane itself burned. That keeps
    a read storm from being hidden by (or polluting) the commit-path
    shares above."""
    from foundationdb_tpu.obs.span import READ_STAGES

    pref = "obs.stage_sum_ms."
    m = snap.get("metrics") or {}
    sums = {k[len(pref):]: float(v) for k, v in m.items()
            if k.startswith(pref) and k[len(pref):] in READ_STAGES}
    return sums, sum(sums.values())


def _snap_at(snaps: list[dict], t: float, after: bool) -> "dict | None":
    """Last snapshot at/before t (after=False) or first at/after t."""
    if after:
        for s in snaps:
            if s["t"] >= t:
                return s
        return snaps[-1] if snaps else None
    prev = None
    for s in snaps:
        if s["t"] > t:
            break
        prev = s
    return prev if prev is not None else (snaps[0] if snaps else None)


def dominant_stage(snaps: list[dict], t0: float, t1: float) -> "dict | None":
    """The stage whose share of e2e GREW most inside [t0, t1] vs the
    pre-window baseline. None (an honesty signal, not a silent zero)
    when the window or baseline saw no attributed latency at all —
    e.g. tracing was not armed, or no sampled txn completed."""
    if not snaps:
        return None
    first = snaps[0]
    a = _snap_at(snaps, t0, after=False)
    b = _snap_at(snaps, t1, after=True)
    if a is None or b is None or b["t"] <= a["t"]:
        return None
    sums_a, e2e_a = _stage_sums(a)
    sums_b, e2e_b = _stage_sums(b)
    sums_f, e2e_f = _stage_sums(first)
    d_e2e = e2e_b - e2e_a
    base_e2e = e2e_a - e2e_f
    if d_e2e <= 0:
        return None

    def shares(sums_hi, sums_lo, denom):
        if denom <= 0:
            return {}
        return {s: max(0.0, sums_hi.get(s, 0.0) - sums_lo.get(s, 0.0))
                / denom for s in set(sums_hi) | set(sums_lo)}

    during = shares(sums_b, sums_a, d_e2e)
    before = shares(sums_a, sums_f, base_e2e)
    if not during:
        return None
    best = max(during, key=lambda s: during[s] - before.get(s, 0.0))
    return {
        "stage": best,
        "share_during": round(during[best], 4),
        "share_before": round(before.get(best, 0.0), 4),
        "share_growth": round(during[best] - before.get(best, 0.0), 4),
        "window_e2e_ms": round(d_e2e, 3),
        "baseline_windows": bool(base_e2e > 0),
    }


def dominant_read_stage(snaps: list[dict], t0: float, t1: float) -> "dict | None":
    """Read-plane twin of dominant_stage: the READ_STAGES member whose
    share of the read plane's own time GREW most inside [t0, t1]. None
    when the window saw no read-plane latency — either the read path
    ran unbatched (stages never tick) or nothing was read. A read storm
    shows up here (read_dispatch / watch_sweep dominating) even when the
    commit-path attribution above is quiet."""
    if not snaps:
        return None
    first = snaps[0]
    a = _snap_at(snaps, t0, after=False)
    b = _snap_at(snaps, t1, after=True)
    if a is None or b is None or b["t"] <= a["t"]:
        return None
    sums_a, tot_a = _read_stage_sums(a)
    sums_b, tot_b = _read_stage_sums(b)
    sums_f, tot_f = _read_stage_sums(first)
    d_tot = tot_b - tot_a
    base_tot = tot_a - tot_f
    if d_tot <= 0:
        return None

    def shares(sums_hi, sums_lo, denom):
        if denom <= 0:
            return {}
        return {s: max(0.0, sums_hi.get(s, 0.0) - sums_lo.get(s, 0.0))
                / denom for s in set(sums_hi) | set(sums_lo)}

    during = shares(sums_b, sums_a, d_tot)
    before = shares(sums_a, sums_f, base_tot)
    if not during:
        return None
    best = max(during, key=lambda s: during[s] - before.get(s, 0.0))
    return {
        "stage": best,
        "share_during": round(during[best], 4),
        "share_before": round(before.get(best, 0.0), 4),
        "share_growth": round(during[best] - before.get(best, 0.0), 4),
        "window_read_ms": round(d_tot, 3),
        "baseline_windows": bool(base_tot > 0),
    }


def misspec_storm(snaps: list[dict], t0: float, t1: float,
                  threshold: float = 0.5) -> "dict | None":
    """Mis-speculation storm detector (FDB_TPU_SPEC_RESOLVE): what share
    of the windows speculated inside [t0, t1] rolled back through the
    repair path, from the resolvers' cumulative ``spec_dispatched`` /
    ``spec_repaired`` counters in the ring snapshots. Returns None when
    nothing speculated in the window (serial engine, or the ratekeeper's
    depth clamp already shut speculation off) — an honesty signal, like
    dominant_stage's. ``storm`` trips at ``threshold``, matching the
    coalescer's MISSPEC_CLAMP: past it every other window re-resolves,
    so speculation is adding snapshot+repair work, not hiding latency."""
    if not snaps:
        return None
    a = _snap_at(snaps, t0, after=False)
    b = _snap_at(snaps, t1, after=True)
    if a is None or b is None or b["t"] <= a["t"]:
        return None

    def sums(snap: dict, leaf: str) -> float:
        m = snap.get("metrics") or {}
        return sum(float(v) for k, v in m.items()
                   if k.startswith("resolver.") and k.endswith("." + leaf))

    disp = sums(b, "spec_dispatched") - sums(a, "spec_dispatched")
    rep = sums(b, "spec_repaired") - sums(a, "spec_repaired")
    if disp <= 0:
        return None
    rate = max(0.0, rep) / disp
    return {
        "spec_dispatched": int(disp),
        "spec_repaired": int(rep),
        "misspec_rate": round(rate, 4),
        "storm": bool(rate >= threshold),
    }


def dict_thrash(snaps: list[dict], t0: float, t1: float,
                threshold: float = 0.5,
                min_events: int = 64) -> "dict | None":
    """Tiered-dictionary thrash detector (FDB_TPU_DICT_HOT_CAPACITY):
    inside [t0, t1], did promotions keep pace with demotions? A hot set
    that FITS the HBM tier demotes cold keys that stay cold (promotion
    rate ~ 0); promotion rate ≈ demotion rate means the engine keeps
    re-promoting what it just demoted — the hot working set exceeds the
    hot tier, and every round trip ships delta rows for keys that should
    have stayed resident. From the resolvers' cumulative
    ``engine.demotions`` / ``engine.promotions`` counters in the ring
    snapshots. Returns None when nothing demoted in the window (tiering
    off, or the tier is simply big enough) — the honesty signal, like
    misspec_storm's. ``thrash`` trips when both flows are material
    (>= min_events demotions) and the smaller flow is at least
    ``threshold`` of the larger."""
    if not snaps:
        return None
    a = _snap_at(snaps, t0, after=False)
    b = _snap_at(snaps, t1, after=True)
    if a is None or b is None or b["t"] <= a["t"]:
        return None

    def sums(snap: dict, leaf: str) -> float:
        m = snap.get("metrics") or {}
        return sum(float(v) for k, v in m.items()
                   if k.startswith("resolver.") and k.endswith("." + leaf))

    dem = sums(b, "demotions") - sums(a, "demotions")
    pro = sums(b, "promotions") - sums(a, "promotions")
    if dem <= 0:
        return None
    rate = max(0.0, pro) / dem
    return {
        "demotions": int(dem),
        "promotions": int(pro),
        "promotion_rate": round(rate, 4),
        "thrash": bool(dem >= min_events and min(dem, max(pro, 0.0))
                       >= threshold * max(dem, pro)),
    }


def scale_relief(records: list[dict], slack_s: float = SLACK_S,
                 grace_s: float = 60.0) -> "list | None":
    """Autoscale attribution (autoscale/): per scale event on the ring
    (`AutoscaleRecruit`/`AutoscaleRetire` annotations, cls="autoscale"),
    did the TRIGGERING signal clear after the fleet changed? The
    annotation carries the aggregated-scrape key it fired on (`metric`)
    and the policy's clear threshold (`clear_below`); relief is the
    first ring snapshot after the event where that key reads below the
    threshold (`above=True` events clear upward — a goodput floor).
    Returns None when the ring holds NO autoscale annotations — the
    autoscaler was unarmed, and claiming "no scale events needed relief"
    would be vacuously true (the honesty signal, like dominant_stage's).
    Scale-downs triggered by slack (no `clear_below`) attribute on the
    signal alone: there is no limiting signal left to clear."""
    snaps, anns, _gaps = split_ring(records)
    armed = [a for a in anns if a.get("cls") == "autoscale"]
    if not armed:
        return None
    # Relief confirmations ("AutoscaleRelief") prove the loop was armed
    # but are not scale events themselves — attributing them would be
    # vacuous double-counting.
    events = [a for a in armed
              if a.get("name") in ("AutoscaleRecruit", "AutoscaleRetire")]
    out = []
    for e in events:
        t0 = e["t"]
        metric, clear = e.get("metric"), e.get("clear_below")
        above = bool(e.get("clear_above", False))
        relieved_at = None
        if metric is not None and clear is not None:
            for s in snaps:
                if s["t"] <= t0 or s["t"] > t0 + grace_s:
                    continue
                v = (s.get("metrics") or {}).get(metric)
                if v is None:
                    continue
                if (float(v) > float(clear)) if above \
                        else (float(v) < float(clear)):
                    relieved_at = s["t"]
                    break
        needs_clear = metric is not None and clear is not None
        out.append({
            "name": e.get("name"),
            "role": e.get("role"),
            "signal": e.get("signal"),
            "from_n": e.get("from_n"),
            "to_n": e.get("to_n"),
            "t": t0,
            "metric": metric,
            "clear_below": clear,
            "relieved": (relieved_at is not None) if needs_clear else None,
            "relief_s": (round(relieved_at - t0, 3)
                         if relieved_at is not None else None),
            "attributed": bool(e.get("signal")) and (
                relieved_at is not None if needs_clear else True),
        })
    return out


# -- annotations in a window ---------------------------------------------------


def annotations_in(anns: list[dict], t0: float, t1: float,
                   slack_s: float = SLACK_S,
                   exclude_cls: tuple = ()) -> list[dict]:
    out = [a for a in anns
           if t0 - slack_s <= a["t"] <= t1 + slack_s
           and a.get("cls") not in exclude_cls]
    return sorted(out, key=lambda a: a["t"])


def _ann_brief(a: dict) -> str:
    extra = ""
    if a.get("name") == "RecoveryCompleted" and a.get("salvage_s") is not None:
        extra = f" (salvage {a['salvage_s']}s)"
    elif a.get("cls") == "chaos_fault":
        extra = f" {a.get('action', '')} {a.get('target', '')}".rstrip()
    elif a.get("name") == "RkLimitReasonChanged":
        extra = f" -> {a.get('reason')}"
    elif a.get("cls") == "resolver_queue":
        extra = f" depth_hw={a.get('depth_hw')}"
    return f"{a.get('cls')}:{a.get('name')}@{a['t']:.1f}{extra}"


# -- the report ----------------------------------------------------------------


def diagnose(records: list[dict], objectives: "dict | None" = None,
             slack_s: float = SLACK_S) -> dict:
    """Deterministic doctor report over one ring (see module docstring)."""
    snaps, anns, gaps = split_ring(records)
    tracker = SloTracker(objectives)
    for s in snaps:
        tracker.observe(s["t"], s.get("metrics") or {})
    incidents = []
    for inc in tracker.incidents:
        t0, t1 = inc["t0"], inc["t1"]
        co = annotations_in(anns, t0, t1, slack_s)
        co_gaps = [g for g in gaps if t0 - slack_s <= g["t"] <= t1 + slack_s]
        stage = dominant_stage(snaps, t0, t1)
        read_stage = dominant_read_stage(snaps, t0, t1)
        misspec = misspec_storm(snaps, t0, t1)
        thrash = dict_thrash(snaps, t0, t1)
        verdict = {
            "window": [t0, t1],
            "sli": inc["sli"],
            "observed": inc["observed"],
            "baseline_mean": inc["baseline_mean"],
            "windows": inc["windows"],
            "dominant_stage": stage,
            "dominant_read_stage": read_stage,
            "misspec": misspec,
            "dict_thrash": thrash,
            "annotations": co,
            "annotation_classes": sorted(
                {a.get("cls") for a in co}
                | ({"scrape_gap"} if co_gaps else set())),
            "scrape_gaps": len(co_gaps),
        }
        stage_txt = (
            f"dominant stage {stage['stage']} "
            f"({stage['share_before']:.0%}->{stage['share_during']:.0%})"
            if stage else "no stage attribution (tracing not armed or no "
                          "sampled txns in window)")
        if read_stage:
            stage_txt += (
                f"; read plane: {read_stage['stage']} "
                f"({read_stage['share_before']:.0%}->"
                f"{read_stage['share_during']:.0%})")
        if misspec and misspec["storm"]:
            stage_txt += (
                f"; mis-speculation storm ({misspec['misspec_rate']:.0%} of "
                f"{misspec['spec_dispatched']} speculated windows repaired)")
        if thrash and thrash["thrash"]:
            stage_txt += (
                f"; dictionary thrash ({thrash['promotions']} promotions vs "
                f"{thrash['demotions']} demotions — hot set exceeds the "
                f"HBM tier)")
        co_txt = ("; co-occurring: "
                  + ", ".join(_ann_brief(a) for a in co[:6])
                  if co else "; no co-occurring annotations")
        verdict["summary"] = (
            f"{inc['sli']} {inc['observed']} vs baseline "
            f"{inc['baseline_mean']} in [{t0:.1f},{t1:.1f}]s: "
            f"{stage_txt}{co_txt}")
        incidents.append(verdict)
    t_span = ([snaps[0]["t"], snaps[-1]["t"]] if snaps else None)
    return {
        "metric": "doctor_report",
        "ring": {
            "records": len(records),
            "snapshots": len(snaps),
            "annotations": len(anns),
            "scrape_gaps": len(gaps),
            "t_span": t_span,
        },
        "slo": tracker.status(),
        "incidents": incidents,
        "faults": attribute_faults(records, slack_s=slack_s),
        "scale_events": scale_relief(records, slack_s=slack_s),
    }


def attribute_faults(records: list[dict],
                     slack_s: float = SLACK_S,
                     grace_s: float = 20.0) -> list[dict]:
    """Per injected chaos fault: its window (fault stamp -> matching
    heal stamp for the same target, else +grace), the annotation classes
    found inside, and whether the EXPECTED class is among them."""
    _snaps, anns, _gaps = split_ring(records)
    faults = [a for a in anns if a.get("cls") == "chaos_fault"]
    heals = [a for a in anns if a.get("cls") == "chaos_heal"]
    out = []
    for f in faults:
        t0 = f["t"]
        heal = next((h for h in heals
                     if h.get("target") == f.get("target")
                     and h["t"] >= t0), None)
        t1 = heal["t"] if heal is not None else t0 + grace_s
        co = annotations_in(anns, t0, t1, slack_s,
                            exclude_cls=("chaos_fault", "chaos_heal"))
        classes = sorted({a.get("cls") for a in co})
        expected = EXPECTED_FAULT_CLASS.get(f.get("action"))
        out.append({
            "action": f.get("action"),
            "target": f.get("target"),
            "t": t0,
            "window": [t0, round(t1, 3)],
            "healed": heal is not None,
            "classes": classes,
            "expected_class": expected,
            "attributed": expected is None or expected in classes,
        })
    return out


# -- the CI gate ---------------------------------------------------------------


def run_doctor_gate(seed: int = 20260804, rate: float = 60.0,
                    workdir: "str | None" = None) -> dict:
    """The doctor gate: seeded mini-chaos (loadgen/chaos.py
    --fast equivalent) with the flight recorder armed, then the doctor
    over the resulting ring — one JSON line gating EXACTLY:

    - the chaos battery itself passed (its own zero-loss/exactly-once
      gates — a doctor verdict about a broken run proves nothing);
    - every injected fault window is attributed to its expected
      annotation class;
    - the ring audit: snapshots present, every documented recorder_*/
      slo_* counter in the scrape, chaos fault/heal annotations ringed.
    """
    import os
    import tempfile

    from foundationdb_tpu.loadgen.chaos import run_chaos
    from foundationdb_tpu.obs.registry import RECORDER_DOCUMENTED_COUNTERS

    workdir = workdir or tempfile.mkdtemp(prefix="doctor_")
    ring_path = os.path.join(workdir, "flight_ring.jsonl")
    chaos_rec = run_chaos(seed=seed, fast=True, rate=rate, workdir=workdir,
                          recorder_path=ring_path)
    records = FlightRecorder.load(ring_path)
    report = diagnose(records)
    problems: list[str] = []
    if not chaos_rec.get("ok"):
        problems.append(
            f"chaos battery failed: {chaos_rec.get('problems')[:3]}")
    faults = report["faults"]
    if not faults:
        problems.append("no chaos_fault annotations reached the ring")
    unattributed = [f"{f['action']} {f['target']}@{f['t']:.1f}"
                    for f in faults if not f["attributed"]]
    if unattributed:
        problems.append(f"fault windows unattributed: {unattributed}")
    if report["ring"]["snapshots"] < 5:
        problems.append(
            f"only {report['ring']['snapshots']} snapshots ringed")
    snaps, _anns, _gaps = split_ring(records)
    last_metrics = (snaps[-1].get("metrics") or {}) if snaps else {}
    missing = [c for c in RECORDER_DOCUMENTED_COUNTERS
               if c not in last_metrics]
    if missing:
        problems.append(f"documented recorder counters missing: {missing}")
    slo = report["slo"]
    if not slo.get("windows"):
        problems.append("slo tracker evaluated zero windows")
    return {
        "metric": "doctor_gate",
        "ok": not problems,
        "problems": problems[:10],
        "seed": seed,
        "ring_path": ring_path,
        "chaos_ok": bool(chaos_rec.get("ok")),
        "snapshots": report["ring"]["snapshots"],
        "annotations": report["ring"]["annotations"],
        "faults": [{k: f[k] for k in ("action", "target", "expected_class",
                                      "classes", "attributed")}
                   for f in faults],
        "incidents": len(report["incidents"]),
        "slo_windows": slo.get("windows"),
        "slo_warmed_up": slo.get("warmed_up"),
        "replay": f"python -m foundationdb_tpu.obs --doctor-gate "
                  f"--seed {seed}",
    }


def main_doctor(ring_path: str, objectives: "dict | None" = None) -> dict:
    """`--doctor RING` / `cli doctor RING`: report over an existing ring."""
    records = FlightRecorder.load(ring_path)
    if not records:
        return {"metric": "doctor_report", "error":
                f"no records loaded from {ring_path!r}"}
    return diagnose(records, objectives)


if __name__ == "__main__":  # pragma: no cover - debugging convenience
    import sys

    print(json.dumps(main_doctor(sys.argv[1]), indent=1, sort_keys=True))
