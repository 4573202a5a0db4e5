"""Autoscale A/B + selfcheck: sim-twin closed-loop scaling, gated exactly.

``run_autoscale_ab`` produces the committed ``AUTOSCALE_AB.json`` record
(scripts/autoscale_ab.sh): the SAME
seed and open-loop "dur:rate" schedule driven against two arms —

- **autoscale**: the closed-loop controller armed (controller.py), the
  hysteresis policy recruiting/retiring resolvers and proxies live
  through scale-via-recovery, every decision annotated on the flight
  ring;
- **fixed**: the identical cluster with the fleet frozen at the seed
  topology.

plus an **oscillating** run (autoscaler armed, load period sitting
INSIDE the policy cooldown) proving the hysteresis gates: the scale-
event count must stay within the computed bound — an oscillation-
follower would produce one event per period.

Gates (chaos style — exact, never liveness-only):

- zero acked-commit loss across every recruit/retire transition, and
  exactly-once unknown-result resolution (the chaos ledger's counter +
  marker identity, read back at one snapshot after quiesce);
- per scale event: time-to-relief with the staged detect/recruit/relief
  breakdown recorded;
- every scale event attributed by the doctor (``scale_relief``) to its
  triggering signal class from ring snapshots alone;
- the oscillating run within the hysteresis bound.

Honesty flags ride the record: ``valid`` (all gates), ``cpu_fallback``
(this is the CPU sim twin — no device claim), ``p99_quotable``. The
throughput *ratio* between arms is reported but NOT gated: sim virtual
time on a single-core host says nothing about multi-core scaling (the
OPENLOOP_AB precedent — see ROADMAP).
"""

from __future__ import annotations

import os
import tempfile

from foundationdb_tpu.autoscale.controller import Autoscaler, arm as arm_autoscaler
from foundationdb_tpu.autoscale.policy import AutoscalePolicy
from foundationdb_tpu.core.errors import (
    CommitUnknownResult,
    FdbError,
    NotCommitted,
    ProcessKilled,
)
from foundationdb_tpu.loadgen.arrivals import parse_profile, trace_schedule
from foundationdb_tpu.loadgen.chaos import (
    OP_TIMEOUT_S,
    AckedLedger,
    _bounded,
    _OpTimeout,
)

#: per-arrival total retry budget (sim seconds) before abandonment.
TXN_BUDGET_S = 20.0

#: resolver dispatch knobs that make queue depth (and the ratekeeper's
#: resolver_queue backpressure) observable in virtual time — the bench
#: OVERLOAD_SPEC values (loadgen/bench.py).
OVERLOAD_KNOBS = {"resolver_budget_s": 0.05,
                  "resolver_dispatch_cost_s": 0.05}


def _spread(k: int) -> bytes:
    """One raw leading byte spreading keys across the WHOLE keyspace so
    resolver/storage shard maps see balanced ranges (every printable
    prefix would pile onto the first shard of a uniform split)."""
    return bytes([(k * 83) % 250])


def _ctr_key(i: int, n_ctrs: int) -> bytes:
    return _spread(i * 97) + b"ctr/%02d" % i


# -- the exactly-once ledger workload (shared with tests/test_autoscale) ------


async def ledger_txn(loop, db, ledger: AckedLedger, lat: list, k: int,
                     n_ctrs: int, t_sched: float,
                     budget_s: float = TXN_BUDGET_S) -> None:
    """One arrival: atomically increment a counter + write a per-arrival
    marker + unique key (the chaos exactly-once oracle), with the chaos
    retry discipline — known non-commits retry, unknown outcomes stop
    and are resolved at read-back. Latency is CO-correct: measured from
    the SCHEDULED arrival, not the (possibly backlogged) spawn."""
    ctr_key = _ctr_key(k % n_ctrs, n_ctrs)
    marker = _spread(k) + b"m/%06d" % k
    ukey = _spread(k + 1) + b"u/%06d" % k
    val = b"v%06d" % k
    deadline = loop.now + budget_s
    backoff = 0.02
    while True:
        tr = db.transaction()
        commit_in_flight = False
        try:
            cur = await _bounded(loop, tr.get(ctr_key), OP_TIMEOUT_S,
                                 f"autoscale.get{k}")
            tr.set(ctr_key, b"%d" % (int(cur or b"0") + 1))
            tr.set(marker, b"1")
            tr.set(ukey, val)
            commit_in_flight = True
            await _bounded(loop, tr.commit(), OP_TIMEOUT_S,
                           f"autoscale.commit{k}")
            ledger.ack(ukey, val, marker)
            lat.append(loop.now - t_sched)
            return
        except _OpTimeout:
            # A recruit/retire recovery can drop an in-flight promise on
            # the floor (the chaos find): a hung COMMIT is may-be-
            # committed; a hung read provably committed nothing — retry.
            ledger.op_timeouts += 1
            if commit_in_flight:
                ledger.note_unknown(ukey, val, marker)
                return
        except CommitUnknownResult:
            ledger.note_unknown(ukey, val, marker)
            return
        except NotCommitted:
            ledger.conflict_retries += 1
        except FdbError as e:
            if not e.retryable:
                ledger.nonretryable.append(f"{type(e).__name__}: {e}")
                return
            if isinstance(e, ProcessKilled):
                try:  # re-discover the new generation's proxies
                    await db.refresh_client_info()
                except Exception:
                    pass
        if loop.now > deadline:
            ledger.abandoned += 1
            return
        backoff = min(0.5, backoff * 1.6)
        await loop.sleep(backoff * (0.5 + loop.rng.random()))


async def drive_ledger(loop, db, ledger: AckedLedger, schedule, lat: list,
                       n_ctrs: int = 32, max_inflight: int = 1024,
                       drain_s: float = 10.0) -> None:
    """Open-loop driver over an arrivals schedule (loadgen/arrivals.py):
    arrivals are offered on time regardless of completions; past
    max_inflight they are shed (counted, never silently dropped). The
    accounting identity is asserted at the end."""
    t0 = loop.now
    live: set = set()
    for k, off in enumerate(schedule):
        dt = t0 + float(off) - loop.now
        if dt > 0:
            await loop.sleep(dt)
        ledger.offered += 1
        if len(live) >= max_inflight:
            ledger.shed += 1
            continue
        task = loop.spawn(
            ledger_txn(loop, db, ledger, lat, k, n_ctrs, t0 + float(off)),
            name=f"autoscale.txn{k}")
        live.add(task)
        task.add_done_callback(lambda f, t=task: live.discard(t))
    deadline = loop.now + drain_s
    while live and loop.now < deadline:
        await loop.sleep(0.1)
    leftovers = list(live)
    for task in leftovers:
        task.cancel()
    settle = loop.now + 5.0
    while any(not t.done() for t in leftovers) and loop.now < settle:
        await loop.sleep(0.05)
    ledger.abandoned += sum(1 for t in leftovers if t.is_error())
    assert (len(ledger.acked) + len(ledger.unknown) + ledger.shed
            + ledger.abandoned + len(ledger.nonretryable)
            == ledger.offered), "autoscale ledger accounting broke"


async def verify_ledger(loop, db, ledger: AckedLedger) -> dict:
    """Read everything back at ONE snapshot and compute the exactly-once
    identity (chaos semantics): every acked key present, sum(counters)
    == markers present, every unknown resolved committed XOR absent."""
    deadline = loop.now + 60.0
    while True:
        tr = db.transaction()
        try:
            rows = await tr.get_range(b"\x00", b"\xfb", snapshot=True)
            break
        except FdbError as e:
            if loop.now > deadline:
                raise
            if isinstance(e, ProcessKilled):
                try:  # endpoints may be a generation stale post-scale
                    await db.refresh_client_info()
                except Exception:
                    pass
            await loop.sleep(0.5)
    got = dict(rows)
    lost = sorted(k.hex() for k, v in ledger.acked.items()
                  if got.get(k) != v)
    unknown_committed = sum(
        1 for k, v in ledger.unknown.items() if got.get(k) == v)
    unknown_absent = sum(1 for k in ledger.unknown if k not in got)
    unknown_mangled = (len(ledger.unknown) - unknown_committed
                       - unknown_absent)
    markers_present = sum(1 for k in got if k[1:].startswith(b"m/"))
    ctr_sum = sum(int(v) for k, v in got.items()
                  if k[1:].startswith(b"ctr/"))
    acked_marker_missing = [m.hex() for m in ledger.acked_markers
                            if m not in got]
    return {
        "offered": ledger.offered,
        "acked": len(ledger.acked),
        "unknown": len(ledger.unknown),
        "unknown_committed": unknown_committed,
        "unknown_absent": unknown_absent,
        "unknown_mangled": unknown_mangled,
        "shed": ledger.shed,
        "abandoned": ledger.abandoned,
        "conflict_retries": ledger.conflict_retries,
        "acked_lost_count": len(lost),
        "acked_lost": lost[:10],
        "counter_sum": ctr_sum,
        "markers_present": markers_present,
        "acked_marker_missing": acked_marker_missing[:10],
        "exactly_once_ok": (ctr_sum == markers_present
                            and not acked_marker_missing
                            and unknown_mangled == 0),
        "zero_acked_loss": not lost,
        "nonretryable_errors": ledger.nonretryable[:10],
    }


def _p99_ms(lat: list) -> "float | None":
    if not lat:
        return None
    s = sorted(lat)
    return round(s[min(len(s) - 1, int(0.99 * len(s)))] * 1000.0, 3)


# -- one arm ------------------------------------------------------------------


def run_arm(seed: int, profile: str, *, autoscale: bool, workdir: str,
            name: str, policy_kw: "dict | None" = None,
            n_proxies: int = 1, n_resolvers: int = 1,
            n_ctrs: int = 32, drain_s: float = 10.0,
            settle_s: float = 6.0) -> dict:
    """One seeded sim run of the schedule against one arm. Returns the
    arm record: ledger verification, goodput/p99, the applied scale
    events with staged timings, and the doctor's ring-side attribution
    of every event (autoscale arms)."""
    from foundationdb_tpu.client.ryw import open_database
    from foundationdb_tpu.obs.doctor import scale_relief
    from foundationdb_tpu.obs.recorder import FlightRecorder
    from foundationdb_tpu.obs.registry import (
        AUTOSCALE_DOCUMENTED_COUNTERS,
        scrape_sim,
    )
    from foundationdb_tpu.sim.cluster import SimCluster

    ring = os.path.join(workdir, f"ring_{name}.jsonl")
    if os.path.exists(ring):
        os.unlink(ring)
    c = SimCluster(seed=seed, n_proxies=n_proxies, n_resolvers=n_resolvers,
                   n_tlogs=2, n_storages=2, ratekeeper=True,
                   recorder_path=ring, recorder_interval_s=1.0,
                   **OVERLOAD_KNOBS)
    db = open_database(c)
    scaler: "Autoscaler | None" = None
    if autoscale:
        scaler = arm_autoscaler(c, policy=AutoscalePolicy(**(policy_kw or {})))
    ledger = AckedLedger()
    lat: list[float] = []
    segments = parse_profile(profile)
    schedule = trace_schedule(segments, seed=seed)
    duration = sum(d for d, _r in segments)

    async def main() -> dict:
        await drive_ledger(c.loop, db, ledger, schedule, lat,
                           n_ctrs=n_ctrs, drain_s=drain_s)
        ctrl = c.controller
        deadline = c.loop.now + 60.0
        while ctrl._recovering and c.loop.now < deadline:
            await c.loop.sleep(0.2)
        # Post-drain settle: the autoscaler's relief watcher needs a few
        # calm scrapes to stamp relief on the last event.
        await c.loop.sleep(settle_s)
        out = await verify_ledger(c.loop, db, ledger)
        reg = await scrape_sim(c)
        extra = AUTOSCALE_DOCUMENTED_COUNTERS if autoscale else ()
        out["scrape"] = {
            "audit_problems": reg.audit()[:10],
            "missing_documented": reg.missing_documented(extra=extra),
        }
        out["final_epoch"] = ctrl.generation.epoch
        return out

    verify = c.loop.run(main(), timeout=900)
    wall = duration + drain_s + settle_s
    rec = {
        "name": name,
        "autoscale": autoscale,
        "profile": profile,
        "duration_s": duration,
        "fleet_initial": {"proxy": n_proxies, "resolver": n_resolvers},
        "fleet_final": {"proxy": c.n_proxies, "resolver": c.n_resolvers},
        "goodput_tps": round(len(ledger.acked) / wall, 2),
        "p99_ms": _p99_ms(lat),
        "p99_quotable": len(lat) >= 20,
        "ledger": verify,
        "ring_path": ring,
    }
    if scaler is not None:
        rec["scale_events"] = scaler.events
        rec["counters"] = scaler.metrics()
        records = FlightRecorder.load(ring)
        attributed = scale_relief(records)
        rec["doctor_scale_events"] = attributed
        rec["events_attributed"] = (
            attributed is not None
            and len(attributed) == len(scaler.events)
            and all(a["attributed"] for a in attributed))
    if c.flight_recorder is not None:
        c.flight_recorder.close()
    return rec


def hysteresis_bound(policy_kw: dict, duration_s: float,
                     poll_s: float = Autoscaler.POLL_S) -> int:
    """Worst-case scale-event count the hysteresis gates permit over
    ``duration_s``: one initial adaptation per direction, plus one full
    up+down cycle per cooldown+confirmation period — an oscillation-
    follower (one event per load period) sits far above this."""
    p = AutoscalePolicy(**policy_kw)
    cycle_s = (p.cooldown_up_s + p.cooldown_down_s
               + p.confirm_down * poll_s)
    return 1 + 2 * int(duration_s // cycle_s)


# -- the record ---------------------------------------------------------------


def run_autoscale_ab(seed: int = 20260807, fast: bool = False,
                     workdir: "str | None" = None) -> dict:
    workdir = workdir or tempfile.mkdtemp(prefix="autoscale_ab_")
    # Base sits under the single-resolver dispatch capacity at the
    # OVERLOAD_KNOBS; the crowd saturates it (windowed occupancy ~1.0)
    # and piles on conflict-retry amplification, which is where a fixed
    # fleet degrades in this sim — its adaptive batching absorbs raw
    # throughput elastically, so overload shows up as TAIL LATENCY, not
    # lost admission. The fast profile uses a gentler crowd that still
    # trips the scale-up signal (selfcheck-sized).
    crowd = 28.0 if fast else 80.0
    base = 8.0
    flash = (f"4:{base:g},8:{crowd:g},10:{base:g}" if fast
             else f"6:{base:g},12:{crowd:g},16:{base:g}")
    osc_period_on, osc_period_off = 2.0, 2.0
    osc_reps = 6 if fast else 8
    osc = ",".join(f"{osc_period_on:g}:{crowd:g},{osc_period_off:g}:{base:g}"
                   for _ in range(osc_reps))
    osc_duration = osc_reps * (osc_period_on + osc_period_off)
    policy_kw = {"max_fleet": {"proxy": 3, "resolver": 3}}

    arms = {
        "autoscale": run_arm(seed, flash, autoscale=True, workdir=workdir,
                             name="autoscale", policy_kw=policy_kw),
        "fixed": run_arm(seed, flash, autoscale=False, workdir=workdir,
                         name="fixed"),
    }
    oscillation_arm = run_arm(seed + 1, osc, autoscale=True,
                              workdir=workdir, name="oscillation",
                              policy_kw=policy_kw)
    # The bound covers the WHOLE observed window — the oscillating
    # schedule plus the drain/settle tail the autoscaler keeps running
    # through (a tail scale-down is still a scale event).
    bound = hysteresis_bound(policy_kw, osc_duration + 10.0 + 6.0)
    osc_events = len(oscillation_arm.get("scale_events") or [])
    auto = arms["autoscale"]
    events = auto.get("scale_events") or []

    gates = {
        "zero_acked_loss": all(
            a["ledger"]["zero_acked_loss"]
            for a in (*arms.values(), oscillation_arm)),
        "exactly_once": all(
            a["ledger"]["exactly_once_ok"]
            for a in (*arms.values(), oscillation_arm)),
        "scaled_up": any(e["direction"] == "up" and e["recruited"]
                         for e in events),
        "relief_recorded": bool(events) and all(
            e["time_to_relief"] is not None for e in events),
        "events_attributed": bool(auto.get("events_attributed"))
        and (osc_events == 0 or oscillation_arm.get("events_attributed")),
        "hysteresis_within_bound": osc_events <= bound,
        "scrape_clean": all(
            not a["ledger"]["scrape"]["audit_problems"]
            and not a["ledger"]["scrape"]["missing_documented"]
            for a in (*arms.values(), oscillation_arm)),
    }
    cores = (len(os.sched_getaffinity(0))
             if hasattr(os, "sched_getaffinity") else (os.cpu_count() or 1))
    return {
        "metric": "autoscale_ab",
        "seed": seed,
        "fast": fast,
        "schedule": {"flash_crowd": flash, "oscillating": osc,
                     "oscillation_period_s": osc_period_on + osc_period_off},
        "arms": arms,
        "oscillation": {
            "arm": oscillation_arm,
            "events_total": osc_events,
            "bound": bound,
            "within_bound": osc_events <= bound,
        },
        "scale_events": events,
        "gates": gates,
        "valid": all(gates.values()),
        "cpu_fallback": True,  # CPU sim twin: no device claim anywhere
        "p99_quotable": all(a["p99_quotable"] for a in arms.values()),
        "goodput_ratio": (
            round(auto["goodput_tps"] / arms["fixed"]["goodput_tps"], 3)
            if arms["fixed"]["goodput_tps"] else None),
        "p99_ratio": (
            round(auto["p99_ms"] / arms["fixed"]["p99_ms"], 3)
            if auto["p99_ms"] and arms["fixed"]["p99_ms"] else None),
        "single_core_caveat": (
            "goodput_ratio is reported, not gated: sim virtual time on "
            f"{cores} host cores says nothing about multi-core scaling "
            "(OPENLOOP_AB precedent; ROADMAP follow-up)"),
        "host": {"cores": cores},
        "workdir": workdir,
        "replay": ("env JAX_PLATFORMS=cpu python -m foundationdb_tpu."
                   f"autoscale --ab --seed {seed}"
                   + (" --fast" if fast else "")),
    }


def selfcheck(seed: int = 20260807) -> dict:
    """One-JSON-line selfcheck: a fast flash-crowd run
    with the autoscaler armed must scale up, lose nothing, resolve every
    unknown exactly once, and have every event doctor-attributed."""
    workdir = tempfile.mkdtemp(prefix="autoscale_self_")
    a = run_arm(seed, "3:8,8:28,6:8", autoscale=True, workdir=workdir,
                name="selfcheck",
                policy_kw={"max_fleet": {"proxy": 3, "resolver": 3}})
    events = a.get("scale_events") or []
    problems: list[str] = []
    if not any(e["direction"] == "up" and e["recruited"] for e in events):
        problems.append("no scale-up recruited under the flash crowd")
    if not a["ledger"]["zero_acked_loss"]:
        problems.append(
            f"acked-commit loss: {a['ledger']['acked_lost_count']}")
    if not a["ledger"]["exactly_once_ok"]:
        problems.append("exactly-once identity violated")
    if events and not a.get("events_attributed"):
        problems.append("doctor could not attribute every scale event")
    if a["ledger"]["scrape"]["missing_documented"]:
        problems.append(
            f"documented counters missing: "
            f"{a['ledger']['scrape']['missing_documented']}")
    if a["ledger"]["scrape"]["audit_problems"]:
        problems.append(
            f"scrape audit: {a['ledger']['scrape']['audit_problems']}")
    return {
        "metric": "autoscale_selfcheck",
        "ok": not problems,
        "problems": problems[:10],
        "seed": seed,
        "events": [{k: e[k] for k in ("name", "role", "from_n", "to_n",
                                      "signal", "detect_s", "recruit_s",
                                      "relief_s", "time_to_relief",
                                      "relieved")}
                   for e in events],
        "fleet_final": a["fleet_final"],
        "acked": a["ledger"]["acked"],
        "unknown": a["ledger"]["unknown"],
        "replay": ("env JAX_PLATFORMS=cpu python -m foundationdb_tpu."
                   f"autoscale --seed {seed}"),
    }
