"""Deployed-cluster chaos: real-process crash/restart/partition injection
with acked-durability and recovery-time gates (ISSUE 14 tentpole).

Everything the sim's nemesis catalog does to virtual processes, done to
REAL OS processes over REAL TCP: a seeded fault script drives the
SocketCluster supervisor (loadgen/deploy.py) — SIGKILL a tlog mid-fsync,
kill the resolver with batches in flight, kill a commit proxy under its
clients, kill the sequencer to force a real epoch bump over sockets,
black-hole a role's connections through its interposing relay
(runtime/net.TcpRelay) and heal on schedule — while a live open-loop
workload commits against the cluster the whole time.

Verification is EXACT, never liveness-only:

- **Acked-commit ledger.** The workload client records key → value for
  every commit it got an ACK for; commits whose outcome it cannot know
  (CommitUnknownResult, or a commit RPC still in flight when its bound
  expired) are tracked separately as may-be-committed. After heal +
  quiesce the harness reads everything back at one snapshot: an acked
  key missing or mismatched is ACKED-COMMIT LOSS (hard failure); every
  may-be-committed entry must resolve to exactly-committed or cleanly
  absent.
- **Exactly-once oracle.** Every transaction atomically increments one
  of a small set of counters AND writes a per-arrival marker key in the
  same transaction, so `sum(counters) == #markers-present` holds iff no
  transaction committed twice or half; every ACKED transaction's marker
  must be present.
- **Consistency check.** The cluster-wide byte-parity audit
  (consistency/run_deployed_check) must come back green post-heal.
- **MTTR breakdown.** Each injected fault is wall-stamped; the deployed
  controller's recovery log (server.py: detection → lock → salvage →
  accepting-commits stage durations) is matched against those stamps,
  yielding per-fault detection latency + per-stage recovery time, plus
  the client-observed blackout (first post-fault commit ack).

`python -m foundationdb_tpu.loadgen.chaos [--fast] [--seed N]` prints the
one-JSON-line CHAOS record (scripts/chaos_run.sh → CHAOS.json; --fast
runs one kill-restart cycle per role class). The
seed reproduces the fault schedule and workload shape exactly; real-world
interleaving is of course not deterministic — which is the point.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

from dataclasses import dataclass

from foundationdb_tpu.core.errors import (
    CommitUnknownResult,
    FdbError,
    NotCommitted,
    ProcessKilled,
)
from foundationdb_tpu.loadgen.deploy import SocketCluster

#: bound on any single client operation (read/commit await): a commit
#: still in flight past this is classified may-be-committed — a
#: black-holed proxy never delivers the BrokenPromise a dead one would.
OP_TIMEOUT_S = 10.0
#: per-arrival total retry budget before the arrival is abandoned.
TXN_BUDGET_S = 45.0


class _OpTimeout(Exception):
    """A bounded client operation outran OP_TIMEOUT_S (hung link)."""


async def _bounded(loop, coro, timeout_s: float, name: str):
    """Await `coro` for at most `timeout_s` (server.bounded_rpc is the
    one deadline-race implementation; the abandoned task keeps running —
    its eventual result is discarded; for a commit that is exactly
    'outcome unknown', which the caller records as such)."""
    from foundationdb_tpu.server import bounded_rpc

    try:
        return await bounded_rpc(loop, loop.spawn(coro, name=name),
                                 timeout_s)
    except TimeoutError as e:
        raise _OpTimeout(name) from e


# -- fault script -------------------------------------------------------------


@dataclass
class ChaosEvent:
    at_s: float  # offset from workload start
    action: str  # kill | restart | pause | resume | partition | heal
    target: str  # role process name, e.g. "tlog0"
    mode: str = "drop"  # partition mode (drop | cut | delay)
    stamp: "float | None" = None  # wall clock when executed
    error: "str | None" = None


def default_script(fast: bool = False) -> "tuple[list[ChaosEvent], float]":
    """(events, workload duration). The core battery — one SIGKILL +
    restart cycle per role CLASS (tlog, resolver, commit proxy,
    sequencer), each under live load; the full script adds a
    partition-then-heal through the tlog relay and a SIGSTOP/SIGCONT
    freeze of a proxy (alive-but-silent: the probe-timeout case)."""
    ev = [
        ChaosEvent(2.0, "kill", "tlog0"),        # mid-fsync under load
        ChaosEvent(5.0, "restart", "tlog0"),     # from_disk -> tlog_adopt
        ChaosEvent(9.0, "kill", "resolver0"),    # in-flight batches die
        ChaosEvent(11.5, "restart", "resolver0"),
        ChaosEvent(15.5, "kill", "proxy0"),      # clients lose their proxy
        ChaosEvent(18.0, "restart", "proxy0"),
        ChaosEvent(22.0, "kill", "sequencer0"),  # real epoch bump
        ChaosEvent(24.5, "restart", "sequencer0"),
    ]
    duration = 30.0
    if not fast:
        ev += [
            ChaosEvent(30.0, "partition", "tlog1", mode="drop"),
            ChaosEvent(35.0, "heal", "tlog1"),
            ChaosEvent(38.0, "pause", "proxy1"),
            ChaosEvent(42.0, "resume", "proxy1"),
        ]
        duration = 48.0
    return ev, duration


# -- acked-commit ledger ------------------------------------------------------


class AckedLedger:
    """What the client KNOWS: values it holds commit acks for, values
    whose commit outcome it could not learn, and the exact accounting of
    every arrival — offered == acked + unknown + shed + abandoned +
    nonretryable, asserted at the end of the open-loop writer."""

    def __init__(self) -> None:
        self.acked: dict[bytes, bytes] = {}  # unique key -> acked value
        self.acked_markers: list[bytes] = []
        self.unknown: dict[bytes, bytes] = {}  # may-be-committed
        self.unknown_markers: list[bytes] = []
        self.ack_walls: list[float] = []
        self.offered = 0
        self.shed = 0
        self.abandoned = 0  # retry budget exhausted (known non-commits only)
        self.conflict_retries = 0
        self.op_timeouts = 0
        self.nonretryable: list[str] = []

    def ack(self, ukey: bytes, val: bytes, marker: bytes) -> None:
        self.acked[ukey] = val
        self.acked_markers.append(marker)
        self.ack_walls.append(time.time())

    def note_unknown(self, ukey: bytes, val: bytes, marker: bytes) -> None:
        self.unknown[ukey] = val
        self.unknown_markers.append(marker)

    def first_ack_after(self, wall: float) -> "float | None":
        later = [w for w in self.ack_walls if w >= wall]
        return (min(later) - wall) if later else None


# -- the chaos run ------------------------------------------------------------


def _log(msg: str) -> None:
    print(f"[chaos] {msg}", file=sys.stderr, flush=True)


async def _one_txn(loop, db, ledger: AckedLedger, pref: bytes, k: int,
                   n_ctrs: int) -> None:
    ctr_key = pref + b"ctr/%02d" % (k % n_ctrs)
    marker = pref + b"m/%06d" % k
    ukey = pref + b"u/%06d" % k
    val = b"v%06d" % k
    deadline = loop.now + TXN_BUDGET_S
    backoff = 0.02
    while True:
        tr = db.transaction()
        commit_in_flight = False
        try:
            cur = await _bounded(loop, tr.get(ctr_key), OP_TIMEOUT_S,
                                 f"chaos.get{k}")
            tr.set(ctr_key, b"%d" % (int(cur or b"0") + 1))
            tr.set(marker, b"1")
            tr.set(ukey, val)
            commit_in_flight = True
            await _bounded(loop, tr.commit(), OP_TIMEOUT_S, f"chaos.commit{k}")
            ledger.ack(ukey, val, marker)
            return
        except _OpTimeout:
            ledger.op_timeouts += 1
            if commit_in_flight:
                # The commit RPC was launched and never answered in
                # bound: the batch may be durable — may-be-committed.
                ledger.note_unknown(ukey, val, marker)
                return
            # A read/GRV hung: provably nothing was committed — retry.
        except CommitUnknownResult:
            ledger.note_unknown(ukey, val, marker)
            return
        except NotCommitted:
            ledger.conflict_retries += 1  # known non-commit: safe retry
        except FdbError as e:
            if not e.retryable:
                # The reconnect-hardening gate (ISSUE 14 satellite): a
                # connection death must NEVER surface non-retryably.
                ledger.nonretryable.append(
                    f"{type(e).__name__}({e.code}): {e}")
                return
            if isinstance(e, ProcessKilled):
                try:  # re-discover live proxies (ClientDBInfo path)
                    await db.refresh_client_info()
                except Exception:
                    pass
        if loop.now > deadline:
            ledger.abandoned += 1
            return
        backoff = min(0.5, backoff * 1.6)
        await loop.sleep(backoff * (0.5 + loop.rng.random()))


async def _open_loop_writer(loop, db, ledger: AckedLedger, pref: bytes,
                            schedule, n_ctrs: int, max_inflight: int,
                            drain_s: float) -> None:
    t0 = loop.now
    live: set = set()  # in-flight txn tasks (len == concurrency in use)
    for k, off in enumerate(schedule):
        dt = t0 + float(off) - loop.now
        if dt > 0:
            await loop.sleep(dt)
        ledger.offered += 1
        if len(live) >= max_inflight:
            ledger.shed += 1
            continue
        task = loop.spawn(_one_txn(loop, db, ledger, pref, k, n_ctrs),
                          name=f"chaos.txn{k}")
        live.add(task)
        task.add_done_callback(lambda f, t=task: live.discard(t))
    deadline = loop.now + drain_s
    while live and loop.now < deadline:
        await loop.sleep(0.1)
    # Residue at the drain deadline is CANCELLED, not left running: a
    # straggler acking after the read-back snapshot would make its own
    # (correct) commit read as acked-commit loss. A cancelled in-flight
    # commit may still land server-side — it is simply ungated (the
    # exactly-once identity is computed purely from read-back state and
    # holds either way). A task whose completion was ALREADY queued when
    # the cancel landed still runs to completion and records its own
    # outcome (cancel() is a no-op on a done task) — so abandoned counts
    # only the tasks that actually died cancelled, judged after the
    # unwind settles, never by the snapshot alone.
    leftovers = list(live)
    for task in leftovers:
        task.cancel()
    settle = loop.now + 5.0
    while any(not t.done() for t in leftovers) and loop.now < settle:
        await loop.sleep(0.05)
    ledger.abandoned += sum(1 for t in leftovers if t.is_error())
    assert (len(ledger.acked) + len(ledger.unknown) + ledger.shed
            + ledger.abandoned + len(ledger.nonretryable)
            == ledger.offered), "chaos ledger accounting broke"


async def _run_events(loop, cluster: SocketCluster, events, t0: float,
                      counters: dict) -> None:
    # Flight recorder (obs subsystem), when this run armed one: every
    # injected fault / scripted repair is stamped as a first-class
    # annotation on the SAME timeline the metric snapshots ride — the
    # doctor's fault-window attribution keys off exactly these.
    recorder = getattr(loop, "flight_recorder", None)
    for ev in events:
        dt = t0 + ev.at_s - loop.now
        if dt > 0:
            await loop.sleep(dt)
        try:
            if ev.action == "kill":
                ev.stamp = cluster.kill_role(ev.target)
                counters["chaos_kills"] += 1
            elif ev.action == "restart":
                ev.stamp = time.time()
                cluster.restart_role(ev.target, wait=False)
                counters["chaos_restarts"] += 1
                ready_deadline = loop.now + 20.0
                while (not cluster.role_ready(ev.target)
                       and loop.now < ready_deadline):
                    await loop.sleep(0.1)
            elif ev.action == "pause":
                ev.stamp = cluster.pause_role(ev.target)
                counters["chaos_pauses"] += 1
            elif ev.action == "resume":
                ev.stamp = time.time()
                cluster.resume_role(ev.target)
            elif ev.action == "partition":
                ev.stamp = cluster.partition_role(ev.target, ev.mode)
                counters["chaos_partitions"] += 1
            elif ev.action == "heal":
                ev.stamp = time.time()
                cluster.heal_role(ev.target)
                counters["chaos_heals"] += 1
            else:
                raise ValueError(f"unknown chaos action {ev.action!r}")
            if ev.action in ("kill", "pause", "partition"):
                # Faults only: restart/resume/heal are the REPAIRS —
                # counting them would double the published fault count.
                counters["chaos_faults_injected"] += 1
            if recorder is not None:
                recorder.annotate(
                    f"Chaos{ev.action.capitalize()}",
                    cls=("chaos_fault"
                         if ev.action in ("kill", "pause", "partition")
                         else "chaos_heal"),
                    severity=("warn"
                              if ev.action in ("kill", "pause", "partition")
                              else "info"),
                    action=ev.action, target=ev.target,
                    at_s=ev.at_s, wall=ev.stamp)
            _log(f"t+{ev.at_s:.1f}s {ev.action} {ev.target}")
        except Exception as e:  # noqa: BLE001 — record, keep the script going
            ev.error = f"{type(e).__name__}: {e}"
            _log(f"t+{ev.at_s:.1f}s {ev.action} {ev.target} FAILED: {ev.error}")


async def _controller_stable(loop, ctrl, spec: dict, timeout_s: float) -> dict:
    """Wait until the controller reports a full, quiet generation for a
    few consecutive probes; returns the final status."""
    expect = {r: list(range(len(spec[r])))
              for r in ("tlog", "resolver", "proxy")}
    stable, st = 0, {}
    deadline = loop.now + timeout_s
    while stable < 3:
        if loop.now > deadline:
            raise TimeoutError(
                f"cluster never quiesced: last status {st}")
        try:
            st = await _bounded(loop, ctrl.get_status(), 5.0, "chaos.status")
            ok = (not st.get("recovering")
                  and all(st.get("generation", {}).get(r) == idx
                          for r, idx in expect.items()))
        except Exception:
            ok = False
        stable = stable + 1 if ok else 0
        await loop.sleep(1.0)
    return st


def _mttr_report(events, recovery_log, ledger: AckedLedger) -> list[dict]:
    """Per-fault MTTR: match each injected fault to the first recovery
    the controller DETECTED at/after its wall stamp (several faults can
    fold into one generation change — they then share the entry). A
    match detected only after the NEXT scripted event's stamp is marked
    `attribution: "shared"` and claims no detection latency: a fault
    that triggered no recovery at all (a pause shorter than the probe
    timeout, a partition needing no generation change) must not steal
    the following fault's recovery as its own MTTR."""
    out = []
    for i, ev in enumerate(events):
        if ev.action not in ("kill", "partition", "pause"):
            continue
        rep = {"action": ev.action, "target": ev.target,
               "at_s": ev.at_s, "error": ev.error}
        entry = next((e for e in recovery_log
                      if ev.stamp is not None
                      and e["detected_wall"] >= ev.stamp), None)
        # The demotion threshold is the next FAULT only: this fault's
        # own scripted repair (restart/resume/heal) cannot be a
        # competing fault, and on a loaded host detection can honestly
        # land after it.
        next_stamp = next((e2.stamp for e2 in events[i + 1:]
                           if e2.stamp is not None
                           and e2.action in ("kill", "partition", "pause")),
                          None)
        if entry is not None:
            shared = (next_stamp is not None
                      and entry["detected_wall"] >= next_stamp)
            rep.update({
                "recovered_epoch": entry["epoch"],
                "detection_s": (None if shared else round(
                    entry["detected_wall"] - ev.stamp, 3)),
                "lock_s": entry["lock_s"],
                "salvage_s": entry["salvage_s"],
                "recruit_s": entry["recruit_s"],
                "mttr_total_s": (None if shared else round(
                    entry["completed_wall"] - ev.stamp, 3)),
            })
            if shared:
                rep["attribution"] = "shared"
        if ev.stamp is not None:
            blackout = ledger.first_ack_after(ev.stamp)
            rep["first_ack_after_s"] = (round(blackout, 3)
                                        if blackout is not None else None)
        out.append(rep)
    return out


def run_chaos(seed: int = 20260804, fast: bool = False,
              rate: float = 80.0, workdir: "str | None" = None,
              script: "list[ChaosEvent] | None" = None,
              duration_s: "float | None" = None,
              n_ctrs: int = 16, max_inflight: int = 256,
              drain_s: float = 20.0,
              recorder_path: "str | None" = None) -> dict:
    """One seeded chaos run → the CHAOS record (see module docstring).

    ``recorder_path``: arm the obs flight recorder for this run — server
    processes start with FDB_TPU_OBS=1 (stage spans ride commit replies),
    the harness loop gets a SpanSink + FlightRecorder scraping the
    cluster each second, every fault/heal is annotated on the timeline,
    and the client-side ledger counters join the scrape as the `client`
    role (the SLO tracker's unknown-result SLI). The ring at that path
    is the doctor's input (obs/doctor.py, `cli doctor`)."""
    from foundationdb_tpu.loadgen.arrivals import poisson_schedule

    workdir = workdir or tempfile.mkdtemp(prefix="chaos_")
    events, default_dur = default_script(fast)
    if script is not None:
        events = script
    dur = duration_s if duration_s is not None else default_dur
    cores = (len(os.sched_getaffinity(0)) if hasattr(os, "sched_getaffinity")
             else (os.cpu_count() or 1))
    counters = {k: 0 for k in ("chaos_faults_injected", "chaos_kills",
                               "chaos_restarts", "chaos_partitions",
                               "chaos_heals", "chaos_pauses")}
    ledger = AckedLedger()
    pref = b"chaos/%d/" % seed
    # ONE topology literal: the published record and the actual boot call
    # both read it, so they cannot drift apart.
    topo = {"proxies": 2, "tlogs": 2, "storages": 1, "resolvers": 1,
            "managed": True, "relay_roles": ("tlog",)}
    rec: dict = {
        "metric": "deployed_chaos",
        "seed": seed,
        "fast": fast,
        "engine": "cpu-skiplist resolve over real TCP (no TPU claimed)",
        "cpu_fallback": False,
        "cluster": {**topo, "relay_roles": list(topo["relay_roles"])},
        "host": {"cores": cores,
                 "loadavg_1m": round(os.getloadavg()[0], 2)},
        "rate_tps": rate,
        "duration_s": dur,
        "workdir": workdir,
        # The full workload shape rides the replay line: a non-default
        # rate changes the poisson schedule, so omitting it would make
        # the record claim a reproduction it doesn't perform
        # (chaos_run.sh forwards unrecognized args to the module).
        # A recorder-armed run traces the servers (FDB_TPU_OBS=1), which
        # is a different workload than an untraced one — the replay line
        # must say so.
        "replay": f"bash scripts/chaos_run.sh --seed {seed}"
                  + (" --fast" if fast else "")
                  + (f" --rate {rate:g}" if rate != 80.0 else "")
                  + (" --recorder flight_ring.jsonl" if recorder_path
                     else ""),
    }
    if recorder_path:
        rec["recorder_path"] = recorder_path
    problems: list[str] = []
    cluster: "SocketCluster | None" = None
    client_t = None  # the open_client NetTransport: closed on EVERY path
    try:
        # Boot INSIDE the guarded region: a role that dies during boot
        # must still yield an ok:false record and a reaped cluster (the
        # relays' listener threads start at construction).
        _log(f"seed={seed} fast={fast}: booting managed cluster in {workdir}")
        cluster = SocketCluster(
            workdir, ratekeeper=True, data_dirs=True,
            env=({"FDB_TPU_OBS": "1"} if recorder_path else None), **topo)
        cluster.start()
        rec["cluster"]["processes"] = len(cluster.procs)
        loop, t, db = cluster.open_client()
        client_t = t
        from foundationdb_tpu.client.transaction import Transaction

        db.transaction_class = Transaction
        ctrl = cluster.controller_ep(t)
        schedule = poisson_schedule(rate, dur, seed=seed)
        recorder = None
        if recorder_path:
            from foundationdb_tpu.obs.recorder import FlightRecorder
            from foundationdb_tpu.obs.registry import (
                add_span_sink,
                scrape_deployed_async,
            )
            from foundationdb_tpu.obs.span import SpanSink
            from foundationdb_tpu.server import load_spec as _spec_load

            # Client-side sink: servers run FDB_TPU_OBS=1 (env above), so
            # commit replies carry proxy stage spans and the harness
            # assembles full trees — dense enough at 1-in-8 for per-window
            # stage shares without distorting the workload.
            sink = SpanSink(loop, sample_every=8)
            chaos_spec = _spec_load(cluster.spec_path)

            async def recorder_scrape():
                reg = await scrape_deployed_async(loop, t, chaos_spec)
                reg.add("chaos", "", dict(counters))
                # The client's own ledger is the only honest source of
                # the unknown-result SLI — servers cannot know which
                # acks were lost in flight.
                reg.add("client", "", {
                    "commits_acked": len(ledger.acked),
                    "commit_unknowns": len(ledger.unknown),
                    "offered": ledger.offered,
                    "op_timeouts": ledger.op_timeouts,
                    "conflict_retries": ledger.conflict_retries,
                })
                add_span_sink(reg, sink)
                return reg

            recorder = FlightRecorder(loop, recorder_scrape, recorder_path,
                                      interval_s=1.0)

        async def main():
            t0 = loop.now
            recorder_task = (
                loop.spawn(recorder.run(), name="chaos.recorder")
                if recorder is not None else None)
            ev_task = loop.spawn(
                _run_events(loop, cluster, events, t0, counters),
                name="chaos.events")
            await _open_loop_writer(loop, db, ledger, pref, schedule,
                                    n_ctrs, max_inflight, drain_s)
            await ev_task
            # -- heal + quiesce ------------------------------------------
            _log("heal + quiesce")
            cluster.heal_all()
            for p in cluster.procs:
                if p.paused:
                    cluster.resume_role(p.name)
            for p in cluster.procs:
                if not p.alive():
                    _log(f"restarting dead {p.name} for quiesce")
                    cluster.restart_role(p.name, wait=False)
            for p in cluster.procs:
                ready_deadline = loop.now + 30.0
                while (not cluster.role_ready(p.name)
                       and loop.now < ready_deadline):
                    await loop.sleep(0.1)
            st = await _controller_stable(loop, ctrl, cluster.spec, 120.0)
            # Prove the healed cluster ACCEPTS commits before judging it.
            settle_deadline = loop.now + 60.0
            while True:
                tr = db.transaction()
                try:
                    tr.set(pref + b"settle", b"1")
                    await _bounded(loop, tr.commit(), OP_TIMEOUT_S,
                                   "chaos.settle")
                    break
                except (FdbError, _OpTimeout):
                    if loop.now > settle_deadline:
                        raise
                    await loop.sleep(0.5)
            # -- exact read-back -----------------------------------------
            _log("ledger read-back")
            got: dict[bytes, bytes] = {}
            readback_deadline = loop.now + 60.0
            while True:
                tr = db.transaction()
                try:
                    rows = await _bounded(
                        loop,
                        tr.get_range(pref, pref + b"\xff", snapshot=True),
                        30.0, "chaos.readback")
                    got = dict(rows)
                    break
                except (FdbError, _OpTimeout):
                    if loop.now > readback_deadline:
                        raise
                    await loop.sleep(0.5)
            # -- consistency check ---------------------------------------
            _log("consistency check")
            from foundationdb_tpu.consistency import run_deployed_check
            from foundationdb_tpu.server import load_spec

            consistency = await run_deployed_check(
                loop, t, load_spec(cluster.spec_path), db)
            log = await _bounded(loop, ctrl.get_recovery_log(), 5.0,
                                 "chaos.recovery_log")
            if recorder_task is not None:
                # One final scrape so the post-heal state is on the ring
                # (recovery counters, healed metrics), then stop.
                try:
                    recorder.observe_registry(await recorder_scrape())
                except Exception:
                    pass
                recorder_task.cancel()
            return st, got, consistency, log

        st, got, consistency, recovery_log = loop.run(
            main(), timeout=dur + drain_s + 600.0)

        # -- verification ----------------------------------------------------
        lost = sorted(
            k.decode() for k, v in ledger.acked.items() if got.get(k) != v)
        unknown_committed = sum(
            1 for k, v in ledger.unknown.items() if got.get(k) == v)
        unknown_absent = sum(
            1 for k in ledger.unknown if k not in got)
        unknown_mangled = (len(ledger.unknown) - unknown_committed
                           - unknown_absent)
        markers_present = sum(
            1 for k in got if k.startswith(pref + b"m/"))
        ctr_sum = sum(int(v) for k, v in got.items()
                      if k.startswith(pref + b"ctr/"))
        acked_marker_missing = [
            m.decode() for m in ledger.acked_markers if m not in got]
        exactly_once_ok = (ctr_sum == markers_present
                           and not acked_marker_missing
                           and unknown_mangled == 0)
        rec["ledger"] = {
            "offered": ledger.offered,
            "acked": len(ledger.acked),
            "unknown": len(ledger.unknown),
            "unknown_committed": unknown_committed,
            "unknown_absent": unknown_absent,
            "unknown_mangled": unknown_mangled,
            "shed": ledger.shed,
            "abandoned": ledger.abandoned,
            "conflict_retries": ledger.conflict_retries,
            "op_timeouts": ledger.op_timeouts,
            "acked_lost": lost[:20],
            "acked_lost_count": len(lost),
            "counter_sum": ctr_sum,
            "markers_present": markers_present,
            "acked_marker_missing": acked_marker_missing[:20],
            "exactly_once_ok": exactly_once_ok,
            "nonretryable_errors": ledger.nonretryable[:20],
        }
        rec["faults"] = _mttr_report(events, recovery_log, ledger)
        rec["recovery_log"] = recovery_log
        rec["recoveries_completed"] = st.get("recoveries_completed")
        rec["final_epoch"] = st.get("epoch")
        rec["consistency"] = {
            "status": consistency.get("status"),
            "divergences": len(consistency.get("divergences") or []),
            "shards_checked": consistency.get("shards_checked"),
            "rows_compared": consistency.get("rows_compared"),
        }
        # -- metrics scrape (registry + chaos counters, audited) -------------
        from foundationdb_tpu.obs.registry import (
            CHAOS_DOCUMENTED_COUNTERS,
            scrape_deployed,
        )
        from foundationdb_tpu.server import load_spec as _load

        reg = scrape_deployed(loop, t, _load(cluster.spec_path))
        reg.add("chaos", "", dict(counters))
        extra_documented = CHAOS_DOCUMENTED_COUNTERS
        if recorder is not None:
            from foundationdb_tpu.obs.registry import (
                RECORDER_DOCUMENTED_COUNTERS,
            )

            reg.add("recorder", "", recorder.metrics())
            reg.add("slo", "", recorder.slo.metrics())
            extra_documented = (CHAOS_DOCUMENTED_COUNTERS
                                + RECORDER_DOCUMENTED_COUNTERS)
            rec["recorder"] = {
                "path": recorder_path,
                **recorder.metrics(),
                "slo": recorder.slo.status(),
            }
            recorder.close()
        audit = reg.audit()
        missing = reg.missing_documented(extra=extra_documented)
        rec["scrape"] = {"metrics": len(reg.values),
                         "audit_problems": audit[:10],
                         "missing_documented": missing}
        agg = reg.aggregated()
        rec["recovery_counters"] = {
            k: agg[k] for k in agg if k.startswith("controller.recovery")}
        t.close()

        # -- gates -----------------------------------------------------------
        if lost:
            problems.append(f"ACKED-COMMIT LOSS: {len(lost)} keys")
        if not exactly_once_ok:
            problems.append(
                f"exactly-once violated: counters={ctr_sum} "
                f"markers={markers_present} "
                f"acked_marker_missing={len(acked_marker_missing)} "
                f"mangled={unknown_mangled}")
        if consistency.get("status") != "consistent":
            problems.append(
                f"consistency check {consistency.get('status')!r}")
        if ledger.nonretryable:
            problems.append(
                f"{len(ledger.nonretryable)} non-retryable client errors "
                f"(first: {ledger.nonretryable[0]})")
        if not ledger.acked:
            problems.append("no commit was ever acked (harness starved)")
        kill_unmatched = [
            f["target"] for f in rec["faults"]
            if f["action"] == "kill" and "recovered_epoch" not in f]
        if kill_unmatched:
            problems.append(
                f"kills with no matched recovery: {kill_unmatched}")
        inject_failures = [
            f"{ev.action} {ev.target}: {ev.error}"
            for ev in events if ev.error]
        if inject_failures:
            # A fault that failed to INJECT proves nothing about the
            # cluster — a partition that never happened must not let the
            # battery claim the partition was survived.
            problems.append(f"fault injection failed: {inject_failures}")
        if audit:
            problems.append(f"scrape audit problems: {audit[:3]}")
        if missing:
            problems.append(f"documented counters missing: {missing}")
    except Exception as e:  # noqa: BLE001 — the record must say WHY
        problems.append(f"harness error: {type(e).__name__}: {e}")
        if client_t is not None:
            try:  # a failed run must not leak the client's sockets
                client_t.close()
            except Exception:
                pass
        if cluster is not None:
            cluster.kill()
        rec["ok"] = rec["valid"] = False
        rec["problems"] = problems
        return rec
    try:
        cluster.shutdown()
    except RuntimeError as e:
        problems.append(str(e))  # the crashed-process leak check (deploy.py)
        cluster.kill()  # shutdown kept the proc table for exactly this
        # mop-up: reap orphan groups, close the relays' listeners
    rec["chaos_counters"] = counters
    rec["ok"] = rec["valid"] = not problems
    rec["problems"] = problems
    if cores <= 1:
        rec["mttr_caveat"] = (
            "single-core host: MTTR stage durations include CPU "
            "contention with the workload and every other role process — "
            "treat absolute times as upper bounds (correctness gates are "
            "unaffected)")
    return rec


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m foundationdb_tpu.loadgen.chaos",
        description="Deployed-cluster chaos battery -> one JSON line")
    ap.add_argument("--seed", type=int, default=20260804)
    ap.add_argument("--fast", action="store_true",
                    help="one kill-restart cycle per role class only; "
                         "default adds "
                         "partition-then-heal + SIGSTOP freeze")
    ap.add_argument("--rate", type=float, default=80.0,
                    help="open-loop offered load, txns/sec")
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--recorder", default=None, metavar="RING_PATH",
                    help="arm the obs flight recorder: servers traced "
                         "(FDB_TPU_OBS=1), 1s metric snapshots + fault/"
                         "heal annotations ringed to RING_PATH — feed it "
                         "to `cli doctor` / --doctor for the root-cause "
                         "report")
    args = ap.parse_args(argv)
    rec = run_chaos(seed=args.seed, fast=args.fast, rate=args.rate,
                    workdir=args.workdir, recorder_path=args.recorder)
    print(json.dumps(rec), flush=True)
    return 0 if rec["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
