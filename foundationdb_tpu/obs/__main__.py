"""CI entry point: one-JSON-line obs self-check / sampling-overhead A/B.

    python -m foundationdb_tpu.obs                   # selfcheck, rc 0/1
    python -m foundationdb_tpu.obs --ab              # OBS_AB.json record
    python -m foundationdb_tpu.obs --export-trace f  # Perfetto timeline
    python -m foundationdb_tpu.obs --poll cluster.json --poll-out m.jsonl
    python -m foundationdb_tpu.obs --record cluster.json \
        --record-out ring.jsonl                      # flight recorder
    python -m foundationdb_tpu.obs --doctor ring.jsonl   # incident report
    python -m foundationdb_tpu.obs --doctor-gate     # DOCTOR.json gate

The selfcheck is a scrape + span reconciliation on a short sim run; the
A/B is scripts/obs_ab.sh -> OBS_AB.json. `--poll` is the deployed-cluster
time-series scraper (plain snapshots + scrape_gap records); `--record`
is the full flight recorder over a deployed cluster — bounded on-disk
ring with derived annotations and SLO tracking. `--doctor` runs the
incident doctor over an existing ring; `--doctor-gate` runs the seeded
mini-chaos with the recorder armed and gates the per-fault attribution
(scripts/doctor_run.sh -> DOCTOR.json).
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv: "list[str] | None" = None) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # pure sim: no TPU touch
    ap = argparse.ArgumentParser(prog="python -m foundationdb_tpu.obs")
    ap.add_argument("--ab", action="store_true",
                    help="sampling-overhead A/B (tracing off vs 1-in-N "
                         "vs 1-in-N + flight recorder) instead of the "
                         "selfcheck")
    ap.add_argument("--seed", type=int, default=None)
    ap.add_argument("--txns", type=int, default=None)
    ap.add_argument("--sample-every", type=int, default=None)
    ap.add_argument("--reps", type=int, default=None,
                    help="--ab: reps per arm (best-of-N; default 3)")
    ap.add_argument("--export-trace", default=None, metavar="PATH",
                    help="also write the selfcheck run's sampled window "
                         "as a Chrome-trace/Perfetto JSON timeline")
    ap.add_argument("--poll", default=None, metavar="CLUSTER_JSON",
                    help="poll a DEPLOYED cluster's metrics into a JSONL "
                         "time-series instead of running the selfcheck")
    ap.add_argument("--poll-out", default="obs_metrics.jsonl")
    ap.add_argument("--poll-interval", type=float, default=5.0)
    ap.add_argument("--poll-count", type=int, default=0,
                    help="snapshots to take (0 = until interrupted)")
    ap.add_argument("--record", default=None, metavar="CLUSTER_JSON",
                    help="run the flight recorder against a DEPLOYED "
                         "cluster: bounded JSONL ring of snapshots + "
                         "derived annotations + SLO tracking")
    ap.add_argument("--record-out", default="flight_ring.jsonl")
    ap.add_argument("--record-interval", type=float, default=5.0)
    ap.add_argument("--record-count", type=int, default=0,
                    help="snapshots to take (0 = until interrupted)")
    ap.add_argument("--record-max", type=int, default=None,
                    help="ring bound in records (default 4096)")
    ap.add_argument("--doctor", default=None, metavar="RING_JSONL",
                    help="incident-doctor report over a flight ring")
    ap.add_argument("--doctor-gate", action="store_true",
                    help="seeded mini-chaos with the recorder armed, "
                         "gated on per-fault attribution (DOCTOR.json)")
    args = ap.parse_args(argv)

    from foundationdb_tpu.obs.selfcheck import run_overhead_ab, run_selfcheck

    if args.doctor:
        from foundationdb_tpu.obs.doctor import main_doctor

        report = main_doctor(args.doctor)
        print(json.dumps(report, sort_keys=True), flush=True)
        return 0 if "error" not in report else 1

    if args.doctor_gate:
        from foundationdb_tpu.obs.doctor import run_doctor_gate

        kw = {}
        if args.seed is not None:
            kw["seed"] = args.seed
        rec = run_doctor_gate(**kw)
        print(json.dumps(rec), flush=True)
        return 0 if rec["ok"] else 1

    if args.record:
        from foundationdb_tpu.obs.recorder import FlightRecorder
        from foundationdb_tpu.obs.registry import scrape_deployed_async
        from foundationdb_tpu.runtime.net import NetTransport, RealLoop
        from foundationdb_tpu.server import load_spec

        spec = load_spec(args.record)
        loop = RealLoop()
        t = NetTransport(loop)
        recorder = FlightRecorder(
            loop, lambda: scrape_deployed_async(loop, t, spec),
            args.record_out, interval_s=args.record_interval,
            max_records=args.record_max)
        try:
            async def tick():
                await loop.sleep(recorder.interval_s)
                recorder.observe_registry(
                    await scrape_deployed_async(loop, t, spec))

            while (not args.record_count
                   or recorder.counters["recorder_snapshots"]
                   < args.record_count):
                loop.run(tick(), timeout=recorder.interval_s + 60.0)
        except KeyboardInterrupt:
            pass
        finally:
            recorder.close()
            t.close()
        print(json.dumps({"metric": "obs_record_done",
                          **recorder.metrics(),
                          "out": args.record_out}), flush=True)
        return 0

    if args.poll:
        import time

        from foundationdb_tpu.obs.registry import (
            scrape_deployed,
            scrape_gap_records,
        )
        from foundationdb_tpu.runtime.net import NetTransport, RealLoop
        from foundationdb_tpu.server import load_spec

        spec = load_spec(args.poll)
        loop = RealLoop()
        t = NetTransport(loop)
        # The shared gap bookkeeping rides this synchronous drive too: a
        # dead role must be an explicit scrape_gap record in the JSONL,
        # whichever surface runs the scrape loop. This drive stamps its
        # snapshot lines with WALL time, so the gap records ride the
        # same clock (MetricsPoller.run uses loop.now for both).
        armed_at = time.time()
        last_ok: dict = {}
        taken = gaps_written = 0
        try:
            while not args.poll_count or taken < args.poll_count:
                reg = scrape_deployed(loop, t, spec)
                now = time.time()
                lines = [json.dumps(r, sort_keys=True) for r in
                         scrape_gap_records(reg, now, last_ok, armed_at)]
                gaps_written += len(lines)
                lines.append(reg.to_json_line(
                    t=round(now, 3), seq=taken))
                with open(args.poll_out, "a", encoding="utf-8") as f:
                    f.write("\n".join(lines) + "\n")
                taken += 1
                if not args.poll_count or taken < args.poll_count:
                    time.sleep(args.poll_interval)
        except KeyboardInterrupt:
            pass
        finally:
            t.close()
        print(json.dumps({"metric": "obs_poll_done", "snapshots": taken,
                          "scrape_gaps": gaps_written,
                          "out": args.poll_out}), flush=True)
        return 0

    if args.ab:
        kw = {k: v for k, v in (
            ("seed", args.seed), ("txns", args.txns),
            ("sample_every", args.sample_every), ("reps", args.reps),
        ) if v is not None}
        rec = run_overhead_ab(**kw)
        print(json.dumps(rec), flush=True)
        return 0 if rec["valid"] else 1

    kw = {k: v for k, v in (
        ("seed", args.seed), ("txns", args.txns),
        ("sample_every", args.sample_every),
        ("export_trace", args.export_trace),
    ) if v is not None}
    rec = run_selfcheck(**kw)
    print(json.dumps(rec), flush=True)
    return 0 if rec["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
