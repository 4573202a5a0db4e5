#!/bin/bash
# Open-loop scale-out harness (ISSUE 11 acceptance artifact): runs
# bench.py --open-loop — a REAL multi-process cluster per proxy count
# over TCP sockets, driven by out-of-process Poisson open-loop
# generators with coordinated-omission-correct latency accounting — and
# publishes the open_loop_scaleout record:
#
#   scaling_curve  — sustainable txns/s vs proxy-process count (the
#                    horizontal scale-out curve), each point's p99 bounded;
#   latency_curve  — CO-corrected p99 commit latency vs offered load on
#                    the largest proxy count, through and PAST saturation;
#   overload       — offered load far past capacity with the resolver
#                    modelling real dispatch cost: the ratekeeper's
#                    resolver_queue/admission_filter clamps engage, shed
#                    and timed-out load is counted explicitly, and the
#                    clamps release (limiting_reason back to "none",
#                    bounded p99) once offered load drops.
#
# Standard honesty flags ride in the record: `valid` gates on the full
# acceptance including throughput scaling across >= 2 proxy counts;
# `cpu_fallback` is false because no TPU run is attempted or claimed
# (the resolve engine is the C++ skiplist — this artifact is about the
# network stack and control plane); `p99_quotable` carries the
# sample-count rule; every latency is `co_corrected`. A single-core
# host (host.cores == 1) cannot demonstrate proxy scaling — N processes
# on one core add no CPU — and the record then says so in
# invalid_reasons while the curves remain measured and complete.
#
#   PROXIES=1,2 DUR=4 OUT=OPENLOOP_AB.json scripts/openloop_ab.sh
set -u
cd "$(dirname "$0")/.."
OUT=${OUT:-OPENLOOP_AB.json}
LOG=${LOG:-openloop_ab.log}
PROXIES=${PROXIES:-1,2}
DUR=${DUR:-4}
GENERATORS=${GENERATORS:-1}

SCRATCH=$(mktemp -d /tmp/_openloop_ab.XXXXXX)
trap 'rm -rf "$SCRATCH"' EXIT
env JAX_PLATFORMS=cpu python bench.py --open-loop \
    --ol-proxies "$PROXIES" --ol-duration "$DUR" \
    --ol-generators "$GENERATORS" \
    > "$SCRATCH/rec.json" 2>> "$LOG"
rc=$?
if [ $rc -ne 0 ] || [ ! -s "$SCRATCH/rec.json" ]; then
  # Harness errors (nonzero rc is RESERVED for them) must not ship a
  # vacuous artifact a done-check could mistake for the record.
  echo "openloop_ab: bench.py --open-loop failed rc=$rc (see $LOG)" >&2
  exit 1
fi
tail -n 1 "$SCRATCH/rec.json" > "$OUT"
# Human summary to stderr; the LAST stdout line is the full record.
python - "$OUT" >&2 <<'PYEOF'
import json, sys
r = json.load(open(sys.argv[1]))
sc = {s["proxies"]: s["sustainable_tps"] for s in r["scaling_curve"]}
ov = r.get("overload") or {}
print(json.dumps({
    "valid": r["valid"], "sustainable_tps_by_proxies": sc,
    "scaling_ratio": r["throughput_scaling"]["ratio"],
    "past_saturation_observed": r["past_saturation_observed"],
    "overload_engaged": ov.get("engaged"),
    "overload_recovered": ov.get("recovered"),
    "signals": ov.get("signals_observed"),
    "host_cores": r["host"]["cores"],
    "invalid_reasons": r.get("invalid_reasons"),
}))
PYEOF
cat "$OUT"
exit 0
