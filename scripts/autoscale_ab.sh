#!/bin/bash
# Elastic-autoscale A/B harness (ISSUE 20 acceptance artifact): runs
# python -m foundationdb_tpu.autoscale --ab — the SAME seeded open-loop
# "dur:rate" flash-crowd schedule against the closed-loop autoscaler
# (policy + scale-via-recovery) and a frozen fleet, plus an oscillating
# schedule whose period sits inside the policy cooldown — and publishes
# the autoscale_ab record:
#
#   scale_events  — every applied recruit/retire with the staged
#                   detect/recruit/relief breakdown (time-to-relief is
#                   gated per event, and the doctor re-attributes each
#                   event to its triggering signal from ring snapshots);
#   gates         — zero acked-commit loss + exactly-once unknown-result
#                   resolution across every scale transition (the chaos
#                   ledger identity), relief recorded per event, every
#                   event doctor-attributed, oscillation within the
#                   hysteresis bound;
#   oscillation   — scale-event count vs the provable hysteresis bound
#                   (an oscillation-follower would emit one per period).
#
# Standard honesty flags ride in the record: `valid` gates on ALL of the
# above; `cpu_fallback` is true (this is the CPU sim twin — no device
# claim); `p99_quotable` carries the sample-count rule; the goodput and
# p99 ratios between arms are REPORTED but never gated
# (single_core_caveat — the OPENLOOP_AB precedent).
#
#   SEED=20260807 OUT=AUTOSCALE_AB.json scripts/autoscale_ab.sh
set -u
cd "$(dirname "$0")/.."
OUT=${OUT:-AUTOSCALE_AB.json}
LOG=${LOG:-autoscale_ab.log}
SEED=${SEED:-20260807}
FAST=${FAST:-}

SCRATCH=$(mktemp -d /tmp/_autoscale_ab.XXXXXX)
trap 'rm -rf "$SCRATCH"' EXIT
env JAX_PLATFORMS=cpu python -m foundationdb_tpu.autoscale --ab \
    --seed "$SEED" ${FAST:+--fast} \
    > "$SCRATCH/rec.json" 2>> "$LOG"
rc=$?
if [ $rc -ne 0 ] || [ ! -s "$SCRATCH/rec.json" ]; then
  # Harness errors (nonzero rc is RESERVED for them) must not ship a
  # vacuous artifact a done-check could mistake for the record.
  echo "autoscale_ab: --ab run failed rc=$rc (see $LOG)" >&2
  exit 1
fi
tail -n 1 "$SCRATCH/rec.json" > "$OUT"
# Human summary to stderr; the LAST stdout line is the full record.
python - "$OUT" >&2 <<'PYEOF'
import json, sys
r = json.load(open(sys.argv[1]))
print(json.dumps({
    "valid": r["valid"], "gates": r["gates"],
    "scale_events": [
        {k: e[k] for k in ("name", "role", "from_n", "to_n", "signal",
                           "detect_s", "recruit_s", "relief_s",
                           "time_to_relief")}
        for e in r["scale_events"]],
    "oscillation_events": r["oscillation"]["events_total"],
    "hysteresis_bound": r["oscillation"]["bound"],
    "goodput_ratio": r["goodput_ratio"], "p99_ratio": r["p99_ratio"],
    "host_cores": r["host"]["cores"],
}))
PYEOF
cat "$OUT"
exit 0
