#!/bin/bash
# Fixed-window vs adaptive-dispatch A/B (the sched subsystem's acceptance
# harness): ONE bench run carries both sides on the same seeds and the same
# wire stream — the "windowed" record is the fixed --window baseline, the
# "adaptive" record is the deadline coalescer + double-buffered packer
# offered the windowed path's measured rate (equal offered load). Emits a
# JSON comparison with the p99 cut and throughput ratio; acceptance is
# p99_cut_x >= 5 at equal-or-better throughput (kept_up + ratio).
#
# Runs on the device JAX finds, and bench.py names it in each record.
# Without a chip bench.py exits non-zero: say JAX_PLATFORMS=cpu to take the
# delta on the CPU backend on purpose (a real, if hardware-different,
# measurement; `valid` is then false).
#
#   TXNS=262144 MODE=ycsb WINDOW=32 BUDGET_MS=250 OUT=SCHED_AB.json \
#     scripts/sched_ab.sh
set -u
cd "$(dirname "$0")/.."
TXNS=${TXNS:-262144}
MODE=${MODE:-ycsb}
WINDOW=${WINDOW:-32}
BUDGET_MS=${BUDGET_MS:-250}
MAXWIN=${MAXWIN:-8}
OUT=${OUT:-SCHED_AB.json}
LOG=${LOG:-sched_ab.log}

python bench.py --mode "$MODE" --txns "$TXNS" --window "$WINDOW" \
        --latency-budget-ms "$BUDGET_MS" --adaptive-max-window "$MAXWIN" \
        > /tmp/_sched_ab.json 2>> "$LOG"
rc=$?
if [ $rc -ne 0 ]; then
  # A failed bench must not ship a vacuous all-null comparison that a
  # done-check could mistake for the acceptance artifact.
  echo "sched_ab: bench.py failed rc=$rc (see $LOG)" >&2
  exit $rc
fi

python - "$OUT" <<'PYEOF'
import json
import sys


def last(path):
    try:
        return json.loads(open(path).read().strip().splitlines()[-1])
    except Exception:
        return {}


r = last("/tmp/_sched_ab.json")
fixed = r.get("windowed") or {}
adaptive = r.get("adaptive") or {}
fr, ar = fixed.get("value"), adaptive.get("value")
fp99, ap99 = fixed.get("p99_ms"), adaptive.get("p99_ms")
cut = round(fp99 / ap99, 2) if fp99 and ap99 else None
ratio = round(ar / fr, 3) if ar and fr else None
rec = {
    "metric": "sched_ab_fixed_vs_adaptive",
    "mode": r.get("mode"),
    "backend": r.get("backend"),
    "txns": r.get("txns"),
    "fixed_batches_per_dispatch": fixed.get("batches_per_dispatch"),
    "fixed_windowed_txns_per_sec": fr,
    "fixed_p99_ms": fp99,
    "adaptive_txns_per_sec": ar,
    "adaptive_p50_ms": adaptive.get("p50_ms"),
    "adaptive_p99_ms": ap99,
    "adaptive_offered_tps": adaptive.get("offered_tps"),
    "adaptive_mean_depth": adaptive.get("mean_depth"),
    "adaptive_depth_hist": adaptive.get("depth_hist"),
    "latency_budget_ms": adaptive.get("latency_budget_ms"),
    "kept_up": adaptive.get("kept_up"),
    "p99_cut_x": cut,
    "throughput_ratio": ratio,
    # Acceptance: >=5x p99 cut at equal offered load, with the adaptive
    # side keeping up (its achieved rate IS the offered/fixed rate; the
    # measured ratio dips below 1 only by edge effects on short runs).
    "pass_p99_5x": bool(cut and cut >= 5.0 and adaptive.get("kept_up")),
    # Exact A/B verdict parity (same stream, same commit versions — the
    # pack/dispatch split must not change a single verdict). Gradable only
    # when the paced adaptive run covered the whole stream; otherwise the
    # artifact records null, never a vacuous pass.
    "verdict_parity": (
        None
        if (adaptive.get("conflicts") is None or r.get("conflicts") is None
            or adaptive.get("txns") != r.get("txns"))
        else adaptive.get("conflicts") == r.get("conflicts")
    ),
    "valid": bool(r.get("valid")),
}
open(sys.argv[1], "w").write(json.dumps(rec) + "\n")
print(json.dumps(rec))
PYEOF
