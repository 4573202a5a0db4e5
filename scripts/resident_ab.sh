#!/bin/bash
# Resident-dictionary A/B: the same bench stream through FDB_TPU_RESIDENT=1
# (device-resident dictionary + rank-space history, delta-only shipping)
# and =0 (the per-dispatch repack baseline), one JSON line at the end.
#
# The quoted numbers are the ISSUE-8 acceptance pair: host pack time per
# dispatch window (windowed.host_pack_ms_per_window — target >= 3x cut on
# the windowed ycsb path) and the modeled roofline bytes/batch
# (bytes_per_batch_packed vs bytes_per_batch_resident — target >= 1.5x
# further cut vs the packed baseline), at equal oracle-verified verdicts
# on the same seeds. Honesty flags (valid / cpu_fallback / p99_quotable)
# ride along exactly like the other A/B artifacts.
#
# Runs on the device JAX finds, and bench.py names it in each record.
# Without a chip bench.py exits non-zero: say JAX_PLATFORMS=cpu to take the
# delta on the CPU backend on purpose (a real, if hardware-different,
# measurement; `valid` is then false).
#
#   TXNS=262144 MODE=ycsb OUT=RESIDENT_AB.json scripts/resident_ab.sh
set -u
cd "$(dirname "$0")/.."
# Default spans >= 4 dispatch windows so the record carries WARM pack
# times (window 0 is the resident engine's cold-start full repack).
TXNS=${TXNS:-1048576}
MODE=${MODE:-ycsb}
OUT=${OUT:-RESIDENT_AB.json}
LOG=${LOG:-resident_ab.log}

run() {  # run RESIDENT_FLAG OUTFILE
  env FDB_TPU_RESIDENT="$1" \
      python bench.py --mode "$MODE" --txns "$TXNS" --no-adaptive \
      > "$2" 2>> "$LOG"
}

run 1 /tmp/_resident_ab_on.json || true
run 0 /tmp/_resident_ab_off.json || true

python - "$OUT" <<'PYEOF'
import json
import sys


def last(path):
    try:
        return json.loads(open(path).read().strip().splitlines()[-1])
    except Exception:
        return {}


r = last("/tmp/_resident_ab_on.json")
b = last("/tmp/_resident_ab_off.json")
rw = r.get("windowed") or {}
bw = b.get("windowed") or {}
roof = r.get("roofline") or {}
pack_r = rw.get("host_pack_ms_per_window")
pack_b = bw.get("host_pack_ms_per_window")
bp = roof.get("bytes_per_batch_packed")
br = roof.get("bytes_per_batch_resident")
rec = {
    "metric": "resident_ab_dictionary",
    "mode": r.get("mode"),
    "backend": r.get("backend"),
    "txns": r.get("txns"),
    "resident_windowed_txns_per_sec": rw.get("value"),
    "baseline_windowed_txns_per_sec": bw.get("value"),
    "throughput_ratio": (round(rw["value"] / bw["value"], 3)
                         if rw.get("value") and bw.get("value") else None),
    "host_pack_ms_per_window_resident": pack_r,
    "host_pack_ms_per_window_baseline": pack_b,
    "host_pack_mean_ratio": (round(pack_b / pack_r, 2)
                             if pack_r and pack_b else None),
    # The headline per-dispatch claim: WARM windows (steady state; the
    # resident cold window IS the amortized full repack and is quoted
    # separately via host_pack_ms_cold in each side's windowed record).
    "host_pack_ms_warm_resident": rw.get("host_pack_ms_warm"),
    "host_pack_ms_warm_baseline": bw.get("host_pack_ms_warm"),
    "host_pack_ms_cold_resident": rw.get("host_pack_ms_cold"),
    "host_pack_ratio": (
        round(bw["host_pack_ms_warm"] / rw["host_pack_ms_warm"], 2)
        if rw.get("host_pack_ms_warm") and bw.get("host_pack_ms_warm")
        else (round(pack_b / pack_r, 2) if pack_r and pack_b else None)
    ),
    "dictionary": rw.get("dictionary"),
    "roofline_bytes_packed": bp,
    "roofline_bytes_resident": br,
    "roofline_resident_ratio": roof.get("resident_bytes_ratio"),
    "resident_p99_ms": rw.get("p99_ms"),
    "baseline_p99_ms": bw.get("p99_ms"),
    "p99_quotable": bool(rw.get("p99_quotable") and bw.get("p99_quotable")),
    # Equal verdicts on the same seeds: each side's verdict_parity is its
    # own oracle check vs the CPU skiplist; conflicts must also agree
    # ACROSS sides for the A/B to count.
    "verdict_parity_both": bool(r.get("verdict_parity")
                                and b.get("verdict_parity")),
    "conflicts_equal": r.get("conflicts") == b.get("conflicts"),
    "cpu_fallback": bool(r.get("cpu_fallback") or b.get("cpu_fallback")
                         or r.get("backend") != "tpu"),
    "valid": bool(r.get("valid") and b.get("valid")),
}
open(sys.argv[1], "w").write(json.dumps(rec) + "\n")
print(json.dumps(rec))
PYEOF
