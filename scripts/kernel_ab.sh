#!/bin/bash
# Packed-vs-unpacked kernel A/B: the same bench stream through
# FDB_TPU_PACKED=1 and =0, one line of bytes/throughput delta at the end.
#
# Runs on the device JAX finds, and bench.py names it in each record.
# Without a chip bench.py exits non-zero: say JAX_PLATFORMS=cpu to take the
# delta on the CPU backend on purpose (a real, if hardware-different,
# measurement; `valid` is then false).
#
#   TXNS=65536 MODE=ycsb OUT=KERNEL_AB.json scripts/kernel_ab.sh
set -u
cd "$(dirname "$0")/.."
TXNS=${TXNS:-65536}
MODE=${MODE:-ycsb}
OUT=${OUT:-KERNEL_AB.json}
LOG=${LOG:-kernel_ab.log}
run() {  # run PACKED_FLAG OUTFILE
  env FDB_TPU_PACKED="$1" \
      python bench.py --mode "$MODE" --txns "$TXNS" > "$2" 2>> "$LOG"
}

run 1 /tmp/_kernel_ab_packed.json || true
run 0 /tmp/_kernel_ab_unpacked.json || true

python - "$OUT" <<'PYEOF'
import json
import sys


def last(path):
    try:
        return json.loads(open(path).read().strip().splitlines()[-1])
    except Exception:
        return {}


def rate(rec):  # windowed rate: the A/B's throughput yardstick
    return ((rec.get("windowed") or {}).get("value")) or rec.get("value")


p = last("/tmp/_kernel_ab_packed.json")
u = last("/tmp/_kernel_ab_unpacked.json")
rp, ru = rate(p), rate(u)
roof = p.get("roofline") or {}
bp = roof.get("bytes_per_batch")
bu = roof.get("bytes_per_batch_unpacked")
rec = {
    "metric": "kernel_ab_packed_vs_unpacked",
    "mode": p.get("mode"),
    "backend": p.get("backend"),
    "txns": p.get("txns"),
    "packed_windowed_txns_per_sec": rp,
    "unpacked_windowed_txns_per_sec": ru,
    "throughput_ratio": round(rp / ru, 3) if rp and ru else None,
    "packed_p99_ms": (p.get("windowed") or {}).get("p99_ms"),
    "unpacked_p99_ms": (u.get("windowed") or {}).get("p99_ms"),
    "roofline_bytes_packed": bp,
    "roofline_bytes_unpacked": bu,
    "roofline_bytes_ratio": round(bu / bp, 2) if bp and bu else None,
    "verdict_parity_both": bool(p.get("verdict_parity")
                                and u.get("verdict_parity")),
    "valid": bool(p.get("valid") and u.get("valid")),
}
open(sys.argv[1], "w").write(json.dumps(rec) + "\n")
print(json.dumps(rec))
PYEOF
