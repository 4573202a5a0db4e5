#!/bin/bash
# Tiered-dictionary A/B (ISSUE 18): the two-tier HBM/host dictionary
# (FDB_TPU_DICT_HOT_CAPACITY) vs the single-tier resident engine pinned
# to the SAME hot capacity (FDB_TPU_DICT_CAPACITY=H), on a keyspace 100x
# the hot tier — the billion-key regime scaled to the harness. Two
# workloads, four runs, one JSON line:
#
#   zipf     — stationary scrambled Zipf 0.99 (head stays hot, the tail
#              goes cold once and never returns)
#   hotspot  — --shifting-hotspot (keys go cold on a schedule; the
#              adversarial stream for the single-tier design, which must
#              full-repack at every capacity cliff)
#
# Gates (each recorded, all must hold for gates_pass):
#   * capacity_ratio >= 100 (keys / hot capacity)
#   * ZERO full repacks on the tiered arms' hot path
#   * byte-identical verdicts: each arm's own CPU-skiplist parity AND
#     verdicts_sha256 equal across arms per workload
#   * demotion+promotion delta bytes/dispatch at least 10x below the
#     full-repack counterfactual (each demotion event priced as the
#     whole-dictionary ship the pre-tiering engine pays at that same
#     watermark crossing: demotion_events * full_repack_ship_bytes)
#
# Honesty flags ride along exactly like the other A/B artifacts: on the
# CPU backend `valid` is false with the reason, but the parity and
# zero-repack gates still bind (PIPELINE_AB / OPENLOOP_AB precedent).
#
# Runs on the device JAX finds, and bench.py names it in each record.
# Without a chip bench.py exits non-zero: say JAX_PLATFORMS=cpu to take the
# delta on the CPU backend on purpose (a real, if hardware-different,
# measurement; `valid` is then false).
#
# Sizing (see bench.py gen_workload's shifting-hotspot geometry): batch
# 512 keeps the MVCC window (WINDOW=64 versions = 64 batches) well
# inside the stream so keys genuinely age out; H=131072 holds the
# measured Zipf-0.99 working set (~84k dict entries incl. range-end
# sentinels); delta 65536 covers the worst per-window new-key count.
#
#   TXNS=262144 OUT=TIERED_AB.json scripts/tiered_ab.sh
set -u
cd "$(dirname "$0")/.."
TXNS=${TXNS:-262144}
HOT=${HOT:-131072}
KEYS=${KEYS:-$((HOT * 100))}
BATCH=${TIERED_BATCH:-512}
OUT=${OUT:-TIERED_AB.json}
LOG=${LOG:-tiered_ab.log}

run() {  # run HOT_CAPACITY OUTFILE [extra bench args...]
  local hot="$1" out="$2"; shift 2
  env FDB_TPU_DICT_HOT_CAPACITY="$hot" \
      FDB_TPU_DICT_CAPACITY="$HOT" \
      FDB_TPU_DICT_DELTA=$((HOT / 2)) \
      FDB_TPU_DICT_DEMOTE_BATCH=2048 \
      python bench.py --mode ycsb --batch "$BATCH" --txns "$TXNS" \
      --keys "$KEYS" --no-adaptive --smoke "$@" \
      > "$out" 2>> "$LOG"
}

run "$HOT" /tmp/_tiered_ab_zipf_on.json || true
run 0      /tmp/_tiered_ab_zipf_off.json || true
run "$HOT" /tmp/_tiered_ab_hot_on.json --shifting-hotspot || true
run 0      /tmp/_tiered_ab_hot_off.json --shifting-hotspot || true

python - "$OUT" "$HOT" "$KEYS" <<'PYEOF'
import json
import sys


def last(path):
    try:
        return json.loads(open(path).read().strip().splitlines()[-1])
    except Exception:
        return {}


hot_cap, n_keys = int(sys.argv[2]), int(sys.argv[3])


def arm_pair(name, on, off):
    tw, bw = on.get("windowed") or {}, off.get("windowed") or {}
    ts, bs = tw.get("dictionary") or {}, bw.get("dictionary") or {}
    disp = max(1, ts.get("dispatches") or 1)
    ship = ts.get("full_repack_ship_bytes") or 0
    row_bytes = (ship // max(1, (ts.get("dict_capacity") or 0) + 1) - 4
                 if ship else 0)
    # Tiered delta traffic: evict-rank ships plus the promotion rows the
    # delta re-ships for keys returning from the cold tier.
    demote_b = ts.get("demotion_bytes_per_dispatch") or 0.0
    promote_b = (ts.get("promotions") or 0) * max(row_bytes, 0) / disp
    delta_b = demote_b + promote_b
    # Counterfactual: the SAME watermark crossings priced as full
    # repacks (what the single-tier engine does instead of demoting).
    counter_b = (ts.get("demotion_events") or 0) * ship / disp
    sha_on, sha_off = tw.get("verdicts_sha256"), bw.get("verdicts_sha256")
    return {
        "workload": name,
        "tiered_windowed_txns_per_sec": tw.get("value"),
        "baseline_windowed_txns_per_sec": bw.get("value"),
        "tiered_full_repacks": ts.get("full_repacks"),
        "baseline_full_repacks": bs.get("full_repacks"),
        "demotions": ts.get("demotions"),
        "promotions": ts.get("promotions"),
        "demotion_events": ts.get("demotion_events"),
        "cold_tier_keys": ts.get("cold_tier_keys"),
        "dict_hot_occupancy": ts.get("dict_hot_occupancy"),
        "delta_bytes_per_dispatch": round(delta_b, 1),
        "counterfactual_repack_bytes_per_dispatch": round(counter_b, 1),
        "repack_vs_delta_ratio": (round(counter_b / delta_b, 1)
                                  if delta_b else None),
        # Measured cross-arm traffic: what the untiered arm ACTUALLY
        # shipped in repacks on this stream (quoted, not gated — its
        # repack cadence depends on how far past the cliff the stream
        # runs).
        "baseline_repack_bytes_per_dispatch": round(
            (bs.get("full_repacks") or 0) * (bs.get(
                "full_repack_ship_bytes") or 0)
            / max(1, bs.get("dispatches") or 1), 1),
        "verdict_parity_both": bool(on.get("verdict_parity")
                                    and off.get("verdict_parity")),
        "verdicts_sha_equal": bool(sha_on and sha_on == sha_off),
        "conflicts_equal": on.get("conflicts") == off.get("conflicts"),
        "conflicts": on.get("conflicts"),
        "valid_arms": bool(on.get("valid") and off.get("valid")),
        "gates": {
            "zero_hot_path_full_repacks": ts.get("full_repacks") == 0,
            "parity": bool(on.get("verdict_parity")
                           and off.get("verdict_parity")
                           and sha_on and sha_on == sha_off),
            "delta_10x_below_repack": bool(delta_b
                                           and counter_b / delta_b >= 10),
        },
    }


streams = [
    arm_pair("ycsb_zipf_0.99", last("/tmp/_tiered_ab_zipf_on.json"),
             last("/tmp/_tiered_ab_zipf_off.json")),
    arm_pair("shifting_hotspot", last("/tmp/_tiered_ab_hot_on.json"),
             last("/tmp/_tiered_ab_hot_off.json")),
]
r = last("/tmp/_tiered_ab_zipf_on.json")
gates_pass = all(all(s["gates"].values()) for s in streams)
valid = bool(all(s["valid_arms"] for s in streams) and gates_pass)
reasons = []
if not all(s["valid_arms"] for s in streams):
    reasons.append("cpu_fallback" if r.get("backend") != "tpu"
                   else "arm_invalid")
if not gates_pass:
    reasons.append("gate_failed")
rec = {
    "metric": "tiered_ab_dictionary",
    "backend": r.get("backend"),
    "txns": r.get("txns"),
    "hot_capacity": hot_cap,
    "keys": n_keys,
    "capacity_ratio": round(n_keys / hot_cap, 1),
    "streams": streams,
    "gates_pass": gates_pass,
    "p99_quotable": bool(r.get("p99_quotable")),
    "cpu_fallback": bool(r.get("cpu_fallback")
                         or r.get("backend") != "tpu"),
    "valid": valid,
}
if not valid:
    rec["invalid_reason"] = ";".join(reasons) or "unknown"
open(sys.argv[1], "w").write(json.dumps(rec) + "\n")
print(json.dumps(rec))
PYEOF
