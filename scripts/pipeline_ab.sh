#!/bin/bash
# Speculative-pipelined-resolve A/B: the same bench stream through
# FDB_TPU_SPEC_RESOLVE=1 (window N+1 dispatched against window N's
# optimistic paint, reconciled through the repair/wave path) and =0
# (the serial dispatch baseline), one JSON line at the end.
#
# Two streams, same seeds on both arms: the contended Zipf-0.99 ycsb
# stream (the headline) and a uniform-key stream (--theta 0, where
# mis-speculation should be rare and spurious aborts vs the serial
# oracle must be ZERO). The ISSUE-17 acceptance pair is quoted per
# stream: windowed resolved-txns/sec ratio (target >= 1.3x at equal
# p99) and byte-exact replay-checked serializability — each arm's
# verdict_parity is its own CPU-skiplist replay, AND the two arms'
# verdicts_sha256 must be IDENTICAL (compensating flips can't hide).
# The speculative arm's mis-speculation rate (spec_repaired /
# spec_dispatched, the signal the ratekeeper clamps depth on) rides in
# every record. Honesty flags (valid / cpu_fallback / p99_quotable)
# ride along exactly like the other A/B artifacts.
#
# Runs on the device JAX finds, and bench.py names it in each record.
# Without a chip bench.py exits non-zero: say JAX_PLATFORMS=cpu to take the
# delta on the CPU backend on purpose (a real, if hardware-different,
# measurement; `valid` is then false).
#
#   TXNS=262144 OUT=PIPELINE_AB.json scripts/pipeline_ab.sh
set -u
cd "$(dirname "$0")/.."
TXNS=${TXNS:-1048576}
# 8 batches per dispatch window (vs the bench default 32) so the default
# TXNS gives the speculation ring multiple windows to actually overlap —
# one giant window degenerates both arms to a single dispatch and the
# A/B measures nothing.
WINDOW=${WINDOW:-8}
OUT=${OUT:-PIPELINE_AB.json}
LOG=${LOG:-pipeline_ab.log}

run() {  # run SPEC_FLAG THETA OUTFILE
  env FDB_TPU_SPEC_RESOLVE="$1" \
      python bench.py --mode ycsb --theta "$2" --txns "$TXNS" \
      --window "$WINDOW" --no-adaptive > "$3" 2>> "$LOG"
}

run 1 0.99 /tmp/_pipeline_ab_spec_zipf.json || true
run 0 0.99 /tmp/_pipeline_ab_ser_zipf.json || true
run 1 0 /tmp/_pipeline_ab_spec_uni.json || true
run 0 0 /tmp/_pipeline_ab_ser_uni.json || true

python - "$OUT" <<'PYEOF'
import json
import sys


def last(path):
    try:
        return json.loads(open(path).read().strip().splitlines()[-1])
    except Exception:
        return {}


def stream(name, s, b):
    sw = s.get("windowed") or {}
    bw = b.get("windowed") or {}
    spec = sw.get("spec") or {}
    disp = spec.get("spec_dispatched") or 0
    sha_s, sha_b = sw.get("verdicts_sha256"), bw.get("verdicts_sha256")
    rec = {
        "stream": name,
        "spec_windowed_txns_per_sec": sw.get("value"),
        "serial_windowed_txns_per_sec": bw.get("value"),
        "throughput_ratio": (round(sw["value"] / bw["value"], 3)
                             if sw.get("value") and bw.get("value") else None),
        "spec_p99_ms": sw.get("p99_ms"),
        "serial_p99_ms": bw.get("p99_ms"),
        "p99_quotable": bool(sw.get("p99_quotable")
                             and bw.get("p99_quotable")),
        # Byte-exact replay gate: both arms replay-checked against the
        # CPU skiplist on their own seeds (verdict_parity), AND the two
        # arms' full verdict streams hash identically — speculation must
        # be invisible in the verdicts, not just in the conflict count.
        "verdict_parity_both": bool(s.get("verdict_parity")
                                    and b.get("verdict_parity")),
        "verdicts_sha_equal": bool(sha_s and sha_s == sha_b),
        "conflicts_equal": s.get("conflicts") == b.get("conflicts"),
        "serializability_replay_ok": bool(
            s.get("verdict_parity") and b.get("verdict_parity")
            and sha_s and sha_s == sha_b
            and s.get("conflicts") == b.get("conflicts")
        ),
        # Zero spurious aborts by construction: identical verdict hashes
        # mean every mis-speculated txn was re-resolved through the
        # repair path to the SAME verdict the serial oracle produced.
        "conflicts_spec": s.get("conflicts"),
        "conflicts_serial": b.get("conflicts"),
        "spec": spec or None,
        "mis_spec_rate": (round((spec.get("spec_repaired") or 0) / disp, 4)
                          if disp else None),
        "cpu_fallback": bool(s.get("cpu_fallback") or b.get("cpu_fallback")
                             or s.get("backend") != "tpu"),
        "valid_arms": bool(s.get("valid") and b.get("valid")),
    }
    return rec


sz = last("/tmp/_pipeline_ab_spec_zipf.json")
bz = last("/tmp/_pipeline_ab_ser_zipf.json")
su = last("/tmp/_pipeline_ab_spec_uni.json")
bu = last("/tmp/_pipeline_ab_ser_uni.json")
streams = [stream("ycsb_zipf_0.99", sz, bz), stream("ycsb_uniform", su, bu)]
head = streams[0]
reasons = []
if not all(s["serializability_replay_ok"] for s in streams):
    reasons.append("replay_gate_failed")
if any(s["cpu_fallback"] for s in streams):
    reasons.append("cpu_fallback")
if not all(s["valid_arms"] for s in streams):
    reasons.append("arm_invalid")
ratio = head["throughput_ratio"]
if not ratio or ratio < 1.3:
    reasons.append("ratio_below_1.3x_headline")
rec = {
    "metric": "pipeline_ab_spec_resolve",
    "backend": sz.get("backend"),
    "txns": sz.get("txns"),
    "spec_depth": (sz.get("windowed") or {}).get("spec", {}).get(
        "spec_depth"
    ),
    "streams": streams,
    "throughput_ratio": ratio,
    "serializability_replay_ok": all(
        s["serializability_replay_ok"] for s in streams
    ),
    "mis_spec_rate": head["mis_spec_rate"],
    "p99_quotable": all(s["p99_quotable"] for s in streams),
    "cpu_fallback": any(s["cpu_fallback"] for s in streams),
    "valid": not reasons,
}
if reasons:
    rec["invalid_reason"] = ";".join(reasons)
open(sys.argv[1], "w").write(json.dumps(rec) + "\n")
print(json.dumps(rec))
PYEOF
