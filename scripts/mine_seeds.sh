#!/bin/bash
# Continuous fresh-seed mining (TestHarness soak analogue), in chunks.
#
# Runs the full spec battery at ever-increasing seed bases, alternating
# normal buggify with aggressive mode. Appends one line per chunk to
# CAMPAIGN_r05_mine_auto.txt;
# full per-chunk logs land in /tmp/mine_chunk_<base>.log and any FAILURE
# output is copied into the summary so a found bug survives /tmp.
set -u
cd /root/repo
OUT=CAMPAIGN_r05_mine_auto.txt
BASE=${1:-5000}
CHUNK=${2:-25}
say() { echo "$(date +%H:%M:%S) $*" >> "$OUT"; }

say "miner armed: base=$BASE chunk=$CHUNK jobs=5"
i=0
while true; do
  base=$((BASE + i * CHUNK))
  if [ $((i % 2)) -eq 0 ]; then flags="--buggify --clog 0.05"; else flags="--buggify-aggressive --clog 0.05"; fi
  log=/tmp/mine_chunk_$base.log
  timeout 5400 python -m foundationdb_tpu.sim.run tests/specs \
    --seeds "$CHUNK" --seed-base "$base" $flags --jobs 5 > "$log" 2>&1
  rc=$?
  # grep -c prints the count (0 included) even on no-match exit 1
  tallies=$(grep -c "^\[" "$log" 2>/dev/null); tallies=${tallies:-0}
  fails=$(grep -c " FAIL " "$log" 2>/dev/null); fails=${fails:-0}
  say "chunk base=$base $flags rc=$rc runs=$tallies fails=$fails"
  if [ "$fails" != "0" ] || [ $rc -ne 0 ]; then
    say "---- failure detail (base=$base) ----"
    grep -A 30 "FAILURES:" "$log" >> "$OUT" 2>/dev/null
    say "---- end detail ----"
    # Stop mining on a real find so the failure is investigated, not
    # buried under more chunks.
    [ "$fails" != "0" ] && exit 1
  fi
  i=$((i + 1))
done
