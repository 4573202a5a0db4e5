#!/bin/bash
# fdbmonitor analogue: launch every role of a cluster spec and RESTART any
# process that exits (reference: fdbmonitor supervises fdbserver processes
# from foundationdb.conf; `fdbcli> kill` bounces a process through it).
#
#   scripts/fdbmonitor.sh CLUSTER_DIR
#
# CLUSTER_DIR must contain cluster.json (as written by start_cluster.sh).
# If CLUSTER_DIR/data exists, every role gets a durable --data-dir under
# it, so restarts reload tlog disk queues / storage sqlite state.
#
# Scope depends on the spec's wiring mode (see server.py):
# - STATIC (no "controller" in the spec): a restarted STORAGE rejoins
#   live; chain roles (sequencer/resolver/tlog/proxy) need a WHOLE-
#   cluster bounce, which with data dirs restores every acked commit
#   (boot_sequencer truncates unacked suffixes, new epoch).
# - MANAGED (spec names a "controller" process — supervised here like
#   any other role): the controller heals chain-role failures live with
#   a generation change and folds this script's restarts back in; no
#   full bounce needed (tests/test_managed_cluster.py).
# Stop everything with: touch CLUSTER_DIR/stop
set -euo pipefail
cd "$(dirname "$0")/.."

DIR="${1:?usage: fdbmonitor.sh CLUSTER_DIR}"
SPEC="$DIR/cluster.json"
[ -f "$SPEC" ] || { echo "no $SPEC" >&2; exit 1; }
rm -f "$DIR/stop"
# A chip belongs to one process: with "engine": "tpu" in the spec only the
# resolver runs without the JAX_PLATFORMS=cpu pin (as start_cluster.sh).
ENGINE=$(python -c 'import json, sys
print(json.load(open(sys.argv[1])).get("engine", "cpu"))' "$SPEC")

supervise() { # role index
  local role=$1 idx=$2
  while [ ! -e "$DIR/stop" ]; do
    local data_args=()
    if [ -d "$DIR/data" ]; then
      mkdir -p "$DIR/data/$role$idx"
      data_args=(--data-dir "$DIR/data/$role$idx")
    fi
    local pin=(env JAX_PLATFORMS=cpu)
    if [ "$role" = resolver ] && [ "$ENGINE" = tpu ]; then
      pin=(env)
    fi
    "${pin[@]}" python -m foundationdb_tpu.server \
      --cluster "$SPEC" --role "$role" --index "$idx" \
      --trace-dir "$DIR/traces" "${data_args[@]}" \
      >> "$DIR/$role$idx.log" 2>&1 || true
    [ -e "$DIR/stop" ] && break
    echo "$(date +%H:%M:%S) $role$idx exited — restarting in 1s" \
      >> "$DIR/monitor.log"
    sleep 1
  done
}

ROLES=$(python - "$SPEC" <<'EOF'
import json, sys
spec = json.load(open(sys.argv[1]))
for role, addrs in spec.items():
    if isinstance(addrs, list):
        for i in range(len(addrs)):
            print(role, i)
EOF
)

n=0
while read -r role idx; do
  [ -z "$role" ] && continue
  supervise "$role" "$idx" &
  n=$((n + 1))
done <<< "$ROLES"

echo $$ > "$DIR/monitor.pid"
echo "fdbmonitor supervising $n role processes"
echo "stop with: touch $DIR/stop && python -m foundationdb_tpu.cli --cluster $SPEC --exec 'kill ...' (or kill the pids in $DIR/pids)"

# Track child server pids so stop actually terminates them: the stop file
# gates RESTARTS; the running servers must be told to exit.
( while [ ! -e "$DIR/stop" ]; do
    pgrep -f "foundationdb_tpu.server --cluster $SPEC" > "$DIR/pids" 2>/dev/null || true
    sleep 1
  done
  # stop requested: kill the current server processes; supervise loops
  # see the stop file and do not relaunch.
  pkill -f "foundationdb_tpu.server --cluster $SPEC" 2>/dev/null || true
) &
wait
