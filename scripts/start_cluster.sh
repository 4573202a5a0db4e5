#!/bin/bash
# Boot a local multi-process cluster (1 sequencer, 1 resolver, 2 tlogs,
# 2 storages, 2 proxies, ratekeeper) and wait until the cli can commit
# against it.
#
#   scripts/start_cluster.sh [CLUSTER_DIR] [ENGINE]
#
# ENGINE is the resolver's conflict engine: "cpu" (default, the C++
# skiplist) or "tpu". A chip belongs to one process, so with "tpu" the
# resolver alone is launched without the JAX_PLATFORMS=cpu pin (it refuses
# to boot unless JAX gives it a TPU); every other role and the cli probe
# stay pinned to the CPU.
#
# Writes CLUSTER_DIR/cluster.json (default /tmp/fdb_tpu_cluster), launches
# the role processes, and leaves them running; pids in CLUSTER_DIR/pids.
# Stop with: kill $(cat CLUSTER_DIR/pids)
set -euo pipefail
cd "$(dirname "$0")/.."

DIR="${1:-/tmp/fdb_tpu_cluster}"
ENGINE="${2:-cpu}"
BASE_PORT="${FDB_TPU_BASE_PORT:-4500}"
# FDB_TPU_MANAGED=1: include a controller process — the cluster then
# heals chain-role failures live with generation changes (managed mode;
# see server.py DeployedController) instead of needing a full bounce.
MANAGED="${FDB_TPU_MANAGED:-0}"
mkdir -p "$DIR"
SPEC="$DIR/cluster.json"

python - "$SPEC" "$BASE_PORT" "$MANAGED" "$ENGINE" <<'EOF'
import json, sys
spec_path, base, managed = sys.argv[1], int(sys.argv[2]), sys.argv[3] == "1"
ports = iter(range(base, base + 32))
spec = {
    "sequencer": [f"127.0.0.1:{next(ports)}"],
    "resolver": [f"127.0.0.1:{next(ports)}"],
    "tlog": [f"127.0.0.1:{next(ports)}" for _ in range(2)],
    "storage": [f"127.0.0.1:{next(ports)}" for _ in range(2)],
    "proxy": [f"127.0.0.1:{next(ports)}" for _ in range(2)],
    "ratekeeper": [f"127.0.0.1:{next(ports)}"],
    "engine": sys.argv[4],
}
if managed:
    spec["controller"] = [f"127.0.0.1:{next(ports)}"]
json.dump(spec, open(spec_path, "w"), indent=1)
print(spec_path)
EOF

: > "$DIR/pids"
launch() { # role index
  local pin=(env JAX_PLATFORMS=cpu)
  if [ "$1" = resolver ] && [ "$ENGINE" = tpu ]; then
    pin=(env)
  fi
  "${pin[@]}" python -m foundationdb_tpu.server \
    --cluster "$SPEC" --role "$1" --index "$2" --trace-dir "$DIR/traces" \
    >> "$DIR/$1$2.log" 2>&1 &
  echo $! >> "$DIR/pids"
}

launch sequencer 0
launch resolver 0
launch tlog 0
launch tlog 1
launch storage 0
launch storage 1
launch proxy 0
launch proxy 1
launch ratekeeper 0
if [ "$MANAGED" = "1" ]; then
  launch controller 0
fi

# Wait until a client transaction commits end to end. A resolver on the
# chip compiles before it serves (SocketCluster.BOOT_DEADLINE_S).
for i in $(seq 1 "$([ "$ENGINE" = tpu ] && echo 300 || echo 30)"); do
  if JAX_PLATFORMS=cpu python -m foundationdb_tpu.cli --cluster "$SPEC" \
      --exec 'writemode on; set __boot__ ok; get __boot__' 2>/dev/null \
      | grep -q "is .ok"; then
    echo "cluster up: $SPEC"
    exit 0
  fi
  sleep 1
done
echo "cluster failed to come up; logs in $DIR" >&2
exit 1
