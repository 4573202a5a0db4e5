"""Self-contained consistency audit: one replicated SimCluster under load.

    python -m foundationdb_tpu.consistency [--seed N] [--keys N] [--txns N]

Boots a 3-storage / 2-replica cluster with data distribution on, commits a
randomized write load, runs the full ConsistencyChecker walk, and prints
ONE JSON line (the report). Exit 0 iff the audit came back consistent —
the CI stage contract.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv: list[str] | None = None) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    ap = argparse.ArgumentParser(
        prog="python -m foundationdb_tpu.consistency")
    ap.add_argument("--seed", type=int, default=20260803)
    ap.add_argument("--keys", type=int, default=96)
    ap.add_argument("--txns", type=int, default=48)
    args = ap.parse_args(argv)

    from foundationdb_tpu.client.ryw import open_database
    from foundationdb_tpu.consistency.checker import ConsistencyChecker
    from foundationdb_tpu.runtime.flow import Loop
    from foundationdb_tpu.sim.cluster import SimCluster

    loop = Loop(seed=args.seed)
    cluster = SimCluster(loop=loop, seed=args.seed, n_storages=3,
                         n_replicas=2, n_tlogs=2, data_distribution=True)
    db = open_database(cluster)
    rng = loop.rng

    async def go() -> dict:
        for i in range(args.txns):
            async def body(tr, i=i):
                for _ in range(4):
                    k = b"audit/%05d" % rng.randrange(args.keys)
                    tr.set(k, b"v%08d" % rng.randrange(1 << 30))

            await db.run(body)
        return await ConsistencyChecker(cluster, db).run()

    report = loop.run(go(), timeout=3000)
    report["metric"] = "consistency_check"
    report["seed"] = args.seed
    print(json.dumps(report), flush=True)
    return 0 if report["status"] == "consistent" else 1


if __name__ == "__main__":
    sys.exit(main())
