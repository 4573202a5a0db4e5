"""CI entry point: one-JSON-line read-plane self-check / READS_AB bench.

    python -m foundationdb_tpu.reads          # selfcheck, rc 0/1
    python -m foundationdb_tpu.reads --ab     # full READS_AB record

The selfcheck is a fast all-parity pass — batched point/range reads vs
the sequential oracle on host AND device arms, watch fire-set parity
across arms 0/1/device, plus a small end-to-end get_multi through a
storage server.
The A/B (scripts/reads_ab.sh -> READS_AB.json) additionally measures the
batched-vs-per-key-actor throughput gates and watch-sweep scaling; see
reads/bench.py.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def selfcheck(seed: int = 0) -> dict:
    import random

    from foundationdb_tpu.core.mutations import Mutation, MutationType as M
    from foundationdb_tpu.reads.bench import (
        bench_watch_parity,
        _oracle_results,
        _oracle_shaped_engine,
    )
    from foundationdb_tpu.reads.read_set import TPUReadSet
    from foundationdb_tpu.runtime.flow import Loop
    from foundationdb_tpu.runtime.storage import StorageServer

    rng = random.Random(seed)
    loop = Loop(seed=seed)
    ss = StorageServer(loop, tag=0, tlog_ep=None)
    keys = sorted({bytes(rng.randrange(256) for _ in range(rng.randrange(1, 24)))
                   for _ in range(800)})
    ss._apply(1, [Mutation(M.SET_VALUE, k, b"v0%s" % k[:4]) for k in keys])
    for v in (2, 3, 4):
        ss._apply(v, [Mutation(M.SET_VALUE, rng.choice(keys), b"v%d" % v)
                      for _ in range(60)])

    stream = []
    for _ in range(150):
        ver = rng.randrange(1, 5)
        if rng.random() < 0.3:
            a, b = sorted([rng.choice(keys), rng.choice(keys)])
            stream.append(("range", a, b + b"\x00", rng.randrange(0, 20), ver))
        else:
            stream.append(("points",
                           [rng.choice(keys) for _ in range(rng.randrange(1, 9))]
                           + [bytes([rng.randrange(256)])],  # misses too
                           ver))
    oracle = _oracle_results(ss.read_set, stream)
    host_ok = _oracle_shaped_engine(ss.read_set, stream) == oracle
    dev_ok = _oracle_shaped_engine(TPUReadSet(ss.map, device=True),
                                   stream) == oracle

    async def multi():
        ks = [rng.choice(keys) for _ in range(20)]
        got = await ss.get_multi(ks, 4)
        want = [await ss.get(k, 4) for k in ks]
        return got == want

    rpc_ok = loop.run(multi(), timeout=60_000)
    watch_ok = bench_watch_parity(n_keys=120, versions=25, seed=seed)
    ok = bool(host_ok and dev_ok and rpc_ok and watch_ok)
    return {
        "metric": "reads_selfcheck",
        "ok": ok,
        "host_parity": host_ok,
        "device_parity": dev_ok,
        "get_multi_rpc_parity": rpc_ok,
        "watch_fire_parity": watch_ok,
        "ops": len(stream),
        "read_stats": dict(ss.read_set.stats, pack_s=round(
            ss.read_set.stats["pack_s"], 5)),
    }


def main(argv: "list[str] | None" = None) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # pure sim: no TPU touch
    ap = argparse.ArgumentParser(prog="python -m foundationdb_tpu.reads")
    ap.add_argument("--ab", action="store_true",
                    help="full READS_AB bench instead of the selfcheck")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ops", type=int, default=2000)
    ap.add_argument("--keys", type=int, default=4096)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--clients", type=int, default=24)
    ap.add_argument("--watch-sizes", type=str, default="1000,100000,1000000")
    args = ap.parse_args(argv)
    if args.ab:
        from foundationdb_tpu.reads.bench import run_ab

        sizes = tuple(int(s) for s in args.watch_sizes.split(",") if s)
        rec = run_ab(n_keys=args.keys, n_ops=args.ops, batch=args.batch,
                     n_clients=args.clients, seed=args.seed,
                     watch_sizes=sizes)
        print(json.dumps(rec))
        return 0
    rec = selfcheck(seed=args.seed)
    print(json.dumps(rec))
    return 0 if rec["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
