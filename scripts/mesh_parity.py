#!/usr/bin/env python3
"""The mesh engine the resolver role is served from, on the chips it finds,
against the plain one-history reference, verdict for verdict:

    python3 scripts/mesh_parity.py [--seed N] [--epochs 3] [--zipf-batches 130]
                                   [--out chiprun_out/mesh_parity.json]

The engine is `server.make_conflict_set("tpu", mesh=4)`: what a spec with
`"resolver_mesh": 4` builds, at the served role's sizes and the engine's
runtime defaults (auto_reshard on). The stream is `ycsb_f_closed_mesh4`'s
own plan (`benchmark/lib/ycsb.py` `plan` at `--seed` and the traffic file's
`base_seed`, the read-modify-writes: one read and one write range on a
scrambled Zipfian key of 50,000 records) in batches of 512, at a FIXED lag:
a transaction's read version is the commit version two batches back, 10,000
versions a batch, the MVCC floor 5,000,000 versions behind. Each of
`--epochs` epochs starts, after a gap longer than the MVCC window (the
history empties), with 8 batches of the bulk load's shape (512 never-seen
keys each, here in ASCENDING key order, so the live history sits in a
narrow key range and the splits have to move) and goes on with the plan's
batches, which spread over all keys (and the splits move back). The
reference is `benchmark/lib/reference.py` `point_verdicts`, which imports
nothing of the program.

Then the two readings the cell's limits lie between: one epoch through
`ShardedConflictSet(n_shards=4, auto_reshard=False)` (the engine's existing
constructor argument; a throw-away probe, not a flag of the program), which
stays at the bootstrap's first-byte split.

Prints one JSON line; exits 1 on a mismatched verdict, an overflow or fewer
than three automatic re-splits. On the CPU backend (JAX_PLATFORMS=cpu, four
or more host devices) it is a rehearsal: use small `--zipf-batches`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

BATCH = 512
STEP = 10_000  # versions a batch
WINDOW = 5_000_000  # versions: the MVCC window
LAG = 2  # batches: a read version is the commit version two batches back
LOAD_BATCHES = 8
RECORDS = 50_000
BASE_SEED = 2301  # benchmark/traffic/f_closed_64.json


def epochs_of(seed: int, epochs: int, zipf_batches: int):
    """[(kind, [key] * 512)] per epoch: LOAD_BATCHES of ascending
    never-seen keys, then the plan's read-modify-writes."""
    from benchmark.lib import ycsb

    records = ycsb.Records(RECORDS, seed)
    ordered = sorted(records.keys)
    n_rmw = epochs * zipf_batches * BATCH
    kinds, items = ycsb.plan(records.count, 2 * n_rmw + 4096, 0.5, seed,
                             BASE_SEED)
    rmw = [int(i) for k, i in zip(kinds, items) if k == ycsb.RMW]
    assert len(rmw) >= n_rmw, (len(rmw), n_rmw)
    out, at = [], 0
    for e in range(epochs):
        batches = []
        first = e * LOAD_BATCHES * BATCH
        for b in range(LOAD_BATCHES):
            lo = first + b * BATCH
            batches.append(("load", ordered[lo:lo + BATCH]))
        for _ in range(zipf_batches):
            batches.append(("plan", [records.keys[i]
                                     for i in rmw[at:at + BATCH]]))
            at += BATCH
        out.append(batches)
    return out


def drive(cs, stream, judge: bool) -> dict:
    """The stream through `cs`; with `judge`, every verdict against the
    reference. Returns counts, the engine's split policy's own numbers and
    wall milliseconds a batch (dispatch to verdicts, collected at once)."""
    from benchmark.lib import reference
    from foundationdb_tpu.core.types import KeyRange, TxnConflictInfo

    last_write: dict = {}
    version, versions = 0, []
    verdicts = mismatched = conflicts = too_old = 0
    ms, resplit_at = [], []
    for batches in stream:
        version += WINDOW + 1_000_000  # the gap: the history empties
        for kind, keys in batches:
            version += STEP
            versions.append(version)
            oldest = max(0, version - WINDOW)
            back = versions[-1 - LAG] if len(versions) > LAG else 0
            rvs = [back] * len(keys)
            txns = [TxnConflictInfo(
                read_version=rv, read_ranges=[KeyRange(k, k + b"\x00")],
                write_ranges=[KeyRange(k, k + b"\x00")])
                for k, rv in zip(keys, rvs)]
            before = cs.auto_reshards
            t0 = time.perf_counter()
            got = cs.resolve_async(txns, version, oldest)()
            ms.append((time.perf_counter() - t0) * 1e3)
            if cs.auto_reshards != before:
                resplit_at.append((len(versions) - 1, kind))
            verdicts += len(got)
            if judge:
                want = reference.point_verdicts(
                    last_write, keys, rvs, version, oldest)
                mismatched += sum(1 for g, w in zip(got, want)
                                  if int(g) != w)
                conflicts += want.count(reference.CONFLICT)
                too_old += want.count(reference.TOO_OLD)
    rows = cs.shard_occupancy()
    cs.headroom()  # a capacity reading: cs.hist_merges is as of the last
    ms_sorted = sorted(ms)
    return {
        "batches": len(ms), "verdicts": verdicts, "mismatched": mismatched,
        "reference_conflicts": conflicts, "reference_too_old": too_old,
        "overflowed": bool(cs.overflowed),
        "auto_reshards": cs.auto_reshards, "resplit_at": resplit_at,
        "reshard_probes": cs.reshard_probes,
        "reshard_probe_ms_mean": round(
            cs.reshard_probe_s / max(1, cs.reshard_probes) * 1e3, 3),
        "reshard_ms_mean": round(
            cs.reshard_s / max(1, cs.auto_reshards) * 1e3, 3),
        "shard_rows_in_use": rows,
        "shard_fullest_pct": round(100.0 * max(rows) / sum(rows), 3),
        "batch_ms_p50": round(ms_sorted[len(ms) // 2], 3),
        "batch_ms_max": round(ms_sorted[-1], 3),
        "hist_merges": cs.hist_merges,
        "compiles": (cs.dict_stats or {}).get("compiles"),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="scripts/mesh_parity.py")
    ap.add_argument("--seed", type=int, default=4200000001)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--zipf-batches", type=int, default=130)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from foundationdb_tpu.parallel.sharded_resolver import ShardedConflictSet
    from foundationdb_tpu.server import make_conflict_set

    t0 = time.perf_counter()
    stream = epochs_of(args.seed, args.epochs, args.zipf_batches)
    cs = make_conflict_set("tpu", mesh=4)
    assert type(cs) is ShardedConflictSet and cs.auto_reshard
    warm = cs.warm_up()
    out = {"seed": args.seed, "device": cs.device_info(), "warm_up_s": warm,
           "served": drive(cs, stream, judge=True)}
    fixed = ShardedConflictSet(n_shards=4, auto_reshard=False)
    out["auto_reshard_off"] = {
        k: v for k, v in drive(fixed, stream[:1], judge=False).items()
        if k in ("batches", "auto_reshards", "shard_rows_in_use",
                 "shard_fullest_pct", "overflowed", "batch_ms_p50")}
    out["wall_s"] = round(time.perf_counter() - t0, 1)
    s = out["served"]
    out["ok"] = (s["mismatched"] == 0 and not s["overflowed"]
                 and s["auto_reshards"] >= 3)
    line = json.dumps(out)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
