"""Driver `cluster_nr`: driver `cluster`'s YCSB operations through a
deployed cluster with SEVERAL resolvers, each a process of its own bound to
one chip, the keys split between them where the configuration's
`deployment.resolver_splits` says.

The client, the load, the read-back and the generator's statistics are
driver `cluster`'s own, imported. What several resolvers force is here: a
control directory a launcher (benchmark/lib/cluster_nr.py), the counters of
every role, the traces of every chip taken over the same seconds and merged
(benchmark/lib/observe_nr.py).

`correct` is driver `cluster`'s, held over every role: every record a
read-modify-write touched read back from each storage replica holding
exactly the acknowledged increments, the untouched sample as loaded, the
resolvers' failure counters 0 in sum, every process exit 0. And one check
more, `ranges_share_fullest_resolver_pct`: the share of the conflict ranges
of this run's traffic (warm-up and window; the load is left out) that the
fullest resolver was sent, against the configuration's limit. A run above
it did not run the stated deployment: under `KeyShardMap.uniform`, which
splits by first byte, every `"user..."` key is one resolver's (100 %).
"""

from __future__ import annotations

import numpy as np

from benchmark.drivers.cluster import (
    YcsbClient,
    load_records,
    read_replicas,
    summarize,
)
from benchmark.lib import loadgen, observe_nr, ycsb
from benchmark.lib.control import Control
from benchmark.lib.loadgen import pc
from benchmark.lib.reference import CounterReplay


def device_lines(cluster) -> list:
    """The `device <name> engine=... platform=... warm_up_s=...` line each
    resolver process printed before `ready` (server.make_engine)."""
    out = []
    for p in cluster.procs:
        if p.role == "resolver":
            with open(p.log_path, errors="replace") as f:
                out += [ln.strip() for ln in f if ln.startswith("device ")]
    return out


def run(ctx) -> dict:
    # A program without a resolver map that follows the spec cannot run
    # this deployment: refuse before any process is started.
    from foundationdb_tpu.server import parse_addr, resolver_shard_map

    from benchmark.lib.cluster_nr import BenchClusterNR
    from foundationdb_tpu.obs.span import SpanSink

    cfg, traffic = ctx.config, ctx.traffic
    dep = cfg["deployment"]
    n_res = dep["resolvers"]
    splits = [s.encode() for s in dep["resolver_splits"]]
    obs_sample = traffic.get("obs_sample", 4)
    env = {"FDB_TPU_OBS": "1",
           "FDB_TPU_OBS_SAMPLE": str(obs_sample)} if ctx.trace else {}
    records = ycsb.Records(cfg["recordcount"], ctx.seed)
    replay = CounterReplay(records)
    warm_s = float(traffic.get("warm_up_s", 2.0))
    n_ops = int((warm_s + ctx.seconds) * traffic["plan_ops_per_s"])
    kinds, items = ycsb.plan(records.count, n_ops, traffic["rmw_share"],
                             ctx.seed, traffic["base_seed"])
    out: dict = {"checks": []}

    cluster = BenchClusterNR(
        ctx.workdir, proxies=dep["proxies"], tlogs=dep["tlogs"],
        storages=dep["storages"], resolvers=n_res,
        ratekeeper=dep["ratekeeper"], engine=dep["engine"],
        data_dirs=dep["data_dirs"], resolver_splits=splits,
        spec_extra={"replicas": dep["replicas"]}, env=env)
    resolver_shard_map(cluster.spec)  # the spec as written must boot
    controls = [Control(cluster.resolver_control_dir(i))
                for i in range(n_res)]
    with cluster:
        ctx.log(f"cluster up {pc() - ctx.t0:.1f}s after launch")
        for line in device_lines(cluster):
            ctx.log(line)  # which device each resolver sits on, its warm-up
        loop, t, db = cluster.open_client()
        try:
            sink = SpanSink(loop, sample_every=obs_sample) \
                if ctx.trace else None
            client = YcsbClient(
                db, records, replay, kinds, items,
                timeout_ms=traffic["timeout_ms"],
                retry_limit=traffic.get("retry_limit"),
                snapshot_rmw=ctx.control == "snapshot_rmw")
            t_load = pc()
            loop.run(load_records(loop, db, records, cfg["load_width"],
                                  cfg["load_in_flight"]), timeout=900)
            out["load_s"] = pc() - t_load
            ctx.log(f"loaded {records.count} records in {out['load_s']:.1f}s")
            observer = observe_nr.ObserverNR(
                loop, controls,
                [t.endpoint(parse_addr(a), "resolver")
                 for a in cluster.spec["resolver"]],
                [cluster.admin_ep(t, p.name) for p in cluster.procs], sink)
            loaded = loop.run(observer.counters(), timeout=30)

            t_gen = pc()
            t_start, t_stop = t_gen + warm_s, t_gen + warm_s + ctx.seconds

            async def window():
                watch = loop.spawn(observer.watch_window(
                    t_start, t_stop, traffic.get("trace_s", 3.0), pc),
                    name="bench.observer") if ctx.trace else None
                rows = await loadgen.closed_loop(
                    loop, client.op, traffic["clients"], t_stop)
                return rows, (await watch) if watch is not None else None

            rows, watched = loop.run(window(), timeout=ctx.seconds + 240)
            out["setup_s"] = t_start - ctx.t0
            gen = summarize(rows, kinds, t_start, t_stop,
                            traffic["commit_limit_ms"])
            out["generator"] = gen
            out["attempted"], out["failed"] = gen["attempted"], gen["failed"]

            # -- outside the timed window: hold the cluster to its word ----
            touched = replay.touched()
            rng = np.random.default_rng([ctx.seed, 0x53414D50])
            untouched = np.setdiff1d(
                rng.choice(records.count, min(records.count,
                                              traffic["sample_untouched"]),
                           replace=False), touched).tolist()
            ids = touched + untouched
            replicas = loop.run(read_replicas(
                db, t, cluster.spec, [records.keys[i] for i in ids]),
                timeout=300)
            for r, values in enumerate(replicas):
                wrong, why = replay.count_wrong(ids, values)
                if why:
                    ctx.log(f"storage{r}: {why}")
                out["checks"].append(
                    (f"records_wrong_storage{r}", wrong, 0))
            out["checks"].append(("records_compared", len(ids), None))
            out["checks"].append(("reads_wrong", client.reads_wrong, 0))
            counters = loop.run(observer.counters(), timeout=30)
            for name in ("overflow_events", "txns_rejected_fail_safe",
                         "resolve_failures"):
                out["checks"].append((name, counters[name], 0))
            sent = observe_nr.counters_between(
                loaded, counters)["per_role"]["ranges_received"]
            # on the printed `generator` line: the one place an untraced
            # run shows what each resolver was sent and each chip holds
            gen["ranges_received"] = sent
            check = cfg["checks"]["ranges_share_fullest_resolver_pct"]
            out["checks"].append((
                "ranges_share_fullest_resolver_pct",
                round(observe_nr.share_fullest_pct(sent), 3),
                check["limit"]))
            reports = observe_nr.call_all(controls, "report")
            if watched is not None:
                out["sources"] = observe_nr.window_sources(
                    watched, controls, reports, ctx.fixture)
            out["device"] = observe_nr.merge_reports(reports)
            gen["chips"] = [dict(r, resolver=i)
                            for i, r in enumerate(reports)]
        finally:
            t.close()
        stopped = cluster.shutdown()
    bad = [n for n, rc in stopped["exit_codes"].items() if rc != 0]
    out["checks"].append(("roles_exit_nonzero", len(bad), 0))
    out["checks"].append(("roles_killed", len(stopped["killed"]), 0))
    return out
