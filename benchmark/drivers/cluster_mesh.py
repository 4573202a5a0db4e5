"""Driver `cluster_mesh`: driver `cluster`'s YCSB operations through a
deployed cluster whose ONE resolver process spans several chips: the spec's
`resolver_mesh` (`deployment.resolver_mesh` here), upstream's `configure
resolvers=<N>` served as one history sharded by key range over N chips, the
conflict bits summed on the device before anything is painted, the splits
following the live history by themselves.

The client, the load, the read-back and the generator's statistics are
driver `cluster`'s own, imported. What the mesh forces is here: a launcher
whose trace reduction knows one plane a chip (benchmark/lib/mesh_proc.py),
and an `Observer` of its own with the counters of the engine's split policy.

`correct` is driver `cluster`'s (every record a read-modify-write touched
read back from each storage replica holding exactly the acknowledged
increments, the untouched sample as loaded, the resolver's failure counters
0, every process exit 0) and two checks more, against the configuration's
limits: `shard_fullest_pct`, the fullest shard's share of the history rows
in use AT the window's end (at the bootstrap's first-byte split every
`"user..."` key is one shard's: 100), and `never_resplit`, 1 where
`auto_reshards_since_boot` (printed beside it) is under the configuration's
`at_least`. A run outside either did not run the stated deployment.
"""

from __future__ import annotations

import numpy as np

from benchmark.drivers.cluster import (
    YcsbClient,
    load_records,
    read_replicas,
    summarize,
)
from benchmark.drivers.cluster_nr import device_lines
from benchmark.lib import loadgen, observe, ycsb
from benchmark.lib.control import Control
from benchmark.lib.hist import stages_between
from benchmark.lib.loadgen import pc
from benchmark.lib.reference import CounterReplay

ENGINE_EXTRA = ("reshard_probes", "reshard_probe_s", "reshard_s")
SHUTDOWN_S = 60.0  # for every role to exit once asked to
ROWS = "shard_rows_in_use"  # a list, one a shard: kept, never subtracted


class Observer(observe.Observer):
    """observe.Observer, with the counters of the mesh engine's split
    policy where the program has them."""

    async def counters(self) -> dict:
        m = await self.resolver_ep.get_metrics()
        out = {k: m[k] for k in observe.COUNTERS}
        engine = m["engine"]
        out.update({k: engine[k] for k in
                    observe.ENGINE_COUNTERS + ENGINE_EXTRA if k in engine})
        out[ROWS] = list(engine.get(ROWS, ()))
        return out


def fullest_pct(rows: list) -> float:
    """The fullest shard's share of the history rows in use, in %; 100
    where the engine reports none (no split was exercised)."""
    total = sum(rows)
    return 100.0 * max(rows) / total if total else 100.0


def window_sources(watched: dict, rows: list, control,
                   fixture: "str | None") -> dict:
    """`observe.window_sources`, the list of rows a shard left out of the
    differences; `rows` is that list as the window's end had it."""
    first, last = watched["first"], watched["last"]
    stopped = watched["stopped"]
    return {
        "spans": stages_between(first["dumps"], last["dumps"]),
        "counters": {k: last["counters"][k] - first["counters"][k]
                     for k in last["counters"] if k != ROWS},
        ROWS: rows,
        "shard_fullest_pct": fullest_pct(rows),
        "trace": control.call("reduce", timeout_s=300, fixture=fixture,
                              xplane=stopped["xplane"],
                              window_s=stopped["window_s"]),
    }


def run(ctx) -> dict:
    # A program whose spec has no key for the mesh cannot run this
    # deployment: refuse before any process is started.
    from foundationdb_tpu.server import parse_addr, resolver_mesh

    from benchmark.lib.cluster_mesh import BenchClusterMesh
    from foundationdb_tpu.obs.span import SpanSink

    cfg, traffic = ctx.config, ctx.traffic
    dep = cfg["deployment"]
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

    cluster = BenchClusterMesh(
        ctx.workdir, proxies=dep["proxies"], tlogs=dep["tlogs"],
        storages=dep["storages"], resolvers=dep["resolvers"],
        ratekeeper=dep["ratekeeper"], engine=dep["engine"],
        data_dirs=dep["data_dirs"],
        spec_extra={"replicas": dep["replicas"],
                    "resolver_mesh": dep["resolver_mesh"]}, env=env)
    # the spec as written must boot, and say what the configuration says
    assert resolver_mesh(cluster.spec) == dep["resolver_mesh"]
    control = Control(cluster.control_dir)
    with cluster:
        ctx.log(f"cluster up {pc() - ctx.t0:.1f}s after launch")
        for line in device_lines(cluster):
            ctx.log(line)  # how many chips the resolver holds, its warm-up
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
            res_ep = t.endpoint(parse_addr(cluster.spec["resolver"][0]),
                                "resolver")
            observer = Observer(
                loop, control, res_ep,
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
            # The shards' rows while the window's history is still there:
            # the read-back below takes seconds, in which the idle
            # cluster's empty batches expire all of it.
            at_end = loop.run(observer.counters(), timeout=30)
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
            checks = cfg["checks"]
            out["checks"].append((
                "shard_fullest_pct", round(fullest_pct(at_end[ROWS]), 3),
                checks["shard_fullest_pct"]["limit"]))
            resplits = counters["auto_reshards"]
            out["checks"].append(("auto_reshards_since_boot", resplits, None))
            out["checks"].append((
                "never_resplit",
                int(resplits < checks["auto_reshards_since_boot"]["at_least"]),
                0))
            # on the printed `generator` line: what an untraced run shows of
            # the mesh (rows a shard after the load and at the end, the
            # policy's probes and seconds since boot)
            gen["mesh"] = {
                "shard_rows_after_load": loaded[ROWS],
                "shard_rows_at_end": at_end[ROWS],
                "auto_reshards_in_load": loaded["auto_reshards"],
                **{k: counters[k] for k in ENGINE_EXTRA if k in counters}}
            if watched is not None:
                out["sources"] = window_sources(watched, at_end[ROWS],
                                                control, ctx.fixture)
                # a plane a chip: busy seconds, executions, collectives
                gen["mesh"]["trace"] = out["sources"]["trace"].get("mesh")
            out["device"] = control.call("report")
        finally:
            t.close()
        # One process gives four chips back to the runtime: 12.1-12.2 s on
        # the v5e host, and past the launcher's 15 s in the one run that
        # had compiled first (my chip runs, PR 42).
        t_down = pc()
        stopped = cluster.shutdown(timeout_s=SHUTDOWN_S)
        out["generator"]["mesh"]["shutdown_s"] = round(pc() - t_down, 1)
    bad = [n for n, rc in stopped["exit_codes"].items() if rc != 0]
    if bad or stopped["killed"]:
        ctx.log(f"exit codes {stopped['exit_codes']}, "
                f"killed {stopped['killed']}")
    out["checks"].append(("roles_exit_nonzero", len(bad), 0))
    out["checks"].append(("roles_killed", len(stopped["killed"]), 0))
    return out
