"""Driver `cluster`: YCSB operations through a deployed cluster, the resolver
on the chip and every other role off JAX, from the client's side.

The configuration file gives the deployment and the record count, the traffic
file the mix and the closed loop's parameters. One operation is
one transaction: a read is a `get` at a fresh read version, a
read-modify-write reads the record, increments the counter in field0,
rewrites the field and commits, retrying on a conflict.

`correct`: after the window every record a read-modify-write touched is read
back from each storage replica and must hold exactly the acknowledged
increments (benchmark/lib/reference.py `CounterReplay`); a seeded sample of
untouched records must be as loaded; the resolver's failure counters must be
0; every process must exit 0.
"""

from __future__ import annotations

import numpy as np

from benchmark.lib import loadgen, observe, ycsb
from benchmark.lib.control import Control
from benchmark.lib.hist import percentile_of
from benchmark.lib.loadgen import OK, pc
from benchmark.lib.reference import CounterReplay

UNKNOWN = "unknown_result"
TIMED_OUT = "timed_out"
FAILED = "failed"


class YcsbClient:
    """The YCSB operations of one run over the program's client library."""

    def __init__(self, db, records, replay: CounterReplay, kinds, items,
                 timeout_ms: int, retry_limit: "int | None",
                 snapshot_rmw: bool = False):
        self.db = db
        self.records = records
        self.replay = replay
        self.kinds = kinds
        self.items = items
        self.timeout_ms = timeout_ms
        self.retry_limit = retry_limit
        # The control (tests and PERF.md's control runs only): the
        # read-modify-write reads at snapshot isolation, so the resolver is
        # told of no read and concurrent increments are lost.
        self.snapshot_rmw = snapshot_rmw
        self.reads_wrong = 0

    def _transaction(self):
        tr = self.db.transaction()
        tr.set_option("timeout", self.timeout_ms)
        if self.retry_limit is not None:
            tr.set_option("retry_limit", self.retry_limit)
        return tr

    async def op(self, k: int):
        from foundationdb_tpu.core.errors import (
            FdbError,
            NotCommitted,
            TransactionTimedOut,
        )

        n = k % len(self.kinds)
        i = int(self.items[n])
        key = self.records.keys[i]
        rmw = self.kinds[n] == ycsb.RMW
        tr = self._transaction()
        retries = 0
        try:
            while True:
                try:
                    value = await tr.get(key, snapshot=rmw and self.snapshot_rmw)
                    if value is None or len(value) != ycsb.RECORD_BYTES:
                        self.reads_wrong += 1
                        return FAILED, retries
                    if not rmw:
                        return OK, retries
                    count = int.from_bytes(value[: ycsb.COUNTER_BYTES], "big")
                    tr.set(key, ycsb.field0(key, count + 1)
                           + value[ycsb.FIELD_LENGTH:])
                except FdbError as e:
                    retries += 1
                    await tr.on_error(e)  # raises when out of budget
                    continue
                try:
                    await tr.commit()
                except NotCommitted as e:
                    retries += 1
                    await tr.on_error(e)
                    continue
                except Exception:  # noqa: BLE001 — the commit may have landed
                    # a timeout inside the commit too: its result is unknown
                    self.replay.unknown_result(i)
                    return UNKNOWN, retries
                self.replay.ack(i)
                return OK, retries
        except TransactionTimedOut:
            return TIMED_OUT, retries
        except FdbError:
            return FAILED, retries


async def load_records(loop, db, records, width: int, in_flight: int) -> None:
    """Every record through the normal commit path, `width` to a
    transaction, `in_flight` transactions at a time."""
    from foundationdb_tpu.runtime.flow import all_of

    n_txns = -(-records.count // width)

    async def worker(w: int) -> None:
        for n in range(w, n_txns, in_flight):
            ids = range(n * width, min((n + 1) * width, records.count))
            pairs = [(records.keys[i], records.value(i)) for i in ids]

            async def body(tr, pairs=pairs) -> None:
                for key, value in pairs:
                    tr.set(key, value)

            await db.run(body)

    await all_of([loop.spawn(worker(w), name=f"bench.load{w}")
                  for w in range(in_flight)])


async def read_replicas(db, t, spec, keys: list) -> list:
    """`keys` from each storage replica's own serve path, at one version."""
    from foundationdb_tpu.server import parse_addr

    version = await db.transaction().get_read_version()
    out = []
    for addr in spec["storage"]:
        ep = t.endpoint(parse_addr(addr), "storage")
        values = []
        for lo in range(0, len(keys), 1000):
            values.extend(await ep.get_multi(keys[lo:lo + 1000], version))
        out.append(values)
    return out


def summarize(rows, kinds, t_start: float, t_stop: float,
              commit_limit_ms: float) -> dict:
    """The generator's numbers for the window [t_start, t_stop]: the
    operations that ENDED in it, timed from their send. A read-modify-write
    that failed or timed out counts at its elapsed time, and never as
    inside a limit."""
    k = np.asarray(rows.k)
    sent, end = np.asarray(rows.sent), np.asarray(rows.end)
    status = np.asarray(rows.status)
    retries = np.asarray(rows.retries)
    in_window = (end >= t_start) & (end < t_stop)
    is_rmw = kinds[k % len(kinds)] == ycsb.RMW
    ok = status == OK
    ms = (end - sent) * 1e3
    window_s = t_stop - t_start
    commits = in_window & is_rmw & ok
    rmw_all = in_window & is_rmw
    reads = in_window & ~is_rmw & ok
    out = {
        "window_s": window_s,
        "attempted": int(in_window.sum()),
        "failed": int((in_window & ~ok).sum()),
        "commits": int(commits.sum()),
        "commits_per_s": float(commits.sum() / window_s),
        "reads_per_s": float(reads.sum() / window_s),
        "retries": int(retries[rmw_all].sum()),
        # whether the rate is flat through the window: commits per 10 s
        "commits_by_10s": np.histogram(
            end[commits] - t_start,
            bins=np.arange(0.0, window_s + 10.0, 10.0))[0].tolist(),
    }
    if commits.any():
        out["retries_per_commit"] = out["retries"] / out["commits"]
    if rmw_all.any():
        for q in (50, 95, 99):
            out[f"commit_p{q}_ms"] = percentile_of(ms[rmw_all], q)
        out["commit_in_limit_pct"] = float(
            (commits & (ms <= commit_limit_ms)).sum() / rmw_all.sum() * 100.0)
    if reads.any():
        for q in (50, 95):
            out[f"read_p{q}_ms"] = percentile_of(ms[reads], q)
    return out


def run(ctx) -> dict:
    from benchmark.lib.cluster import BenchCluster
    from foundationdb_tpu.obs.span import SpanSink
    from foundationdb_tpu.server import parse_addr

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

    cluster = BenchCluster(
        ctx.workdir, proxies=dep["proxies"], tlogs=dep["tlogs"],
        storages=dep["storages"], resolvers=dep["resolvers"],
        ratekeeper=dep["ratekeeper"], engine=dep["engine"],
        data_dirs=dep["data_dirs"],
        spec_extra={"replicas": dep["replicas"]}, env=env)
    control = Control(cluster.control_dir)
    with cluster:
        ctx.log(f"cluster up {pc() - ctx.t0:.1f}s after launch")
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
            observer = observe.Observer(
                loop, control, res_ep,
                [cluster.admin_ep(t, p.name) for p in cluster.procs], sink)

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
            if watched is not None:
                out["sources"] = observe.window_sources(
                    watched, control, ctx.fixture)
            out["device"] = control.call("report")
        finally:
            t.close()
        stopped = cluster.shutdown()
    bad = [n for n, rc in stopped["exit_codes"].items() if rc != 0]
    out["checks"].append(("roles_exit_nonzero", len(bad), 0))
    out["checks"].append(("roles_killed", len(stopped["killed"]), 0))
    return out
