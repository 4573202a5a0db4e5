"""Driver `resolver_replay_tpcc`: one resolver's share of a cluster that runs
TPC-C, replayed at the resolver's own rate.

`resolver_replay_mako` with another stream (benchmark/lib/tpcc.py): every
batch holds new-orders (26-66 point ranges, 2-5 engine rows), payments (7
or 8 ranges of which one may be a true range, one row) and deliveries
(120-220 ranges of which 20 are true, 10-23 rows) side by side, so a batch
of 512 is some 17,100 ranges in 1,455 rows and three or four dispatches of
unequal transaction counts. The role, its launcher, the pump with batches
built ahead of their turn, the set-up that runs to the dictionary's first
full repack, the fixed version step a batch and the window (from nothing in
flight to nothing in flight) are that driver's and `resolver_replay`'s,
imported; what differs is here:

- The plain reference is benchmark/lib/reference_prefix.py: the rule of
  reference_ranges.py, which keeps ONE sorted list of written keys and
  would take minutes over this stream's 1.5 million.
- `correct`, besides mako's comparisons: the role counted as many TRUE
  ranges (end other than begin + "\\x00") as were sent, so none arrived as
  a point or widened; the engine's codec widened no key (and the generator
  made none longer than `max_key_bytes`).
- `--control ranges_as_points` sends every true range as its begin's
  point while the reference judges the stream as dealt: the run that must
  come out not correct.
- `reference_verdicts_on_true_ranges`: the reference judges the stream a
  second time with every true range read as its begin's point, and prints
  how many verdicts differ. At the source's 228,200 warehouses that is a
  handful a run or none (only a delivery whose district another delivery
  of the lag window emptied), so THIS cell holds the role to sending and
  counting true ranges and times the kernel on them; that the engine
  judges an interval exactly is held by the CPU tests on this generator's
  stream at 8 and 40 warehouses (tests/test_tpcc_stream_parity.py, the
  rehearsal), where a third of the verdicts are conflicts.

A program whose resolver reports no `true_ranges_received` in
`get_metrics()` is refused before anything is started (mako's
`counts_wide_txns` pattern): three of the cell's declared metrics have
nothing to read there and a traced run could not meet the contract.
"""

from __future__ import annotations

import numpy as np

from benchmark.drivers import resolver_replay_mako as mako
from benchmark.drivers.resolver_replay import (
    MVCC_WINDOW_VERSIONS,
    VERSIONS_PER_SECOND,
    ResolverProcess,
    _watch,
)
from benchmark.lib import observe, tpcc
from benchmark.lib.control import Control
from benchmark.lib.hist import percentile_of
from benchmark.lib.loadgen import pc
from benchmark.lib.reference_prefix import (
    CONFLICT,
    TOO_OLD,
    PrefixHistory,
    prefix_verdicts,
    reads_as_points,
)

EXTRA_COUNTERS = mako.EXTRA_COUNTERS + ("true_ranges_received",
                                        "slots_filled")
ENGINE_EXTRA = mako.ENGINE_EXTRA + ("dispatches", "keys_widened")
KINDS = ("new_order", "payment", "delivery")


class Stream:
    """The batches of one run: tpcc.Deal cut into batches of the engine's
    `batch_size`, under `resolver_replay`'s versions. Batches are dealt in
    turn (the districts' order counters move with them); `replay()` deals
    the same stream again for the reference."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, n_batches: int,
                 as_points: bool = False, deal: "tpcc.Deal | None" = None):
        self.cfg, self.traffic = cfg, traffic
        self.batch = cfg["engine"]["batch_size"]
        self.step = int(round(self.batch / cfg["nominal_rate_per_s"]
                              * VERSIONS_PER_SECOND))
        self.lag = traffic["read_version_lag_batches"] * self.step
        self.n_batches = n_batches
        self.as_points = as_points
        self.deal = deal or tpcc.Deal(
            cfg["warehouses"], [traffic["base_seed"], seed],
            n_batches * self.batch)
        self.kinds = np.zeros(len(KINDS), np.int64)

    def replay(self) -> "Stream":
        return Stream(self.cfg, self.traffic, 0, self.n_batches,
                      deal=self.deal.replay())

    def version(self, n: int) -> int:
        return (n + 1) * self.step

    def read_version(self, n: int) -> int:
        return max(0, self.version(n) - self.lag)

    def oldest(self, n: int) -> int:
        return max(0, self.version(n) - MVCC_WINDOW_VERSIONS)

    def batch_pairs(self, n: int) -> list:
        """What the reference takes: (read version, reads, writes)."""
        rv = self.read_version(n)
        dealt = self.deal.batch(n, self.batch)
        self.kinds += np.bincount([k for k, _r, _w in dealt],
                                  minlength=len(KINDS))
        return [(rv, reads, writes) for _kind, reads, writes in dealt]

    def batch_txns(self, n: int) -> list:
        from foundationdb_tpu.core.types import KeyRange, TxnConflictInfo

        def sent(b: bytes, e: bytes) -> KeyRange:
            return KeyRange(b, b + b"\x00" if self.as_points else e)

        return [TxnConflictInfo(
            read_version=rv,
            read_ranges=[sent(b, e) for b, e in reads],
            write_ranges=[KeyRange(b, e) for b, e in writes])
            for rv, reads, writes in self.batch_pairs(n)]


class Observer(observe.Observer):
    """observe.Observer, with the counters this cell's metrics read where
    the program has them (one `get_metrics()` a reading, so the counters of
    a reading belong to one moment)."""

    async def counters(self) -> dict:
        m = await self.resolver_ep.get_metrics()
        out = {k: m[k] for k in observe.COUNTERS}
        out.update({k: m[k] for k in EXTRA_COUNTERS if k in m})
        engine = m["engine"]
        out.update({k: engine[k] for k in
                    observe.ENGINE_COUNTERS + ENGINE_EXTRA if k in engine})
        return out


def counts_true_ranges(loop) -> bool:
    """Whether the program's resolver role reports `true_ranges_received`
    among its `get_metrics()`: asked of a role built here over no engine at
    all, so nothing is started and no device is touched."""
    from foundationdb_tpu.runtime.resolver import Resolver

    return "true_ranges_received" in loop.run(
        Resolver(loop, object()).get_metrics(), timeout=10)


def run(ctx) -> dict:
    from foundationdb_tpu.runtime.net import NetTransport, RealLoop

    loop = RealLoop()
    if not counts_true_ranges(loop):
        raise RuntimeError(
            "this program's resolver reports no `true_ranges_received` in "
            "get_metrics(): nothing says whether a true range arrived as "
            "one, how many dispatches a batch took or how much of a "
            "dispatch was padding, and this cell's metrics read all three")
    cfg, traffic = ctx.config, ctx.traffic
    if ctx.control not in (None, "ranges_as_points"):
        raise ValueError(f"no control {ctx.control!r} in this driver")
    env = {"FDB_TPU_OBS": "1", "FDB_TPU_OBS_SAMPLE": str(
        traffic.get("obs_sample", 1))} if ctx.trace else {}
    depth = traffic["batches_in_flight"]
    engine = cfg["engine"]
    batch = engine["batch_size"]
    step_s = batch / cfg["nominal_rate_per_s"]
    n_prefill = int(np.ceil(
        MVCC_WINDOW_VERSIONS / VERSIONS_PER_SECOND / step_s))
    max_prefill = cfg["prefill_at_most_windows"] * n_prefill
    # Enough batches for set-up and for the window at several times the
    # nominal rate, all drawn now; this stream does not wrap.
    n_batches = max_prefill + int(np.ceil(
        ctx.seconds / step_s * traffic["plan_rate_factor"])) + 3 * depth
    stream = Stream(cfg, traffic, ctx.seed, n_batches,
                    as_points=ctx.control == "ranges_as_points")
    ctx.log(f"the deal is made: {n_batches} batches, "
            f"{pc() - ctx.t0:.1f}s after launch")
    out: dict = {"checks": []}
    proc = ResolverProcess(ctx.root, ctx.workdir, ctx.config_path, env)
    control = Control(proc.control_dir)
    proc.start()
    ctx.log(f"resolver up {pc() - ctx.t0:.1f}s after launch")
    with open(proc.log_path, errors="replace") as f:
        for line in f:
            if line.startswith("device "):  # what it compiled, and how long
                ctx.log(line.strip())
    t = NetTransport(loop)
    try:
        addr = ("127.0.0.1", proc.port)
        ep, admin = t.endpoint(addr, "resolver"), t.endpoint(addr, "admin")
        observer = Observer(loop, control, ep, [admin])
        marks: dict = {}

        async def window():
            watch = loop.spawn(_watch(loop, observer, marks, ctx.seconds,
                                      traffic.get("trace_s", 3.0)),
                               name="bench.observer") if ctx.trace else None
            rows = await mako.pump(loop, ep, stream, depth, n_prefill,
                                   max_prefill, ctx.seconds, marks)
            return rows, (await watch) if watch is not None else None

        rows, watched = loop.run(window(), timeout=ctx.seconds + 900)
        t_start, t_stop = marks["t_start"], marks["t_stop"]
        out["setup_s"] = t_start - ctx.t0
        timed = [r for r in rows if t_start < r[2] <= t_stop]
        rtt_ms = [(r[2] - r[1]) * 1e3 for r in timed]
        replies = sorted(r[2] for r in timed)
        counters = loop.run(observer.counters(), timeout=30)
        out["attempted"] = len(timed) * batch

        # -- outside the timed window: the plain reference, in order -------
        # (the same stream dealt a second time, which draws nothing)
        again = stream.replay()
        history = PrefixHistory(tpcc.PREFIX_LEN)
        # ... and once more with every true range read as its begin's
        # point, a history of its own: what an engine that judged no
        # interval would answer. The verdicts that differ are all that
        # `verdicts_wrong` holds the engine's INTERVALS to in this run.
        narrowed = PrefixHistory(tpcc.PREFIX_LEN)
        wrong = conflicts = too_old = on_true_ranges = 0
        for n, _t0, _t1, got, _fs in rows:
            pairs = again.batch_pairs(n)
            ref = prefix_verdicts(history, pairs,
                                  again.version(n), again.oldest(n))
            as_points = prefix_verdicts(narrowed, reads_as_points(pairs),
                                        again.version(n), again.oldest(n))
            on_true_ranges += sum(1 for a, b in zip(ref, as_points) if a != b)
            wrong += sum(1 for a, b in zip(got, ref) if a != b)
            wrong += abs(len(got) - len(ref))
            conflicts += sum(1 for v in ref if v == CONFLICT)
            too_old += sum(1 for v in ref if v == TOO_OLD)
        sent = len(rows) * batch
        deal = again.deal
        out["generator"] = {
            "window_s": t_stop - t_start,
            "batches": len(timed),
            "resolved_per_s": len(timed) * batch / (t_stop - t_start),
            "resolve_p50_ms": percentile_of(rtt_ms, 50),
            "resolve_p95_ms": percentile_of(rtt_ms, 95),
            # whether the rate is flat through the window: replies per 10 s
            "batches_by_10s": np.histogram(
                [r[2] - t_start for r in timed],
                bins=np.arange(0.0, t_stop - t_start + 10.0, 10.0)
            )[0].tolist(),
            # the longest stretch of the window in which no reply came
            "longest_reply_gap_s": float(np.max(np.diff(
                [t_start] + replies))) if replies else 0.0,
            "prefill_batches": marks["prefill_batches"],
            "prefill_batches_one_window": n_prefill,
            "prefill_s": t_start - rows[0][1],
            # batches built on the timed path after all (0 is the aim)
            "built_late": marks["built_late"],
            # what was sent, set-up and window together: the mix, and the
            # cards dealt and dropped on the way
            "sent": dict(zip(KINDS, again.kinds.tolist())),
            **deal.dropped(),
            "ranges_per_txn_sent": deal.ranges / sent,
            "true_ranges_per_txn_sent": deal.true_ranges / sent,
            "longest_key_bytes": deal.longest_key,
            "reference_conflict_share": conflicts / sent,
            # since boot, set-up and window together
            "since_boot": {k: counters[k] for k in (
                "full_repacks",) + ENGINE_EXTRA + EXTRA_COUNTERS
                if k in counters},
        }
        out["failed"] = min(wrong, out["attempted"])
        out["checks"] += [
            ("verdicts_wrong", wrong, 0),
            ("verdicts_compared", sent, None),
            ("reference_conflicts", conflicts, None),
            ("reference_verdicts_on_true_ranges", on_true_ranges, None),
            # a nominal rate under lag x batch / 5 s puts every read version
            # behind the MVCC window: nothing is judged, nothing painted
            ("reference_too_old", too_old, 0),
            ("batches_fail_safe", sum(1 for r in rows if r[4]), 0),
            ("batches_out_of_order",
             int([r[0] for r in rows] != list(range(len(rows)))), 0),
            ("built_late", marks["built_late"], None),
            # every range sent reached the role, and every true range as
            # a true range: none became a point, none was widened
            ("ranges_not_received",
             abs(deal.ranges - counters["ranges_received"]), 0),
            ("true_ranges_not_received",
             abs(deal.true_ranges - counters["true_ranges_received"]), 0),
            ("keys_widened", counters["keys_widened"], 0),
            ("keys_longer_than_the_engine_takes",
             max(0, deal.longest_key - engine["max_key_bytes"]), 0),
        ]
        for name in ("overflow_events", "txns_rejected_fail_safe",
                     "resolve_failures"):
            out["checks"].append((name, counters[name], 0))
        out["sources"] = {} if watched is None else observe.window_sources(
            watched, control, ctx.fixture)
        window_counters = out["sources"].get("counters")
        if window_counters and window_counters["rows_dispatched"]:
            # the share of the dispatched rows' slots that hold a range
            window_counters["slot_fill_pct"] = (
                100.0 * window_counters["slots_filled"]
                / (window_counters["rows_dispatched"]
                   * (engine["max_read_ranges"]
                      + engine["max_write_ranges"])))
        out["device"] = control.call("report")
        code, killed = proc.shutdown(loop, admin)
    except BaseException:
        proc.kill()
        raise
    finally:
        t.close()
    out["checks"] += [("roles_exit_nonzero", int(code != 0), 0),
                      ("roles_killed", int(killed), 0)]
    return out
