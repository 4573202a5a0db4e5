"""Driver `resolver_replay_mako`: one resolver's share of upstream's own load
tool, mako, at its documented `--transaction g8ui`, replayed at the
resolver's own rate.

`resolver_replay` with other transactions: every transaction is 8 GETs, 1
UPDATE (a get and a set of one row) and 1 INSERT (a set of a key never seen
before), rows chosen uniformly: 9 point read ranges and 2 point write
ranges, 11 conflict ranges against workload F's 2, and more reads than the
served engine's row has slots. The role, its launcher, the versions that
advance by a fixed step a batch, the batches in flight and the window (from
a moment with nothing in flight to a moment with nothing in flight) are
`resolver_replay`'s; what differs is here:

- Set-up sends one whole MVCC window of batches AND goes on until the
  engine's dictionary has had its first full repack, so the window opens
  inside the steady cycle of fill and repack and not before its first one.
- A batch's 512 x 11 `KeyRange`s are built before its turn to be sent (a
  queue of built batches, topped up while replies are awaited): the
  emulated proxies add no Python of their own between a reply and the next
  send.
- `correct`: every verdict of set-up and window equals the plain
  reference's for range lists (benchmark/lib/reference_ranges.py) on the
  same stream in the same order, no reply was marked fail-safe, the role's
  failure counters are 0, and the role was sent 11 ranges a transaction.

A program whose resolver reports no `wide_txns` in `get_metrics()` is
refused before anything is started (`counts_wide_txns`): before this
driver's PR the engine widened a transaction's ranges to fit one row and
counted none, so two of the cell's declared metrics have nothing to read
there and a traced run could not meet the contract. One that reports the
counter and still answers otherwise comes out not correct by the
comparisons above.
"""

from __future__ import annotations

import collections

import numpy as np

from benchmark.drivers.resolver_replay import (
    MVCC_WINDOW_VERSIONS,
    VERSIONS_PER_SECOND,
    ResolverProcess,
    _watch,
)
from benchmark.lib import observe
from benchmark.lib.control import Control
from benchmark.lib.hist import percentile_of
from benchmark.lib.loadgen import pc
from benchmark.lib.reference_ranges import (
    CONFLICT,
    RangeHistory,
    range_verdicts,
)

GETS = 8  # then 1 UPDATE and 1 INSERT: mako's "g8ui"
RANGES_PER_TXN = (GETS + 1) + 2
EXTRA_COUNTERS = ("ranges_received", "rows_dispatched", "wide_txns")
ENGINE_EXTRA = ("repacks_frag_due", "repacks_dict_full",
                "repacks_delta_overflow", "repack_s", "compiles")


def key_of(cfg: dict, num: int) -> bytes:
    """mako's key of row `num`: the prefix, the number in as many digits
    as `rows` has, padded with 'x' to `keylen` (mako.c genkey)."""
    digits = len(str(cfg["rows"]))
    key = b"mako%0*d" % (digits, num)
    return key + b"x" * (cfg["keylen"] - len(key))


class Stream:
    """The batches of one run, made from the seed: batch n has `batch`
    transactions of 8 GETs, 1 UPDATE and 1 INSERT."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, n_batches: int):
        self.cfg = cfg
        self.batch = cfg["engine"]["batch_size"]
        self.step = int(round(self.batch / cfg["nominal_rate_per_s"]
                              * VERSIONS_PER_SECOND))
        self.lag = traffic["read_version_lag_batches"] * self.step
        self.n_batches = n_batches
        rng = np.random.default_rng([traffic["base_seed"], seed])
        # uniform over `rows` (--zipf off); the stream wraps, the INSERTs'
        # keys do not
        self.picks = rng.integers(0, cfg["rows"],
                                  (n_batches, self.batch, GETS + 1),
                                  dtype=np.int32)

    def version(self, n: int) -> int:
        return (n + 1) * self.step

    def read_version(self, n: int) -> int:
        return max(0, self.version(n) - self.lag)

    def oldest(self, n: int) -> int:
        return max(0, self.version(n) - MVCC_WINDOW_VERSIONS)

    def batch_keys(self, n: int) -> list:
        """Per transaction (its nine rows' keys, its INSERT's key)."""
        cfg, rows = self.cfg, self.cfg["rows"]
        first_new = rows + n * self.batch  # never seen before, nor again
        return [([key_of(cfg, r) for r in picks], key_of(cfg, first_new + i))
                for i, picks in enumerate(
                    self.picks[n % self.n_batches].tolist())]

    def batch_pairs(self, n: int) -> list:
        """What the reference takes: (read version, reads, writes)."""
        rv = self.read_version(n)
        return [(rv, [(k, k + b"\x00") for k in ks],
                 [(ks[-1], ks[-1] + b"\x00"), (new, new + b"\x00")])
                for ks, new in self.batch_keys(n)]

    def batch_txns(self, n: int) -> list:
        from foundationdb_tpu.core.types import KeyRange, TxnConflictInfo

        return [TxnConflictInfo(
            read_version=rv,
            read_ranges=[KeyRange(b, e) for b, e in reads],
            write_ranges=[KeyRange(b, e) for b, e in writes])
            for rv, reads, writes in self.batch_pairs(n)]


class Observer(observe.Observer):
    """observe.Observer, with the counters this cell's metrics read where
    the program has them."""

    async def counters(self) -> dict:
        m = await self.resolver_ep.get_metrics()
        out = {k: m[k] for k in observe.COUNTERS}
        out.update({k: m[k] for k in EXTRA_COUNTERS if k in m})
        engine = m["engine"]
        out.update({k: engine[k] for k in
                    observe.ENGINE_COUNTERS + ENGINE_EXTRA if k in engine})
        return out


async def pump(loop, ep, stream: Stream, depth: int, n_prefill: int,
               max_prefill: int, seconds: float, marks: dict) -> list:
    """`resolver_replay.pump`, with the batches built ahead of their turn
    and a set-up that goes on past `n_prefill` batches (to `max_prefill`
    at most) until the role reports a full repack of its dictionary.
    Returns one row per batch: (n, sent, received, verdicts, fail_safe)."""
    rows: list = []
    ready: collections.deque = collections.deque()
    state = {"next": 0, "built": 0, "out": 0, "error": None, "done": False,
             "repacked": False, "built_late": 0}

    def build() -> None:
        ready.append(stream.batch_txns(state["built"]))
        state["built"] += 1

    async def builder() -> None:
        """Top the queue of built batches up while replies are awaited, one
        batch a turn of the loop."""
        while not state["done"]:
            if len(ready) < 2 * depth:
                build()
                await loop.sleep(0)
            else:
                await loop.sleep(0.002)

    def more_setup() -> bool:
        return state["next"] < n_prefill or (
            not state["repacked"] and state["next"] < max_prefill)

    state["more"] = more_setup

    async def send(n: int, txns: list) -> None:
        try:
            prev = stream.version(n - 1) if n else 0
            t0 = pc()
            verdicts, _conf, fail_safe, _wave = await ep.resolve(
                prev, stream.version(n), txns, stream.oldest(n))
            rows.append((n, t0, pc(), [int(v) for v in verdicts],
                         bool(fail_safe)))
            if ("t_start" not in marks and not state["repacked"]
                    and n + 1 >= n_prefill - depth):
                engine = (await ep.get_metrics())["engine"]
                state["repacked"] = engine["full_repacks"] > 0
            state["out"] -= 1
            fill()
        except BaseException as e:  # noqa: BLE001 — raised by the waiter
            state["error"] = e

    def fill() -> None:
        while state["out"] < depth and state["more"]():
            if not ready:
                state["built_late"] += 1
                build()
            n = state["next"]
            state["next"] = n + 1
            state["out"] += 1
            loop.spawn(send(n, ready.popleft()), name=f"bench.batch{n}")

    async def drained() -> float:
        while state["out"] or state["more"]():
            if state["error"] is not None:
                raise state["error"]
            await loop.sleep(0.005)
        return max(r[2] for r in rows)

    for _ in range(2 * depth):
        build()
    loop.spawn(builder(), name="bench.builder")
    try:
        fill()
        marks["t_start"] = await drained()
        marks["prefill_batches"] = state["next"]
        marks["built_late_in_setup"] = state["built_late"]
        deadline = marks["t_start"] + seconds
        state["more"] = lambda: pc() < deadline
        fill()
        marks["t_stop"] = await drained()
        marks["built_late"] = (state["built_late"]
                               - marks["built_late_in_setup"])
    finally:
        state["done"] = True
    return sorted(rows)


def counts_wide_txns(loop) -> bool:
    """Whether the program's resolver role reports `wide_txns` among its
    `get_metrics()`: asked of a role built here over no engine at all, so
    nothing is started and no device is touched."""
    from foundationdb_tpu.runtime.resolver import Resolver

    return "wide_txns" in loop.run(
        Resolver(loop, object()).get_metrics(), timeout=10)


def run(ctx) -> dict:
    from foundationdb_tpu.runtime.net import NetTransport, RealLoop

    loop = RealLoop()
    if not counts_wide_txns(loop):
        raise RuntimeError(
            "this program's resolver reports no `wide_txns` in "
            "get_metrics(): it does not lay a transaction of more ranges "
            "than its engine's row has slots out in rows of its own (it "
            "widens the ranges to fit one), and cannot run mako's g8ui")
    cfg, traffic = ctx.config, ctx.traffic
    env = {"FDB_TPU_OBS": "1", "FDB_TPU_OBS_SAMPLE": str(
        traffic.get("obs_sample", 1))} if ctx.trace else {}
    depth = traffic["batches_in_flight"]
    batch = cfg["engine"]["batch_size"]
    step_s = batch / cfg["nominal_rate_per_s"]
    n_prefill = int(np.ceil(
        MVCC_WINDOW_VERSIONS / VERSIONS_PER_SECOND / step_s))
    max_prefill = cfg["prefill_at_most_windows"] * n_prefill
    # Enough batches for set-up and for the window at several times the
    # nominal rate; the stream wraps if the system is faster still.
    n_batches = max_prefill + int(np.ceil(
        ctx.seconds / step_s * traffic["plan_rate_factor"])) + depth
    stream = Stream(cfg, traffic, ctx.seed, n_batches)
    out: dict = {"checks": []}
    proc = ResolverProcess(ctx.root, ctx.workdir, ctx.config_path, env)
    control = Control(proc.control_dir)
    proc.start()
    ctx.log(f"resolver up {pc() - ctx.t0:.1f}s after launch")
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
            rows = await pump(loop, ep, stream, depth, n_prefill,
                              max_prefill, ctx.seconds, marks)
            return rows, (await watch) if watch is not None else None

        rows, watched = loop.run(window(), timeout=ctx.seconds + 900)
        t_start, t_stop = marks["t_start"], marks["t_stop"]
        out["setup_s"] = t_start - ctx.t0
        timed = [r for r in rows if t_start < r[2] <= t_stop]
        rtt_ms = [(r[2] - r[1]) * 1e3 for r in timed]
        replies = sorted(r[2] for r in timed)
        counters = loop.run(observer.counters(), timeout=30)
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
            # since boot, set-up and window together
            "since_boot": {k: counters[k] for k in (
                "full_repacks",) + ENGINE_EXTRA + EXTRA_COUNTERS
                if k in counters},
        }
        out["attempted"] = len(timed) * batch

        # -- outside the timed window: the plain reference, in order -------
        history = RangeHistory()
        wrong = conflicts = 0
        for n, _t0, _t1, got, _fs in rows:
            ref = range_verdicts(history, stream.batch_pairs(n),
                                 stream.version(n), stream.oldest(n))
            wrong += sum(1 for a, b in zip(got, ref) if a != b)
            wrong += abs(len(got) - len(ref))
            conflicts += sum(1 for v in ref if v == CONFLICT)
        sent = len(rows) * batch
        out["failed"] = min(wrong, out["attempted"])
        out["checks"] += [
            ("verdicts_wrong", wrong, 0),
            ("verdicts_compared", sent, None),
            ("reference_conflicts", conflicts, None),
            ("batches_fail_safe", sum(1 for r in rows if r[4]), 0),
            ("batches_out_of_order",
             int([r[0] for r in rows] != list(range(len(rows)))), 0),
            # every range sent reached the role as it was: 11 a transaction
            ("ranges_not_received",
             abs(sent * RANGES_PER_TXN - counters["ranges_received"]), 0),
            # and was laid out as a wide transaction, not cut down to a row
            ("wide_txns_not_counted",
             abs(sent - counters.get("wide_txns", 0)), 0),
        ]
        for name in ("overflow_events", "txns_rejected_fail_safe",
                     "resolve_failures"):
            out["checks"].append((name, counters[name], 0))
        out["sources"] = {} if watched is None else observe.window_sources(
            watched, control, ctx.fixture)
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
