"""Driver `resolver_replay`: one resolver's share of a cluster too large for
this host, replayed at the resolver's own rate.

The resolver role runs in its own process on the chip
(benchmark/lib/resolver_proc.py, mode `replay`). This process emulates the
deployment's commit proxies: it sends `resolve(prev_version, version, txns,
oldest_version)` over real TCP, chain-ordered, keeping a fixed number of
batches in flight. Versions advance by a fixed step per batch, so verdicts do
not depend on timing. Set-up sends one whole MVCC window of batches, untimed;
the measured window follows without a pause.

`correct`: every verdict of the pre-fill and of the window equals the plain
reference's (benchmark/lib/reference.py `point_verdicts`) on the same
batches in version order, no reply was marked fail-safe, and the role's
failure counters are 0.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np

from benchmark.lib import observe, ycsb
from benchmark.lib.control import Control
from benchmark.lib.hist import percentile_of
from benchmark.lib.loadgen import pc
from benchmark.lib.reference import point_verdicts

MVCC_WINDOW_VERSIONS = 5_000_000  # upstream's 5 s at 1e6 versions a second
VERSIONS_PER_SECOND = 1_000_000
BOOT_DEADLINE_S = 600.0


class ResolverProcess:
    """The launcher in `replay` mode, supervised as SocketCluster does it:
    its own session, output to a log file, `ready` awaited, shut down by
    its admin RPC and reaped."""

    def __init__(self, root: str, workdir: str, config_path: str, env: dict):
        from foundationdb_tpu.loadgen.deploy import free_ports

        self.port = free_ports(1)[0]
        self.control_dir = os.path.join(workdir, "ctl")
        self.log_path = os.path.join(workdir, "resolver0.log")
        self.root = root
        self.argv = [sys.executable, "-m", "benchmark.lib.resolver_proc",
                     "--ctl", self.control_dir, "replay",
                     "--config", config_path, "--port", str(self.port)]
        self.env = dict(os.environ, **env)
        self.popen = None

    def start(self) -> None:
        with open(self.log_path, "ab") as log:
            self.popen = subprocess.Popen(
                self.argv, cwd=self.root, env=self.env, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True)
        deadline = time.monotonic() + BOOT_DEADLINE_S
        while True:
            with open(self.log_path, "rb") as f:
                if any(ln.startswith(b"ready ") for ln in f.read().splitlines()):
                    return
            if self.popen.poll() is not None:
                raise RuntimeError(
                    f"the resolver process exited {self.popen.returncode} "
                    f"during boot (see {self.log_path})")
            if time.monotonic() > deadline:
                self.kill()
                raise RuntimeError("timed out waiting for the resolver")
            time.sleep(0.05)

    def kill(self) -> None:
        if self.popen is not None and self.popen.poll() is None:
            try:
                os.killpg(self.popen.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        if self.popen is not None:
            self.popen.wait()

    def shutdown(self, loop, admin_ep) -> tuple[int, bool]:
        """(exit code, whether it had to be killed)."""
        try:
            loop.run_until(admin_ep.shutdown(), timeout=5.0)
        except Exception:  # noqa: BLE001 — the kill below reaps it
            pass
        try:
            return self.popen.wait(timeout=15.0), False
        except subprocess.TimeoutExpired:
            self.kill()
            return self.popen.returncode, True


class Stream:
    """The batches of one run, made from the seed: batch n has `batch`
    transactions, each reading and writing one record's key."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, n_batches: int):
        self.batch = cfg["engine"]["batch_size"]
        self.step = int(round(self.batch / cfg["nominal_rate_per_s"]
                              * VERSIONS_PER_SECOND))
        self.lag = traffic["read_version_lag_batches"] * self.step
        self.n_batches = n_batches
        universe = cfg["key_universe"]
        # Workload F's read-modify-writes alone reach a resolver.
        _kinds, self.items = ycsb.plan(universe, n_batches * self.batch, 1.0,
                                       seed, traffic["base_seed"])
        self._keys = ycsb.record_keys(self.items)

    def version(self, n: int) -> int:
        return (n + 1) * self.step

    def batch_keys(self, n: int) -> list:
        lo = (n % self.n_batches) * self.batch
        return [self._keys[i] for i in self.items[lo:lo + self.batch].tolist()]

    def read_version(self, n: int) -> int:
        return max(0, self.version(n) - self.lag)

    def oldest(self, n: int) -> int:
        return max(0, self.version(n) - MVCC_WINDOW_VERSIONS)


async def pump(loop, ep, stream: Stream, depth: int, n_prefill: int,
               seconds: float, marks: dict) -> list:
    """Keep `depth` batches in flight, in two stretches that each end with
    nothing in flight: the pre-fill's `n_prefill` batches, then the window,
    in which batches are sent for `seconds` seconds. The window runs from the
    pre-fill's last reply to its own last reply, so both its ends are
    moments at which the resolver has answered everything it was sent. (The
    role answers a whole dispatch group at once, so replies come in bursts
    of up to `depth`; a window cut at a fixed instant counted 139 or 145
    batches by where the cut fell in a burst.) Returns one row per batch:
    (n, sent, received, verdicts, fail_safe)."""
    from foundationdb_tpu.core.types import KeyRange, TxnConflictInfo

    rows: list = []
    state = {"next": 0, "out": 0, "more": lambda: state["next"] < n_prefill,
             "error": None}

    async def send(n: int) -> None:
        try:
            keys = stream.batch_keys(n)
            rv = stream.read_version(n)
            txns = [TxnConflictInfo(
                read_version=rv,
                read_ranges=[KeyRange(k, k + b"\x00")],
                write_ranges=[KeyRange(k, k + b"\x00")]) for k in keys]
            prev = stream.version(n - 1) if n else 0
            t0 = pc()
            verdicts, _conf, fail_safe, _wave = await ep.resolve(
                prev, stream.version(n), txns, stream.oldest(n))
            rows.append((n, t0, pc(), [int(v) for v in verdicts],
                         bool(fail_safe)))
            state["out"] -= 1
            fill()
        except BaseException as e:  # noqa: BLE001 — raised by the waiter
            state["error"] = e

    def fill() -> None:
        while state["out"] < depth and state["more"]():
            n = state["next"]
            state["next"] = n + 1
            state["out"] += 1
            loop.spawn(send(n), name=f"bench.batch{n}")

    async def drained() -> float:
        """When nothing is in flight and nothing more is to be sent:
        returns the last reply's time."""
        while state["out"] or state["more"]():
            if state["error"] is not None:
                raise state["error"]
            await loop.sleep(0.005)
        return max(r[2] for r in rows)

    fill()
    marks["t_start"] = await drained()
    deadline = marks["t_start"] + seconds
    state["more"] = lambda: pc() < deadline
    fill()
    marks["t_stop"] = await drained()
    return sorted(rows)


def run(ctx) -> dict:
    from foundationdb_tpu.runtime.net import NetTransport, RealLoop

    cfg, traffic = ctx.config, ctx.traffic
    env = {"FDB_TPU_OBS": "1", "FDB_TPU_OBS_SAMPLE": str(
        traffic.get("obs_sample", 1))} if ctx.trace else {}
    depth = traffic["batches_in_flight"]
    batch = cfg["engine"]["batch_size"]
    step_s = batch / cfg["nominal_rate_per_s"]
    n_prefill = int(np.ceil(
        MVCC_WINDOW_VERSIONS / VERSIONS_PER_SECOND / step_s))
    # Enough batches for the window at several times the nominal rate; the
    # stream wraps if the system is faster still.
    n_batches = n_prefill + int(np.ceil(
        ctx.seconds / step_s * traffic["plan_rate_factor"])) + depth
    stream = Stream(cfg, traffic, ctx.seed, n_batches)
    config_path = ctx.config_path
    if ctx.control == "small_history":
        # The control: a history a sixty-fourth of the stated size cannot
        # hold five seconds of writes, and the capacity fail-safe rejects.
        small = dict(cfg, engine=dict(
            cfg["engine"], capacity=cfg["engine"]["capacity"] // 64,
            dict_capacity=cfg["engine"]["dict_capacity"] // 64))
        config_path = os.path.join(ctx.workdir, "control_config.json")
        with open(config_path, "w") as f:
            json.dump(small, f)
    out: dict = {"checks": []}
    proc = ResolverProcess(ctx.root, ctx.workdir, config_path, env)
    control = Control(proc.control_dir)
    proc.start()
    ctx.log(f"resolver up {pc() - ctx.t0:.1f}s after launch")
    loop = RealLoop()
    t = NetTransport(loop)
    try:
        addr = ("127.0.0.1", proc.port)
        ep, admin = t.endpoint(addr, "resolver"), t.endpoint(addr, "admin")
        observer = observe.Observer(loop, control, ep, [admin])
        marks: dict = {}

        async def window():
            watch = loop.spawn(_watch(loop, observer, marks, ctx.seconds,
                                      traffic.get("trace_s", 3.0)),
                               name="bench.observer") if ctx.trace else None
            rows = await pump(loop, ep, stream, depth, n_prefill,
                              ctx.seconds, marks)
            return rows, (await watch) if watch is not None else None

        rows, watched = loop.run(window(), timeout=ctx.seconds + 900)
        t_start, t_stop = marks["t_start"], marks["t_stop"]
        out["setup_s"] = t_start - ctx.t0
        timed = [r for r in rows if t_start < r[2] <= t_stop]
        rtt_ms = [(r[2] - r[1]) * 1e3 for r in timed]
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
            "prefill_batches": n_prefill,
            "prefill_s": t_start - rows[0][1],
        }
        out["attempted"] = len(timed) * batch

        # -- outside the timed window: the plain reference, in order -------
        last_write: dict = {}
        wrong = conflicts = 0
        for n, _t0, _t1, got, _fs in rows:
            ref = point_verdicts(
                last_write, stream.batch_keys(n),
                [stream.read_version(n)] * batch, stream.version(n),
                stream.oldest(n))
            wrong += sum(1 for a, b in zip(got, ref) if a != b)
            wrong += abs(len(got) - len(ref))
            conflicts += sum(1 for v in ref if v == 1)
        out["failed"] = min(wrong, out["attempted"])
        out["checks"] += [
            ("verdicts_wrong", wrong, 0),
            ("verdicts_compared", len(rows) * batch, None),
            ("reference_conflicts", conflicts, None),
            ("batches_fail_safe", sum(1 for r in rows if r[4]), 0),
            ("batches_out_of_order",
             int([r[0] for r in rows] != list(range(len(rows)))), 0),
        ]
        counters = loop.run(observer.counters(), timeout=30)
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


async def _watch(loop, observer, marks: dict, seconds: float, trace_s: float):
    while "t_start" not in marks:
        await loop.sleep(0.01)
    return await observer.watch_window(
        marks["t_start"], marks["t_start"] + seconds, trace_s, pc)
