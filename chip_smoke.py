#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py

Drives the repo's main path once on a TPU, through the entry points a user
calls, and checks what comes out by the repo's own means. Three phases, one
after another, because a chip belongs to one process at a time:

- engine: one child process. TPUConflictSet at its default design point and
  at a deployment's size, driven as bench.py drives it (YCSB core workload A
  through the wire window path), every verdict compared with the C++
  skiplist on the same stream.
- served: a deployed cluster in start_cluster.sh's shape with the resolver
  on the chip and every other process off JAX. Loads data through the client
  library, runs the open-loop generator, then holds the cluster to its
  guarantees: every acknowledged write read back from both replicas, a
  conflicting pair loses exactly one, no resolve RPC failed.
- four_chip: one child process, only where four chips are visible.
  ShardedConflictSet over the four real devices, placement and parity.

This process never imports JAX: it would hold the chip against its own
children. It exits non-zero, and prints no result, unless JAX in the first
child reports platform "tpu" — JAX's silent fall-back to the CPU included.
Otherwise standard output ends with two JSON lines: the report (versions,
per-phase results, compile cache), then, last, exactly
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}} with
the device as JAX reports it. The times in the report are set-up information,
not measurements.
"""

from __future__ import annotations

import argparse
import functools
import importlib.metadata
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 20260926

# Engine phase: upstream's 5 s MVCC window at the north star's order of
# rate is ~2.5 M live write ranges, so the history holds 1<<22 boundaries;
# the key universe is 1<<24 scrambled-Zipf keys; 128 batches of 8,192 are
# BASELINE.json's "1M in-flight" transactions.
ENGINE_CAPACITY = 1 << 22
ENGINE_KEYS = 1 << 24
ENGINE_BATCHES = 128
ENGINE_WINDOW = 32  # batches per dispatch, bench.py's default

# Served phase (ISSUE 21): >= 100,000 keys of 100-byte values in ~100-key
# transactions, then >= 2,000 read-write transactions at a fixed modest rate
# (2,184 arrivals with this seed). Modest means well under what the cluster
# holds: at 200/s PR 21's chip run held the rate but with co-latency p50
# 0.9 s, too near the generator's 5 s timeout for a smoke.
SERVED_KEYS = 100_000
SERVED_KEYS_PER_TXN = 100
SERVED_VALUE_BYTES = 100
SERVED_RATE = 100.0
SERVED_DURATION_S = 21.0

# Four-chip phase: 16 dispatches, so the default auto-reshard policy (every
# 8th dispatch) gets to look at the Zipf skew twice.
FOUR_CHIP_CAPACITY = 1 << 18
FOUR_CHIP_KEYS = 1 << 20
FOUR_CHIP_BATCHES = 64
FOUR_CHIP_WINDOW = 4

CHILD_TIMEOUT_S = {"engine": 600.0, "four_chip": 400.0}


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


class SmokeFailure(Exception):
    """A phase ran and what came out is wrong."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# -- child side: the phases that need the chip ------------------------------


class CompileLog:
    """Seconds per jitted function spent tracing, lowering and in the
    backend compile (a persistent-cache fetch counts there too), and the
    persistent cache's hits and misses, from jax.monitoring's events."""

    _STAGES = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "compile_s",
    }

    def __init__(self) -> None:
        self.by_fn: dict[str, dict[str, float]] = {}
        self.cache = {"hits": 0, "misses": 0}

    def __enter__(self) -> "CompileLog":
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *_exc) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, event: str, duration: float, **kw) -> None:
        stage = self._STAGES.get(event)
        if stage is not None:
            rec = self.by_fn.setdefault(str(kw.get("fun_name", "?")), {})
            rec[stage] = rec.get(stage, 0.0) + duration

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache["misses"] += 1

    def report(self, floor_s: float = 0.5) -> dict:
        """Functions that cost at least `floor_s` in all, largest first."""
        rows = {fn: {k: round(v, 3) for k, v in rec.items()}
                for fn, rec in self.by_fn.items()
                if sum(rec.values()) >= floor_s}
        return {"entry_points": dict(sorted(
                    rows.items(), key=lambda kv: -sum(kv[1].values()))),
                "all_functions_s": round(sum(
                    sum(rec.values()) for rec in self.by_fn.values()), 3),
                "persistent_cache": dict(self.cache)}


def _device_phase(phase):
    """A phase that holds the chip: its result gains the compile report of
    everything it jitted and the first device's peak memory."""

    @functools.wraps(phase)
    def run(*args, **kwargs) -> dict:
        import jax

        with CompileLog() as compiles:
            out = phase(*args, **kwargs)
        stats = jax.devices()[0].memory_stats()  # None on the CPU backend
        return dict(out, compile=compiles.report(),
                    peak_hbm_bytes=stats and stats.get("peak_bytes_in_use"))

    return run


def _ycsb_stream(n_batches: int, n_keys: int, batch: "int | None",
                 seed: int):
    """bench.py's YCSB-A stream: (mode, wire blob, txn ends, and the C++
    skiplist's verdict for every transaction — the plain reference)."""
    from dataclasses import replace

    import bench

    mode = bench.MODES["ycsb"]
    if batch is not None:
        mode = replace(mode, batch=batch)
    stream = bench.gen_workload(n_batches * mode.batch, n_keys, seed, mode)
    _dt, _conf, _lat, ref = bench.run_cpu(
        bench.marshal_cpu_batches(n_batches, *stream, mode), mode)
    blob, ends = bench.build_wire_stream(*stream, n_batches, mode)
    return mode, blob, ends, ref


def _resolve_stream(cs, mode, blob, ends, n_batches: int, window: int):
    """The stream through resolve_wire_window_async, `window` batches per
    dispatch; fetching each window's verdicts forces its completion.
    Returns (verdicts int8 [n_batches, batch], wall seconds per window)."""
    import numpy as np

    b = mode.batch
    got, secs = [], []
    for first in range(0, n_batches, window):
        lo, hi = int(ends[first * b]), int(ends[(first + window) * b])
        t0 = time.perf_counter()
        collect = cs.resolve_wire_window_async(
            blob[lo:hi], list(range(first + 1, first + window + 1)), b)
        got.append(np.asarray(collect()))
        secs.append(round(time.perf_counter() - t0, 3))
    return np.concatenate(got), secs


def _parity(got, ref) -> dict:
    import numpy as np

    check(got.shape == ref.shape, f"verdicts {got.shape}, skiplist {ref.shape}")
    differ = np.argwhere(got != ref)
    out = {"txns": int(ref.size), "mismatched": len(differ),
           "conflicts": int((ref == 1).sum())}
    check(len(differ) == 0, f"verdicts differ from the C++ skiplist: {out}, "
                            f"first at {differ[:3].tolist()}")
    return out


@_device_phase
def engine_phase(capacity: int = ENGINE_CAPACITY, n_keys: int = ENGINE_KEYS,
                 n_batches: int = ENGINE_BATCHES, window: int = ENGINE_WINDOW,
                 batch: "int | None" = None, seed: int = SEED) -> dict:
    """The resolve engine alone, at deployment size, against the skiplist."""
    import bench
    from foundationdb_tpu.models.conflict_set import TPUConflictSet

    check(n_batches % window == 0, "n_batches must be whole windows")
    t0 = time.perf_counter()
    mode, blob, ends, ref = _ycsb_stream(n_batches, n_keys, batch, seed)
    stream_s = round(time.perf_counter() - t0, 1)
    log(f"engine: stream of {ref.size} txns and its skiplist verdicts "
        f"in {stream_s}s")
    cs = TPUConflictSet(
        capacity=capacity, batch_size=mode.batch,
        max_read_ranges=mode.n_reads, max_write_ranges=mode.n_writes,
        max_key_bytes=bench.KEY_BYTES, window_versions=bench.WINDOW,
    )
    check(not (cs.wave_commit or cs.spec or cs.tiered),
          "engine is not at its default design point (FDB_TPU_* set?)")
    got, window_s = _resolve_stream(cs, mode, blob, ends, n_batches, window)
    log(f"engine: windows took {window_s}s")
    parity = _parity(got, ref)
    check(not cs.overflowed, "history overflowed")
    return {
        "capacity": capacity, "dict_capacity": cs.dict_capacity,
        "keys": n_keys, "batch": mode.batch, "batches": n_batches,
        "batches_per_dispatch": window, "parity": parity,
        "overflow": False, "dictionary": cs.dict_stats,
        "stream_setup_s": stream_s, "window_wall_s": window_s,
    }


@_device_phase
def four_chip_phase(capacity: int = FOUR_CHIP_CAPACITY,
                    n_keys: int = FOUR_CHIP_KEYS,
                    n_batches: int = FOUR_CHIP_BATCHES,
                    window: int = FOUR_CHIP_WINDOW,
                    batch: "int | None" = None, seed: int = SEED) -> dict:
    """ShardedConflictSet(n_shards=4) on four real devices: the dry run's
    checks, each sharded state leaf on four distinct devices, and a
    windowed Zipf stream under the default auto-reshard policy with every
    verdict compared with the skiplist."""
    import jax

    import __graft_entry__
    import bench
    from foundationdb_tpu.parallel.sharded_resolver import ShardedConflictSet
    from foundationdb_tpu.server import make_conflict_set

    def placement(cs) -> list[list[int]]:
        """Device ids per sharded leaf; each must hold one shard on each
        of four distinct devices."""
        st = cs.state
        leaves = jax.tree.leaves((st.hist, st.shard_lo, st.shard_hi))
        ids = []
        for leaf in leaves:
            shards = leaf.addressable_shards
            ids.append(sorted(s.device.id for s in shards))
            check(len({s.device for s in shards}) == 4
                  and all(s.data.shape[0] == 1 for s in shards),
                  f"a sharded state leaf of shape {leaf.shape} sits on "
                  f"devices {ids[-1]} as {leaf.sharding}")
        return ids

    dry = __graft_entry__.dryrun_multichip(4)
    placement(dry)
    mode, blob, ends, ref = _ycsb_stream(n_batches, n_keys, batch, seed)
    # Through the factory the resolver role is served from (a spec with
    # "resolver_mesh": 4), at this phase's sizes: what is proved here is
    # the construction that is deployed.
    cs = make_conflict_set(
        "tpu", mesh=4, capacity=capacity, batch_size=mode.batch,
        max_read_ranges=mode.n_reads, max_write_ranges=mode.n_writes,
        max_key_bytes=bench.KEY_BYTES, window_versions=bench.WINDOW,
    )
    check(type(cs) is ShardedConflictSet and cs.n_shards == 4,
          f"the factory built {type(cs).__name__} for mesh=4")
    check(cs.auto_reshard, "auto_reshard is no longer the default")
    placed_before = placement(cs)
    got, window_s = _resolve_stream(cs, mode, blob, ends, n_batches, window)
    parity = _parity(got, ref)
    check(not cs.overflowed, "history overflowed")
    placed_after = placement(cs)
    return {
        "capacity_per_shard": capacity, "keys": n_keys, "batch": mode.batch,
        "batches": n_batches, "batches_per_dispatch": window,
        "parity": parity, "overflow": False,
        "auto_reshards": cs.auto_reshards,
        "shard_occupancy": cs.shard_occupancy(),
        "leaf_device_ids": placed_after[0],
        "placement_held": placed_before == placed_after,
        "window_wall_s": window_s,
    }


def _child_main(phase: str) -> int:
    """Own the chip for one phase. Standard output carries JSON lines: the
    device first, so the parent has it whatever happens next, then the
    phase's result or its error."""
    from foundationdb_tpu.utils import (
        device_summary,
        enable_compilation_cache,
    )

    enable_compilation_cache()
    device = device_summary()
    if device["platform"] != "tpu":
        log(f"JAX found platform {device['platform']!r} "
            f"({device['device_kind']}, {device['count']} device(s)), "
            "not a TPU")
        return 3
    print(json.dumps({"device": device}), flush=True)
    try:
        result = {"engine": engine_phase, "four_chip": four_chip_phase}[phase]()
    except Exception as e:  # noqa: BLE001 — the parent reports it
        import traceback

        traceback.print_exc()
        print(json.dumps({"error": f"{type(e).__name__}: {e}"[:4000]}),
              flush=True)
        return 1
    print(json.dumps({"result": result}), flush=True)
    return 0


# -- parent side -------------------------------------------------------------


def _run_child(phase: str) -> tuple["dict | None", dict]:
    """Run one chip phase in its own process (and process group, so a
    timeout reaps whatever it started). Returns (the device it named, or
    None; the phase's result, or {"error": ...})."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child", phase],
        cwd=HERE, stdout=subprocess.PIPE, text=True, start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=CHILD_TIMEOUT_S[phase])
        said = {"error": f"child exited {proc.returncode}"}
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        out, _ = proc.communicate()
        said = {"error": f"timed out after {CHILD_TIMEOUT_S[phase]:.0f}s"}
    for line in out.splitlines():
        if line.startswith("{"):
            said.update(json.loads(line))
    return said.get("device"), said.get("result") or {"error": said["error"]}


def _role_maps_jax(pid: int) -> bool:
    """Has this process mapped jaxlib or libtpu?"""
    with open(f"/proc/{pid}/maps") as f:
        maps = f.read()
    return "jaxlib" in maps or "libtpu" in maps


def served_phase(workdir: str, n_keys: int = SERVED_KEYS,
                 keys_per_txn: int = SERVED_KEYS_PER_TXN,
                 rate: float = SERVED_RATE,
                 duration_s: float = SERVED_DURATION_S,
                 env: "dict | None" = None, seed: int = SEED,
                 mesh: "int | None" = None) -> dict:
    """A store that loads data and answers queries, the resolver on the
    device. Guarantees held to: strict serializability (the conflicting
    pair), durability (tlogs fsync before the ack: data_dirs=True) and
    two-way replication (both replicas hold every acknowledged write).
    ``mesh``: the spec's `resolver_mesh`, the one resolver's history
    sharded over that many chips."""
    from foundationdb_tpu.consistency import run_deployed_check
    from foundationdb_tpu.core.errors import NotCommitted
    from foundationdb_tpu.loadgen.deploy import REPO, SocketCluster
    from foundationdb_tpu.runtime.flow import all_of
    from foundationdb_tpu.server import parse_addr

    out: dict = {}
    expected: dict[bytes, bytes] = {}

    def value_of(i: int) -> bytes:
        return (b"%012d" % i).ljust(SERVED_VALUE_BYTES, b".")

    spec_extra = {"replicas": 2}
    if mesh:
        spec_extra["resolver_mesh"] = mesh
    t_boot = time.monotonic()
    with SocketCluster(workdir, proxies=2, tlogs=2, storages=2, resolvers=1,
                       ratekeeper=True, engine="tpu", data_dirs=True,
                       spec_extra=spec_extra, env=env) as cluster:
        out["boot_s"] = round(time.monotonic() - t_boot, 1)
        with open(os.path.join(workdir, "resolver0.log")) as f:
            out["resolver_log"] = [ln.strip() for ln in f
                                   if ln.startswith(("device ", "ready "))]
        log(f"served: cluster up in {out['boot_s']}s; {out['resolver_log']}")
        on_jax = {p.name: _role_maps_jax(p.popen.pid) for p in cluster.procs}
        out["roles_with_jax_mapped"] = sorted(n for n, v in on_jax.items() if v)
        check(out["roles_with_jax_mapped"] == ["resolver0"],
              f"only resolver0 may load JAX; found {on_jax}")

        loop, t, db = cluster.open_client()
        try:
            async def commit_sets(pairs) -> None:
                async def body(tr) -> None:
                    for k, v in pairs:
                        tr.set(k, v)

                await db.run(body)  # the client's standard retry loop

            # Ten single-key commits, one after another: `ready` meant
            # compiled iff the first is not slower than the tenth by a
            # compile.
            first_ten = []
            for i in range(10):
                t0 = time.perf_counter()
                kv = (b"smoke/first/%02d" % i, value_of(i))
                loop.run(commit_sets([kv]), timeout=120)
                first_ten.append(round(time.perf_counter() - t0, 4))
                expected[kv[0]] = kv[1]
            out["first_ten_commit_s"] = first_ten
            check(first_ten[0] - first_ten[9] < 1.0,
                  f"first commit paid a compile: {first_ten}")

            # Bulk load, 16 transactions in flight.
            n_txns = -(-n_keys // keys_per_txn)
            t0 = time.perf_counter()

            async def load_worker(w: int) -> None:
                for n in range(w, n_txns, 16):
                    ids = range(n * keys_per_txn,
                                min((n + 1) * keys_per_txn, n_keys))
                    pairs = [(b"smoke/load/%08d" % i, value_of(i))
                             for i in ids]
                    await commit_sets(pairs)
                    expected.update(pairs)

            loop.run(all_of([loop.spawn(load_worker(w), name=f"load{w}")
                             for w in range(16)]), timeout=600)
            out["load"] = {"keys": n_keys, "txns": n_txns,
                           "wall_s": round(time.perf_counter() - t0, 1)}
            log(f"served: loaded {out['load']}")

            # The generator the repo has, in a process of its own, CPU-pinned.
            n_offered_keys = int(rate * duration_s * 2) + 1024
            gen = subprocess.run(
                [sys.executable, "-m", "foundationdb_tpu.loadgen",
                 "--cluster", cluster.spec_path, "--reads", "2",
                 "--rate", str(rate), "--duration", str(duration_s),
                 "--keys", str(n_offered_keys), "--seed", str(seed),
                 "--value-bytes", str(SERVED_VALUE_BYTES)],
                cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                stdout=subprocess.PIPE, text=True,
                timeout=duration_s + 240)
            check(gen.returncode == 0, f"loadgen exited {gen.returncode}")
            rec = json.loads(gen.stdout.strip().splitlines()[-1])
            out["loadgen"] = {k: rec[k] for k in (
                "offered", "committed", "shed", "timed_out", "failed",
                "abandoned", "conflict_retries", "max_dispatch_lag_s",
                "co_p50_ms", "co_p99_ms")}
            log(f"served: loadgen {out['loadgen']}")
            check(rec["committed"] == rec["offered"] > 0,
                  f"the generator's modest rate was not held: {out['loadgen']}")
            # Arrival k wrote key k (keys > arrivals), all acknowledged.
            for k in range(rec["offered"]):
                expected[b"ol/%d/%d" % (seed, k)] = b"v" * SERVED_VALUE_BYTES

            # Two transactions read k at one read version, both write it.
            async def conflicting_pair() -> list[str]:
                key = b"smoke/pair"
                a, b = db.transaction(), db.transaction()
                b.set_read_version(await a.get_read_version())
                outcomes = []

                async def rmw(tr, val: bytes) -> None:
                    await tr.get(key)
                    tr.set(key, val)
                    try:
                        await tr.commit()
                        outcomes.append("committed")
                        expected[key] = val
                    except NotCommitted:
                        outcomes.append("not_committed")

                await all_of([loop.spawn(rmw(a, b"a"), name="pair.a"),
                              loop.spawn(rmw(b, b"b"), name="pair.b")])
                return sorted(outcomes)

            out["conflicting_pair"] = loop.run(conflicting_pair(), timeout=60)
            check(out["conflicting_pair"] == ["committed", "not_committed"],
                  f"conflicting pair ended {out['conflicting_pair']}")

            # Every acknowledged write, from each replica's own serve path.
            async def replica_rows(ep, version: int) -> dict[bytes, bytes]:
                rows: dict[bytes, bytes] = {}
                for begin, end in ((b"ol/", b"ol0"), (b"smoke/", b"smoke0")):
                    while True:
                        page = await ep.get_range(begin, end, version,
                                                  limit=10_000)
                        rows.update(page)
                        if len(page) < 10_000:
                            break
                        begin = page[-1][0] + b"\x00"
                return rows

            async def read_back() -> dict:
                version = await db.transaction().get_read_version()
                report = {}
                for i, addr in enumerate(cluster.spec["storage"]):
                    ep = t.endpoint(parse_addr(addr), "storage")
                    rows = await replica_rows(ep, version)
                    wrong = sum(1 for k, v in expected.items()
                                if rows.get(k) != v)
                    report[f"storage{i}"] = {
                        "rows": len(rows), "missing_or_wrong": wrong,
                        "unexpected": len(rows.keys() - expected.keys())}
                return report

            out["read_back"] = dict(loop.run(read_back(), timeout=300),
                                    acknowledged_keys=len(expected))
            log(f"served: read back {out['read_back']}")
            for name in ("storage0", "storage1"):
                check(out["read_back"][name] == {
                    "rows": len(expected), "missing_or_wrong": 0,
                    "unexpected": 0}, f"{name}: {out['read_back']}")
            audit = loop.run(
                run_deployed_check(loop, t, cluster.spec, db), timeout=300)
            out["consistencycheck"] = {
                k: audit.get(k) for k in (
                    "status", "shards_checked", "replicas_compared",
                    "rows_compared", "divergences", "unreachable")}
            check(audit.get("status") == "consistent",
                  f"consistencycheck: {out['consistencycheck']}")

            metrics = loop.run(
                t.endpoint(parse_addr(cluster.spec["resolver"][0]),
                           "resolver").get_metrics(), timeout=30)
            commits = 10 + n_txns + rec["committed"] + 1
            out["resolver"] = {k: metrics[k] for k in (
                "device", "txns_resolved", "batches_resolved",
                "txns_conflicted", "overflow_events",
                "txns_rejected_fail_safe", "resolve_failures")}
            out["resolver"]["commits"] = commits
            out["resolver"]["dictionary_full_repacks"] = (
                metrics["engine"]["full_repacks"])
            out["resolver"]["auto_reshards"] = (
                metrics["engine"]["auto_reshards"])
            check(metrics["txns_resolved"] >= commits
                  and metrics["overflow_events"] == 0
                  and metrics["txns_rejected_fail_safe"] == 0
                  and metrics["resolve_failures"] == 0,
                  f"resolver counters: {out['resolver']}")
        finally:
            t.close()
        # shutdown() raises on a leak_report that is not clean.
        stopped = cluster.shutdown()
        out["teardown"] = stopped
        check(not stopped["killed"]
              and set(stopped["exit_codes"].values()) == {0},
              f"teardown: {stopped}")
    return out


def _cache_entries(cache_dir: str) -> int:
    try:
        return len(os.listdir(cache_dir))
    except FileNotFoundError:
        return 0


def last_line(ok: bool, device: dict) -> str:
    """The last line of standard output: these keys and no others (the
    driver refuses anything else), the device as JAX reported it."""
    return json.dumps({
        "ok": ok,
        "device": {"platform": device["platform"],
                   "kind": device["device_kind"], "count": device["count"]},
    })


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--child", choices=sorted(CHILD_TIMEOUT_S),
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        return _child_main(args.child)

    if not os.path.isdir(os.path.join(HERE, "foundationdb_tpu")):
        log(f"the program is not here: no foundationdb_tpu/ in {HERE}")
        return 2
    t0 = time.monotonic()
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or os.path.join(HERE, ".jax_cache"))
    entries_before = _cache_entries(cache_dir)
    phases: dict = {}

    log("engine phase")
    device, phases["engine"] = _run_child("engine")
    if device is None:
        log(f"no TPU: the engine child named none "
            f"({phases['engine']['error']})")
        return 1

    log("served phase")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        try:
            phases["served"] = served_phase(workdir)
            dev = phases["served"]["resolver"]["device"]
            check(dev is not None and dev["platform"] == "tpu",
                  f"the resolver's arrays are on {dev}")
        except Exception as e:  # noqa: BLE001 — reported in the result
            import traceback

            traceback.print_exc()
            phases["served"] = {"error": f"{type(e).__name__}: {e}"[:4000]}

    if device["count"] >= 4:
        log("four-chip phase")
        _, phases["four_chip"] = _run_child("four_chip")
    else:
        phases["four_chip"] = {
            "skipped": f"needs 4 devices, {device['count']} visible"}

    check("jax" not in sys.modules, "the parent imported JAX")
    ok = not any("error" in p for p in phases.values())
    print(json.dumps({"report": {
        "versions": {p: importlib.metadata.version(p)
                     for p in ("jax", "jaxlib", "libtpu", "numpy")},
        "phases": phases,
        "compile_cache": {"dir": cache_dir, "entries_before": entries_before,
                          "entries_after": _cache_entries(cache_dir)},
        "elapsed_s": round(time.monotonic() - t0, 1),
    }}), flush=True)
    print(last_line(ok, device), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
