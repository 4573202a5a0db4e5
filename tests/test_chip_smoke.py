"""The chip path, on the CPU backend and small (ISSUE 21).

chip_smoke.py proves on a TPU that the deployed path starts with the device
in it. Here the same phase functions run under an explicit
JAX_PLATFORMS=cpu, so tier-1 covers the code the chip run exercises: a
cluster whose resolver holds the device engine, the engine against the C++
skiplist, the mesh engine's placement. The rest pins what keeps a CPU run
from passing for a chip run: no TPU, no result.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import chip_smoke
from foundationdb_tpu.core.types import KeyRange, TxnConflictInfo
from foundationdb_tpu.models.conflict_set import TPUConflictSet

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv, env_drop=(), env_set=None, timeout=120):
    env = {k: v for k, v in os.environ.items() if k not in env_drop}
    env.update(env_set or {})
    return subprocess.run(argv, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=timeout)


def test_served_phase_resolver_on_the_cpu_backend(tmp_path):
    """SocketCluster(engine="tpu") under an explicit JAX_PLATFORMS=cpu:
    load, generator, conflicting pair, read-back from both replicas, the
    resolver's counters — served_phase raises on any of them — and the
    resolver names the platform its arrays are on, in its log and in
    get_metrics()."""
    out = chip_smoke.served_phase(
        str(tmp_path), n_keys=1500, keys_per_txn=100, rate=50.0,
        duration_s=2.0, env={"JAX_PLATFORMS": "cpu"})
    assert out["resolver"]["device"] == {
        "platform": "cpu", "device_kind": "cpu", "count": 1}
    device_line, ready_line = out["resolver_log"]
    assert device_line.startswith("device resolver0 engine=tpu platform=cpu")
    assert ready_line.startswith("ready resolver0")
    assert out["roles_with_jax_mapped"] == ["resolver0"]
    assert out["conflicting_pair"] == ["committed", "not_committed"]
    for name in ("storage0", "storage1"):
        assert out["read_back"][name]["missing_or_wrong"] == 0
    assert out["read_back"]["acknowledged_keys"] >= 1500
    assert out["resolver"]["resolve_failures"] == 0
    assert out["resolver"]["txns_resolved"] >= out["resolver"]["commits"]


def test_engine_and_four_chip_phases_tiny():
    """Every verdict against the skiplist, through the wire window path
    (engine) and over a 4-device mesh with the default auto-reshard
    (four_chip: each sharded leaf on four distinct devices)."""
    eng = chip_smoke.engine_phase(capacity=1 << 12, n_keys=1 << 12,
                                  n_batches=4, window=2, batch=256)
    assert eng["parity"] == {"txns": 1024, "mismatched": 0,
                             "conflicts": eng["parity"]["conflicts"]}
    assert eng["parity"]["conflicts"] > 0 and not eng["overflow"]
    four = chip_smoke.four_chip_phase(capacity=1 << 12, n_keys=1 << 12,
                                      n_batches=16, window=1, batch=256)
    assert four["parity"]["mismatched"] == 0
    assert four["leaf_device_ids"] == [0, 1, 2, 3]
    assert four["placement_held"] and four["auto_reshards"] >= 1


def test_chip_smoke_without_a_chip_exits_nonzero_and_prints_no_result():
    """JAX's silent fall-back to the CPU is no TPU: non-zero exit, nothing
    on standard output, and the message names the platform found."""
    r = _run([sys.executable, "chip_smoke.py"], env_drop=("JAX_PLATFORMS",))
    assert r.returncode != 0
    assert r.stdout == ""
    assert "JAX found platform 'cpu'" in r.stderr


def test_last_line_has_the_contracts_keys_and_no_others():
    """The driver reads this line and refuses any key beside these."""
    line = chip_smoke.last_line(True, {
        "platform": "tpu", "device_kind": "TPU v5 lite", "count": 1})
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_chip_smoke_alone_in_a_directory_exits_nonzero(tmp_path):
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        (tmp_path / "chip_smoke.py").write_text(f.read())
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=60)
    assert r.returncode != 0 and r.stdout == ""
    assert "the program is not here" in r.stderr


def test_tpu_resolver_and_bench_refuse_a_cpu_they_were_not_told_about(
        tmp_path):
    spec = tmp_path / "cluster.json"
    spec.write_text(json.dumps({
        "sequencer": ["127.0.0.1:1"], "resolver": ["127.0.0.1:2"],
        "tlog": ["127.0.0.1:3"], "storage": ["127.0.0.1:4"],
        "proxy": ["127.0.0.1:5"], "engine": "tpu"}))
    for argv in (
        [sys.executable, "-m", "foundationdb_tpu.server", "--cluster",
         str(spec), "--role", "resolver"],
        [sys.executable, "bench.py", "--smoke"],
    ):
        r = _run(argv, env_drop=("JAX_PLATFORMS",))
        assert r.returncode != 0, argv
        assert "needs a TPU but JAX found platform 'cpu'" in r.stderr
        assert "ready" not in r.stdout and "{" not in r.stdout


def test_compile_cache_dir_is_placed_from_outside(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the code sets no directory;
    without it, <checkout>/.jax_cache."""
    code = ("import jax; from foundationdb_tpu.utils import "
            "enable_compilation_cache as e; e(); "
            "print(jax.config.jax_compilation_cache_dir)")
    placed = _run([sys.executable, "-c", code],
                  env_set={"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert placed.stdout.strip() == str(tmp_path), placed.stderr[-500:]
    default = _run([sys.executable, "-c", code],
                   env_drop=("JAX_COMPILATION_CACHE_DIR",))
    assert default.stdout.strip() == os.path.join(REPO, ".jax_cache")


def test_warm_up_changes_nothing_but_the_compile():
    """A warmed engine and a cold one give the same verdicts, reports and
    headroom on the same stream, and end in the same device state."""
    def engine():
        return TPUConflictSet(capacity=512, batch_size=32,
                              max_read_ranges=4, max_write_ranges=4)

    warm, cold = engine(), engine()
    assert set(warm.warm_up()) == {
        "resolve", "resolve_report", "advance", "rebase", "reading",
        "repack"}
    rng = np.random.default_rng(1)
    cv = 1000

    def pt(k):
        return KeyRange(k, k + b"\x00")

    for step in range(24):
        txns = []
        for _ in range(int(rng.integers(1, 40))):
            ks = [b"k%03d" % rng.integers(0, 200) for _ in range(3)]
            txns.append(TxnConflictInfo(
                cv - int(rng.integers(1, 300)), [pt(ks[0]), pt(ks[1])],
                [pt(ks[2])] if rng.random() < 0.6 else [],
                report_conflicting_keys=bool(rng.random() < 0.1)))
        cv += int(rng.integers(1, 200))
        assert warm.resolve(txns, cv, cv - 500) == \
            cold.resolve(txns, cv, cv - 500), step
        assert warm.last_conflicting == cold.last_conflicting
        if step % 8 == 5:
            cv += 1
            warm.advance(cv, cv - 400)
            cold.advance(cv, cv - 400)
        assert warm.headroom() == cold.headroom()
    assert warm.dict_stats == cold.dict_stats
    for a, b in zip(jax.tree.leaves(warm.state), jax.tree.leaves(cold.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_native_library_rebuilds_when_its_stamp_does_not_match(
        tmp_path, monkeypatch):
    """A .so from another machine (or another source) is never 'fresh':
    staleness is a hash of source, flags and this host's CPU, not mtime;
    and a missing compiler is an error."""
    from foundationdb_tpu import native

    monkeypatch.setattr(native, "_BUILD", str(tmp_path))
    monkeypatch.setattr(native, "_LIBS", {})
    lib = native.load_library("skiplist")
    assert hasattr(lib, "cs_resolve")
    so = tmp_path / "libskiplist.so"
    stamp = tmp_path / "libskiplist.so.stamp"
    assert stamp.read_text() == native._stamp(
        os.path.join(native._DIR, "skiplist.cpp"))
    assert [p.name for p in tmp_path.iterdir() if ".tmp" in p.name] == []

    # Same mtimes, foreign stamp: rebuilt.
    built = so.stat().st_mtime_ns
    stamp.write_text("built on another CPU")
    monkeypatch.setattr(native, "_LIBS", {})
    native.load_library("skiplist")
    assert so.stat().st_mtime_ns > built
    assert stamp.read_text() != "built on another CPU"

    # Matching stamp: reused as it is.
    built = so.stat().st_mtime_ns
    monkeypatch.setattr(native, "_LIBS", {})
    native.load_library("skiplist")
    assert so.stat().st_mtime_ns == built

    stamp.unlink()
    monkeypatch.setattr(native, "_LIBS", {})
    monkeypatch.setenv("PATH", str(tmp_path))
    with pytest.raises(RuntimeError, match="needs g\\+\\+"):
        native.load_library("skiplist")


def _cluster(tmp_path, monkeypatch, **kw):
    """A SocketCluster that is never started: its per-role environments."""
    from foundationdb_tpu.loadgen.deploy import SocketCluster

    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")  # the chip host's own
    cluster = SocketCluster(str(tmp_path), proxies=1, ratekeeper=False, **kw)
    return {p.name: cluster._env_for(p) for p in cluster.procs}


def test_only_a_tpu_resolver_keeps_the_callers_platform(tmp_path,
                                                        monkeypatch):
    envs = _cluster(tmp_path, monkeypatch, engine="tpu")
    assert envs.pop("resolver0")["JAX_PLATFORMS"] == "tpu,cpu"
    assert {e["JAX_PLATFORMS"] for e in envs.values()} == {"cpu"}
    assert "TPU_VISIBLE_CHIPS" not in envs["sequencer0"]


def test_skiplist_cluster_pins_every_role_to_the_cpu(tmp_path, monkeypatch):
    envs = _cluster(tmp_path, monkeypatch, engine="cpu")
    assert {e["JAX_PLATFORMS"] for e in envs.values()} == {"cpu"}


def test_several_tpu_resolvers_are_each_bound_to_their_own_chip(
        tmp_path, monkeypatch):
    envs = _cluster(tmp_path, monkeypatch, engine="tpu", resolvers=3,
                    env={"JAX_PLATFORMS": "tpu"})
    for i in range(3):
        env = envs[f"resolver{i}"]
        assert env["JAX_PLATFORMS"] == "tpu"
        assert env["TPU_VISIBLE_CHIPS"] == str(i)
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert "TPU_VISIBLE_CHIPS" not in envs["proxy0"]


def test_ready_is_a_line_of_its_own(tmp_path):
    """libtpu and JAX log into the role's file too: `already` or a device
    line must not read as readiness."""
    from foundationdb_tpu.loadgen.deploy import SocketCluster

    cluster = SocketCluster(str(tmp_path), proxies=1, ratekeeper=False)
    p = cluster.procs[0]

    class Alive:
        def poll(self):
            return None

    p.popen = Alive()
    with open(p.log_path, "w") as f:
        f.write("W0000 backend already initialised\n"
                "device resolver0 engine=tpu platform=tpu\n")
    assert not cluster.role_ready(p.name)
    with open(p.log_path, "a") as f:
        f.write(f"ready {p.name} on 127.0.0.1:1\n")
    assert cluster.role_ready(p.name)


def test_resolver_counts_failed_resolves_and_names_no_device_for_host_engines():
    """An engine exception fails the RPC and the role keeps serving: the
    failure has to show in a counter. Host engines have no device."""
    from foundationdb_tpu.runtime.flow import Loop
    from foundationdb_tpu.runtime.resolver import Resolver
    from foundationdb_tpu.sim.oracle import OracleConflictSet

    class Flaky(OracleConflictSet):
        def resolve(self, txns, commit_version, oldest_version=None):
            if commit_version == 20:
                raise RuntimeError("engine fell over")
            return super().resolve(txns, commit_version, oldest_version)

    loop = Loop()
    res = Resolver(loop, Flaky())
    txn = TxnConflictInfo(0, [], [KeyRange(b"k", b"k\x00")])

    async def main():
        await res.resolve(0, 10, [txn])
        with pytest.raises(RuntimeError, match="fell over"):
            await res.resolve(10, 20, [txn])
        await res.resolve(20, 30, [txn])
        return await res.get_metrics()

    m = loop.run(main(), timeout=10)
    assert m["resolve_failures"] == 1
    assert m["batches_resolved"] == 2
    assert m["device"] is None


def test_graft_entry_is_the_program_the_cells_run():
    """entry() hands out the body `ck._resolve_res_jit` jits, with a small
    engine's state and the example transactions as its packer ships them:
    jitted here, its verdicts are the oracle's on the same transactions."""
    import __graft_entry__
    from foundationdb_tpu.models import conflict_kernel as ck
    from foundationdb_tpu.sim.oracle import OracleConflictSet

    fn, args = __graft_entry__.entry()
    assert fn is ck.resolve_batch_res
    assert isinstance(args[0], ck.ResState)
    assert isinstance(args[1], ck.ResidentBatch)
    verdicts, new_state = jax.jit(fn)(*args)
    assert isinstance(new_state, ck.ResState)
    txns = __graft_entry__._example_txns()
    want = OracleConflictSet().resolve(
        txns, __graft_entry__.EXAMPLE_COMMIT, __graft_entry__.EXAMPLE_OLDEST)
    assert [int(v) for v in np.asarray(verdicts)[: len(txns)]] == [
        int(v) for v in want]
    assert len({int(v) for v in want}) == 3  # commits, conflicts, too old


def test_dryrun_multichip_refuses_devices_that_are_not_there():
    import __graft_entry__

    with pytest.raises(RuntimeError, match="needs 64 devices; JAX found 8"):
        __graft_entry__.dryrun_multichip(64)


def test_bench_exit_code_follows_any_recorded_error():
    import bench

    ok = {"value": 1.0, "configs": {"mako": {"skipped": "x"},
                                    "tpcc": {"value": 2.0}}}
    assert not bench._has_error(ok)
    assert bench._has_error({**ok, "adaptive": {"error": "boom"}})
    assert bench._has_error({"configs": {"tpcc": {"error": "boom"}}})
    assert bench.device_peaks(bench.V5E_DEVICE_KIND)["hbm_bytes_per_s"] == 819e9


def test_the_parent_side_stays_off_jax():
    """chip_smoke.py's parent, the launchers, the cli and the client must
    not import JAX: a process that has holds the chip against the one
    resolver that needs it."""
    code = ("import sys, chip_smoke, foundationdb_tpu.server, "
            "foundationdb_tpu.loadgen.deploy, foundationdb_tpu.cli, "
            "foundationdb_tpu.client.ryw, foundationdb_tpu.consistency, "
            "foundationdb_tpu.loadgen.__main__; "
            "print('jax' in sys.modules)")
    r = _run([sys.executable, "-c", code])
    assert r.stdout.strip() == "False", r.stderr[-500:]
