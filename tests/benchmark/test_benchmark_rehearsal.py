"""The benchmark end to end on the CPU backend, at a tiny size: every cell
with --trace 0 and --trace 1 through the resolver launcher and its control
thread (the CPU backend's profiler lines stand in for the device's), the
last stdout line held to the contract with nothing after it; the runs that
must print NO result; and the controls, which must come out not correct.

The tiny cells are files ADDED to a copy of the benchmark (rehearsal.py):
the proof that a cell, a configuration, a traffic mix and a per-layer metric
can be added with no edit to a file that is there.
"""

import filecmp
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.lib import contract
from tests.benchmark import rehearsal

SECONDS = "2"
SEED = str(2 ** 31 + 77)  # the driver's seeds pass 32 signed bits


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return rehearsal.build(str(tmp_path_factory.mktemp("bench") / "root"))


def run_cell(checkout, cell, trace, *extra, env=None):
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         SEED, "--seconds", SECONDS, "--trace", str(trace), *extra],
        cwd=checkout, env=env or rehearsal.environment(),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=600)


def last_line(r) -> dict:
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.endswith("\n") and not r.stdout.endswith("\n\n")
    return json.loads(r.stdout.splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", sorted(rehearsal.TINY_CELLS))
def test_a_cell_runs_and_its_last_line_meets_the_contract(
        checkout, cell, trace):
    line = last_line(run_cell(checkout, cell, trace))
    bm = contract.load_benchmark(checkout)
    contract.validate_last_line(line, bm, cell, bool(trace), cpu_allowed=True)
    assert line["correct"] is True and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert os.listdir(os.path.join(checkout, ".bench_work")) == []
    if trace:
        dev = line["device"]
        assert 0 < dev["busy_s"] <= dev["window_s"]
        assert line["breakdown"]["device_ops"]
        per_layer = contract.declared_metrics(bm, cell)[1]
        assert {m["name"] for m in per_layer
                if m["source"] != "device_trace"} <= set(line["metrics"])


def test_the_tiny_cells_were_added_without_editing_a_file(checkout):
    """Every file of the benchmark is in the copy byte for byte; the tiny
    cells are new files and new BENCHMARK.json entries."""
    cmp = filecmp.dircmp(os.path.join(rehearsal.REPO, "benchmark"),
                         os.path.join(checkout, "benchmark"),
                         ignore=["__pycache__"])
    stack, added = [cmp], []
    while stack:
        c = stack.pop()
        assert not c.diff_files and not c.left_only, (c.diff_files,
                                                      c.left_only)
        added += c.right_only
        stack += c.subdirs.values()
    assert sorted(added) == sorted(
        n + ".json" for n in list(rehearsal.TINY_CONFIGS)
        + list(rehearsal.TINY_TRAFFIC) + list(rehearsal.TINY_METRICS))
    contract.validate_benchmark(contract.load_benchmark(checkout), checkout)


def test_without_a_tpu_a_run_fails_and_prints_no_result(checkout):
    env = rehearsal.environment()
    del env["JAX_PLATFORMS"]  # JAX falls back to the CPU in silence
    r = run_cell(checkout, "tiny_share_f", 0, env=env)
    assert r.returncode != 0 and "{" not in r.stdout, r.stdout[-500:]


def test_alone_in_a_directory_a_run_fails_and_prints_no_result(tmp_path):
    import shutil

    shutil.copytree(os.path.join(rehearsal.REPO, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(rehearsal.REPO, "BENCHMARK.json"), tmp_path)
    r = run_cell(str(tmp_path), "ycsb_f_closed", 0)
    assert r.returncode != 0 and r.stdout == ""


def test_the_harness_process_never_loads_jax(checkout):
    code = ("import sys, runpy; sys.argv = ['run.py', '--help']\n"
            "try:\n runpy.run_path('benchmark/run.py', run_name='__main__')\n"
            "except SystemExit: pass\n"
            "import benchmark.drivers.cluster, benchmark.drivers.resolver_replay\n"
            "import benchmark.lib.cluster, benchmark.lib.observe\n"
            "assert 'jax' not in sys.modules, 'the harness imported jax'\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=checkout,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]


@pytest.mark.parametrize("cell,control", [
    ("tiny_f_closed", "snapshot_rmw"), ("tiny_share_f", "small_history")])
def test_the_control_comes_out_not_correct(checkout, cell, control):
    """The control breaks one guarantee the configuration states: a
    read-modify-write that reads at snapshot isolation loses increments; a
    history a sixty-fourth of the stated size trips the capacity
    fail-safe. Either must fail the run's own comparison."""
    line = last_line(run_cell(checkout, cell, 0, "--control", control))
    assert line["correct"] is False


def test_a_verdict_altered_where_it_arrives_comes_out_not_correct(
        checkout, monkeypatch):
    """Drive a whole run of the replay driver in this process, with the
    timed path broken underneath: one verdict flipped as the emulated proxy
    receives it. The run's own comparison has to see it."""
    import time

    import benchmark.run as bench_run
    from benchmark.drivers import resolver_replay

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       rehearsal.environment()["JAX_COMPILATION_CACHE_DIR"])
    real_pump = resolver_replay.pump

    async def broken_pump(*args, **kwargs):
        rows = await real_pump(*args, **kwargs)
        rows[len(rows) // 2][3][5] ^= 1
        return rows

    monkeypatch.setattr(resolver_replay, "pump", broken_pump)
    with open(os.path.join(checkout, "benchmark/configs/tiny_share.json")) as f:
        config = json.load(f)
    with open(os.path.join(checkout, "benchmark/traffic/tiny_depth.json")) as f:
        traffic = json.load(f)
    workdir = os.path.join(checkout, ".bench_work", "broken")
    os.makedirs(workdir)
    ctx = types.SimpleNamespace(
        root=checkout, t0=time.perf_counter(), workload="tiny_share_f",
        config=config, config_path=os.path.join(
            checkout, "benchmark/configs/tiny_share.json"),
        traffic=traffic, seed=int(SEED), seconds=1.0, trace=False,
        workdir=workdir, control=None, fixture=None, log=lambda _m: None)
    result = resolver_replay.run(ctx)
    assert ("verdicts_wrong", 1, 0) in result["checks"]
    assert bench_run.judge(result["checks"]) is False
