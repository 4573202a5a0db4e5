"""The four-resolver cell end to end on the CPU backend, at a tiny size:
`tiny_f_closed_4r`, four resolver processes behind two proxies, added to a
throw-away copy of the benchmark in rehearsal.py's manner (new files and new
BENCHMARK.json entries only), with --trace 0 and --trace 1; the last line
held to the contract, the range-share check inside its limit, the control
not correct, and the harness process off JAX."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmark.lib import contract, ycsb
from tests.benchmark import rehearsal
from tests.benchmark.test_benchmark_rehearsal import last_line, run_cell

CELL, BASE_CELL = "tiny_f_closed_4r", "ycsb_f_closed_4r"
CONFIG, BASE_CONFIG = "tiny_cluster_4r", "ycsb_cluster_4r"
RECORDS = 400


def _quartile_keys(count: int) -> list:
    ordered = sorted(ycsb.Records(count, seed=0).keys)
    return [ordered[count * q // 4].decode() for q in (1, 2, 3)]


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = rehearsal.build(str(tmp_path_factory.mktemp("bench4r") / "root"))
    b = os.path.join(root, "benchmark")
    with open(os.path.join(b, "configs", BASE_CONFIG + ".json")) as f:
        config = json.load(f)
    config.update(recordcount=RECORDS, load_width=8, load_in_flight=16)
    config["deployment"]["resolver_splits"] = _quartile_keys(RECORDS)
    with open(os.path.join(b, "configs", CONFIG + ".json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["configs"].append(dict(
        contract.find(bm["configs"], BASE_CONFIG, "config"),
        name=CONFIG, file=f"benchmark/configs/{CONFIG}.json"))
    bm["workloads"].append(dict(
        contract.find(bm["workloads"], BASE_CELL, "workload"),
        name=CELL, config=CONFIG, traffic="tiny_closed"))
    for m in bm["end_to_end"] + bm["per_layer"]:
        if BASE_CELL in m.get("workloads", ()):
            m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    contract.validate_benchmark(bm, root)
    return root


def _check(stdout: str, name: str) -> tuple:
    m = re.search(rf"^check {name}: (\S+) \(limit (\S+)\) (.*)$", stdout,
                  re.M)
    assert m, stdout[-2000:]
    return float(m.group(1)), float(m.group(2)), m.group(3)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_four_resolver_cell_runs_and_meets_the_contract(checkout, trace):
    r = run_cell(checkout, CELL, trace)
    line = last_line(r)
    bm = contract.load_benchmark(checkout)
    contract.validate_last_line(line, bm, CELL, bool(trace), cpu_allowed=True)
    assert line["correct"] is True and line["attempted"] > 0
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    assert os.listdir(os.path.join(checkout, ".bench_work")) == []
    share, limit, verdict = _check(r.stdout, "ranges_share_fullest_resolver_pct")
    assert limit == 55 and 25.0 <= share <= limit and verdict == "ok"
    gen = json.loads(re.search(r"^generator (.*)$", r.stdout, re.M).group(1))
    assert len(gen["ranges_received"]) == 4 and min(gen["ranges_received"]) > 0
    assert [c["resolver"] for c in gen["chips"]] == [0, 1, 2, 3]
    if trace:
        dev = line["device"]
        assert 0 < dev["busy_s"] <= dev["window_s"]
        per_layer = contract.declared_metrics(bm, CELL)[1]
        assert {m["name"] for m in per_layer} >= {
            "resolve_straggle_ms.4r", "ranges_share_fullest.4r",
            "chip_busy_least_over_most.4r"}
        assert {m["name"] for m in per_layer
                if m["source"] != "device_trace"} <= set(line["metrics"])
        assert line["metrics"]["resolve_straggle_ms.4r"]["value"] > 0
        assert 25.0 <= line["metrics"]["ranges_share_fullest.4r"]["value"] <= 55
        planes = json.loads(
            re.search(r"^trace (.*)$", r.stdout, re.M).group(1))
        assert [p.split(":")[0] for p in planes["device_planes"]] == [
            "resolver0", "resolver1", "resolver2", "resolver3"]


def test_the_control_comes_out_not_correct_over_four_resolvers(checkout):
    """A read-modify-write that reads at snapshot isolation tells no
    resolver of its read: increments are lost, on whichever resolver."""
    line = last_line(run_cell(checkout, CELL, 0, "--control", "snapshot_rmw"))
    assert line["correct"] is False


def test_the_four_resolver_harness_never_loads_jax(checkout):
    code = ("import sys\n"
            "import benchmark.drivers.cluster_nr, benchmark.lib.cluster_nr\n"
            "import benchmark.lib.observe_nr, benchmark.lib.reference_nr\n"
            "assert 'jax' not in sys.modules, 'the harness imported jax'\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=checkout,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]


def test_a_program_without_the_resolver_map_is_refused_before_any_process(
        checkout, tmp_path):
    """The parent commit's program lacks server.resolver_shard_map: the
    driver's first import fails, the run exits 1 at once and starts
    nothing (the cell is then measured on the change alone)."""
    import shutil
    import time

    root = tmp_path / "root"
    shutil.copytree(checkout, root, symlinks=False, ignore=shutil.ignore_patterns(
        "__pycache__", ".bench_work", "_build", "*.so"))
    server = root / "foundationdb_tpu" / "server.py"
    server.write_text(server.read_text().replace(
        "def resolver_shard_map(", "def _no_resolver_shard_map("))
    t0 = time.monotonic()
    r = run_cell(str(root), CELL, 0)
    assert r.returncode == 1 and "{" not in r.stdout, r.stdout[-500:]
    assert "resolver_shard_map" in r.stderr
    assert time.monotonic() - t0 < 30
    work = root / ".bench_work"
    assert [p for d in work.iterdir() for p in d.iterdir()] == []
