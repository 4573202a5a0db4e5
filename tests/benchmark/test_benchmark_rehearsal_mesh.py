"""The mesh cell end to end on the CPU backend, at a tiny size:
`tiny_f_closed_mesh4`, ONE resolver process whose history is sharded over
four of the host devices behind two proxies, added to a throw-away copy of
the benchmark in rehearsal.py's manner (new files and new BENCHMARK.json
entries only), with --trace 0 and --trace 1; the last line held to the
contract and carrying every declared `.mesh` metric a CPU run can read, the
cell's two checks inside their limits, the control not correct, the harness
process off JAX, a program without the spec key refused before any process;
and what the mesh launcher adds to a trace's reduction, on planes made by
hand (a trace of four chips cannot be taken here)."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmark.lib import contract, mesh_proc
from benchmark.lib.trace_reduce import reduce_planes
from benchmark.readers import collective_per_batch_mesh, device_per_batch_mesh
from tests.benchmark import rehearsal
from tests.benchmark.test_benchmark_rehearsal import last_line, run_cell

REPO = rehearsal.REPO
CELL, BASE_CELL = "tiny_f_closed_mesh4", "ycsb_f_closed_mesh4"
CONFIG, BASE_CONFIG = "tiny_cluster_mesh4", "ycsb_cluster_mesh4"
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    BM = json.load(f)
MESH_METRICS = [m for m in BM["per_layer"] if m["name"].endswith(".mesh")]


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = rehearsal.build(str(tmp_path_factory.mktemp("benchmesh") / "root"))
    b = os.path.join(root, "benchmark")
    with open(os.path.join(b, "configs", BASE_CONFIG + ".json")) as f:
        config = json.load(f)
    config.update(recordcount=400, load_width=8, load_in_flight=16)
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
    m = re.search(rf"^check {name}: (\S+)(?: \(limit (\S+)\) (.*))?$",
                  stdout, re.M)
    assert m, stdout[-2000:]
    return float(m.group(1)), m.group(2), m.group(3)


# -- the accepted benchmark with the cell in it -------------------------------


def test_the_cell_the_configuration_and_the_metrics_are_as_declared():
    cell = contract.find(BM["workloads"], BASE_CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        BASE_CONFIG, "f_closed_64", 4)
    e2e, per_layer = contract.declared_metrics(BM, BASE_CELL)
    assert sorted(m["name"] for m in e2e) == [
        "commit_in_limit_pct", "commits_per_s", "setup_s"]
    assert [m["name"] for m in per_layer] == [m["name"] for m in MESH_METRICS]
    assert len(MESH_METRICS) == 14
    assert all(m["moves"] == "commits_per_s" and m["workloads"] == [BASE_CELL]
               for m in MESH_METRICS)
    # appended: nothing that was there has moved
    assert [m["name"] for m in BM["per_layer"][-14:]] == [
        m["name"] for m in MESH_METRICS]
    assert BM["workloads"][-1]["name"] == BASE_CELL
    assert BM["configs"][-1]["name"] == BASE_CONFIG
    four = [w["name"] for w in BM["workloads"] if w["chips"] == 4]
    assert four == ["ycsb_f_closed_4r", BASE_CELL]


def test_the_configuration_is_the_four_resolver_one_but_for_the_mesh():
    with open(os.path.join(REPO, "benchmark", "configs",
                           BASE_CONFIG + ".json")) as f:
        mesh = json.load(f)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "ycsb_cluster_4r.json")) as f:
        four = json.load(f)
    for key in ("fixed_by_the_source", "guarantees", "reduced", "recordcount",
                "load_width", "load_in_flight", "chips"):
        assert mesh[key] == four[key], key
    dep, dep4 = mesh["deployment"], four["deployment"]
    assert (dep["resolvers"], dep["resolver_mesh"]) == (1, 4)
    assert "resolver_splits" not in dep
    for key in ("sequencer", "engine", "tlogs", "storages", "replicas",
                "proxies", "ratekeeper", "data_dirs"):
        assert dep[key] == dep4[key], key
    assert mesh["driver"] == "cluster_mesh" and list(mesh["reduced"]) == [
        "recordcount"]
    entry = contract.find(BM["configs"], BASE_CONFIG, "config")
    assert entry["source"] == mesh["source"] and len(entry["source"]) <= 200
    checks = mesh["checks"]
    assert 35 < checks["shard_fullest_pct"]["limit"] < 100
    assert checks["auto_reshards_since_boot"]["at_least"] == 1
    for c in checks.values():
        assert "reading" in c["why"]  # both readings, and where from


# -- the tiny cell, run ------------------------------------------------------


@pytest.mark.parametrize("trace", [0, 1])
def test_the_mesh_cell_runs_and_meets_the_contract(checkout, trace):
    r = run_cell(checkout, CELL, trace)
    line = last_line(r)
    bm = contract.load_benchmark(checkout)
    contract.validate_last_line(line, bm, CELL, bool(trace), cpu_allowed=True)
    assert line["correct"] is True and line["attempted"] > 0
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    assert line["device"]["count"] >= 4
    assert os.listdir(os.path.join(checkout, ".bench_work")) == []
    share, limit, verdict = _check(r.stdout, "shard_fullest_pct")
    assert 25.0 <= share <= float(limit) < 100 and verdict == "ok"
    assert _check(r.stdout, "auto_reshards_since_boot")[0] >= 1
    assert _check(r.stdout, "never_resplit") == (0.0, "0", "ok")
    gen = json.loads(re.search(r"^generator (.*)$", r.stdout, re.M).group(1))
    mesh = gen["mesh"]
    assert len(mesh["shard_rows_at_end"]) == 4
    assert min(mesh["shard_rows_at_end"]) > 1  # every shard holds history
    assert mesh["auto_reshards_in_load"] >= 1 and mesh["reshard_probes"] >= 1
    assert mesh["reshard_probe_s"] > 0 and mesh["reshard_s"] > 0
    assert "device resolver0 engine=tpu platform=cpu" in r.stderr
    if trace:
        dev = line["device"]
        assert 0 < dev["busy_s"] <= dev["window_s"]
        per_layer = contract.declared_metrics(bm, CELL)[1]
        assert {m["name"] for m in per_layer} == {
            m["name"] for m in MESH_METRICS}
        # every declared `.mesh` metric a run without a device trace reads
        assert {m["name"] for m in per_layer
                if m["source"] != "device_trace"} == set(line["metrics"]) - {
            "commits_per_s", "commit_in_limit_pct", "setup_s"}
        got = {k: v["value"] for k, v in line["metrics"].items()}
        assert got["reshard_probe_ms.mesh"] > 0
        assert got["device_dispatch_ms.mesh"] > got["host_pack_ms.mesh"] > 0
        assert 25.0 <= got["shard_fullest_pct.mesh"] <= float(limit)
        assert got["auto_reshards.mesh"] >= 0
        assert 0 < got["resolver_loop_busy_share.mesh"] < 1
        assert mesh["trace"] == {"planes": [], "collective_ops": []}


def test_the_control_comes_out_not_correct_on_the_mesh(checkout):
    """A read-modify-write that reads at snapshot isolation tells the
    resolver of no read: increments are lost, on whichever shard. A window
    of 6 s, so that two clients meet on a record however loaded the host
    is (the accepted controls' 2 s now and then sees no lost update beside
    five other workers: PERF.md section 7); a second seed before it counts
    as a failure."""
    for seed in (2 ** 31 + 77, 2 ** 31 + 78):
        r = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload", CELL,
             "--seed", str(seed), "--seconds", "6", "--trace", "0",
             "--control", "snapshot_rmw"],
            cwd=checkout, env=rehearsal.environment(),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            timeout=600)
        line = last_line(r)
        if line["correct"] is False:
            break
    assert line["correct"] is False
    assert re.search(r"^check records_wrong_storage0: [1-9]", r.stdout, re.M)


def test_the_mesh_harness_never_loads_jax(checkout):
    code = ("import sys\n"
            "import benchmark.drivers.cluster_mesh, benchmark.lib.cluster_mesh\n"
            "import benchmark.readers.device_per_batch_mesh\n"
            "import benchmark.readers.collective_per_batch_mesh\n"
            "assert 'jax' not in sys.modules, 'the harness imported jax'\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=checkout,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]


def test_a_program_without_the_spec_key_is_refused_before_any_process(
        checkout, tmp_path):
    """The parent commit's program lacks server.resolver_mesh: the driver's
    first import fails, the run exits 1 at once and starts nothing (the
    cell is then measured on the change alone)."""
    import shutil
    import time

    root = tmp_path / "root"
    shutil.copytree(checkout, root, symlinks=False,
                    ignore=shutil.ignore_patterns(
                        "__pycache__", ".bench_work", "_build", "*.so"))
    server = root / "foundationdb_tpu" / "server.py"
    server.write_text(server.read_text().replace(
        "def resolver_mesh(", "def _no_resolver_mesh(").replace(
        "    resolver_mesh(spec)  #", "    #").replace(
        "mesh=resolver_mesh(spec)", "mesh=None"))
    t0 = time.monotonic()
    r = run_cell(str(root), CELL, 0)
    assert r.returncode == 1 and "{" not in r.stdout, r.stdout[-500:]
    assert "resolver_mesh" in r.stderr
    assert time.monotonic() - t0 < 30
    work = root / ".bench_work"
    assert [p for d in work.iterdir() for p in d.iterdir()] == []


# -- what the launcher adds to a reduction, on planes made by hand -----------

MS = 1_000_000  # ns


def _chip(index: int, starts_ms: list, gather_ms: float, lag_ms: float):
    """One chip's plane: an execution of the resolve program at each start,
    7 ms of fusions with an all-gather and an all-reduce inside; `lag_ms`
    more of the first fusion on this chip (the straggler the others'
    collectives wait for)."""
    ops, modules = [], []
    for t in starts_ms:
        t0 = t * MS
        f1 = (3 + lag_ms) * MS
        ops += [
            ("%fusion.1 = s32[65536]{0:T(1024)} fusion(%p0), kind=kLoop",
             t0, int(f1)),
            ("%all-gather.7 = u32[4,16]{1,0:T(4,128)} all-gather(%fusion.9),"
             " channel_id=1, replica_groups={{0,1,2,3}}",
             t0 + int(f1), int(gather_ms * MS)),
            ("%fusion.2 = s32[65536]{0:T(1024)} fusion(%all-gather.7)",
             t0 + int(f1 + gather_ms * MS), 3 * MS),
            ("%all-reduce.3 = s32[512,8]{1,0} all-reduce(%fusion.2), "
             "to_apply=%add", t0 + int(f1 + (gather_ms + 3) * MS), MS // 2),
        ]
        modules.append(("jit__sharded_resolve_res(123)", t0,
                        int(f1 + (gather_ms + 3.5) * MS)))
    modules.append(("jit__capacity_reading_jit(9)", starts_ms[-1] * MS + 20 * MS,
                    MS // 10))
    return (f"/device:TPU:{index}",
            [("XLA Ops", ops), ("XLA Modules", modules),
             ("Async XLA Ops", [("%copy-start.1 = ...", 0, 50 * MS)])])


def _planes():
    starts = [0, 20, 40, 60]
    # chip 2 lags by 1 ms; the other three wait for it inside the gather
    return [_chip(i, starts, 0.2 if i == 2 else 1.2, 1.0 if i == 2 else 0.0)
            for i in range(4)] + [("/host:CPU", [("main", [
                ("fdb:device_dispatch", 0, 70 * MS)])])]


def test_the_launcher_counts_a_plane_a_chip_and_the_collectives():
    got = mesh_proc.mesh_numbers(_planes())
    assert [p["plane"] for p in got["planes"]] == [
        f"/device:TPU:{i}" for i in range(4)]
    for i, p in enumerate(got["planes"]):
        assert p["executions"] == 4 and p["collectives"] == 8
        assert p["busy_s"] == pytest.approx(4 * 7.7e-3)
        assert p["collective_s"] == pytest.approx(
            4 * (0.7e-3 if i == 2 else 1.7e-3))
    names = [n for n, _count, _s in got["collective_ops"]]
    assert names == ["%all-gather.7 = u32[4,16] all-gather",
                     "%all-reduce.3 = s32[512,8] all-reduce"]
    assert got["collective_ops"][0][1] == 16
    # the CPU backend's stand-in has no device plane: nothing to report
    assert mesh_proc.mesh_numbers([("/host:CPU", [])]) == {
        "planes": [], "collective_ops": []}


def test_the_mesh_readers_divide_a_planes_time_by_a_planes_executions():
    planes = _planes()
    trace = dict(reduce_planes(planes, 0.08),
                 mesh=mesh_proc.mesh_numbers(planes))
    result = {"sources": {"trace": trace}}
    # reduce_planes averages the busy time and SUMS the executions: the
    # accepted reader would be out by the number of chips
    assert trace["modules"]["jit__sharded_resolve_res"][0] == 16
    assert device_per_batch_mesh.read({}, result) == pytest.approx(7.7)
    assert collective_per_batch_mesh.read({}, result) == pytest.approx(1.7)
    for reader in (device_per_batch_mesh, collective_per_batch_mesh):
        assert reader.read({}, {"sources": {}}) is None  # untraced
        assert reader.read({}, {"sources": {"trace": dict(
            trace, stand_in=True)}}) is None  # never a CPU number
        assert reader.read({}, {"sources": {"trace": {
            k: v for k, v in trace.items() if k != "mesh"}}}) is None


@pytest.mark.parametrize("metric", [m["name"] for m in MESH_METRICS])
def test_a_mesh_metric_reads_nothing_and_raises_nothing_untraced(metric):
    """Over an untraced run, and over a program that lacks what the metric
    reads, its reader returns nothing (or the 0.0 of `span_or_zero`) and
    does not raise."""
    import importlib

    with open(os.path.join(REPO, "benchmark", "metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    untraced = {"generator": {}, "checks": []}
    assert reader.read(spec.get("params", {}), untraced) is None
