"""The TPC-C cell end to end on the CPU backend, at a tiny size: a copied
checkout (rehearsal.py), the tiny configuration and cell ADDED to it as new
files and entries, run traced and untraced, the last line held to the
contract. And the runs that must not give a result: one verdict altered
where it arrives, every true range sent as its begin's point, and a program
whose resolver counts no true range."""

import json
import os
import shutil
import time
import types

import pytest

from benchmark.lib import contract
from tests.benchmark import rehearsal
from tests.benchmark.test_benchmark_rehearsal import SEED, last_line, run_cell

CELL = "tiny_tpcc_mix"
TRAFFIC = "tiny_depth_tpcc"
# The configuration's own rule at a rate the CPU backend keeps up with
# (375 x 128 boundaries -> 1<<16, 270 x 128 keys -> 1<<16), and few enough
# warehouses that a third of the transactions conflict.
TINY = {"nominal_rate_per_s": 128, "warehouses": 40,
        "prefill_at_most_windows": 2,
        "engine": {"capacity": 1 << 16, "dict_capacity": 1 << 16,
                   "batch_size": 64, "max_read_ranges": 8,
                   "max_write_ranges": 8, "max_key_bytes": 32}}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = rehearsal.build(str(tmp_path_factory.mktemp("tpcc") / "root"))
    b = os.path.join(root, "benchmark")
    rehearsal._derive(os.path.join(b, "configs", "tpcc_resolver_share.json"),
                      os.path.join(b, "configs", "tiny_tpcc.json"), TINY)
    # share_depth8 plans for four times the nominal rate and this stream
    # does not wrap; an idle CPU runs the tiny cell at more than that
    rehearsal._derive(os.path.join(b, "traffic", "tiny_depth.json"),
                      os.path.join(b, "traffic", TRAFFIC + ".json"),
                      {"plan_rate_factor": 32})
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["configs"].append(dict(
        next(c for c in bm["configs"] if c["name"] == "tpcc_resolver_share"),
        name="tiny_tpcc", file="benchmark/configs/tiny_tpcc.json"))
    bm["workloads"].append(dict(
        next(w for w in bm["workloads"] if w["name"] == "tpcc_share_mix"),
        name=CELL, config="tiny_tpcc", traffic=TRAFFIC))
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "tpcc_share_mix" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    contract.validate_benchmark(bm, root)
    yield root
    shutil.rmtree(os.path.join(root, ".bench_work"), ignore_errors=True)


def _generator(r) -> dict:
    return json.loads(next(
        ln for ln in r.stdout.splitlines() if ln.startswith("generator ")
    )[len("generator "):])


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_and_its_last_line_meets_the_contract(checkout, trace):
    r = run_cell(checkout, CELL, trace)
    line = last_line(r)
    bm = contract.load_benchmark(checkout)
    contract.validate_last_line(line, bm, CELL, bool(trace), cpu_allowed=True)
    assert line["correct"] is True and line["attempted"] > 0
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    assert os.listdir(os.path.join(checkout, ".bench_work")) == []
    for check in ("verdicts_wrong", "true_ranges_not_received",
                  "ranges_not_received", "keys_widened",
                  "batches_fail_safe"):
        assert f"check {check}: 0 (limit 0) ok" in r.stdout
    generator = _generator(r)
    assert generator["prefill_batches"] >= generator[
        "prefill_batches_one_window"] == 10
    assert generator["built_late"] * 10 <= generator["batches"]
    assert generator["reference_conflict_share"] > 0.1
    assert generator["longest_key_bytes"] == 17
    assert generator["read_only_dropped"] > generator[
        "rolled_back_dropped"] >= 0
    sent = generator["sent"]
    assert sent["new_order"] > sent["payment"] > 8 * sent["delivery"] > 0
    # what the clauses reckon, at any W: a deck holds the mix exactly and
    # the widths as evenly as it allows (set-up and window: ~20 decks)
    assert generator["ranges_per_txn_sent"] == pytest.approx(33.4, rel=0.02)
    assert generator["true_ranges_per_txn_sent"] == pytest.approx(
        1.15, rel=0.04)
    since_boot = generator["since_boot"]
    assert since_boot["true_ranges_received"] > 0
    assert since_boot["dispatches"] > since_boot["full_repacks"] >= 0
    if trace:
        metrics = line["metrics"]
        per_layer = contract.declared_metrics(bm, CELL)[1]
        assert {m["name"] for m in per_layer
                if m["source"] != "device_trace"} <= set(metrics)
        # the same over the window alone, a handful of decks
        assert metrics["ranges_per_txn.tpcc"]["value"] == pytest.approx(
            33.4, rel=0.06)
        assert metrics["rows_per_txn.tpcc"]["value"] == pytest.approx(
            2.84, rel=0.06)
        assert metrics["true_ranges_per_txn.tpcc"]["value"] == pytest.approx(
            1.15, rel=0.1)
        assert 2.5 <= metrics["dispatches_per_batch.tpcc"]["value"] <= 4.5
        assert 65 <= metrics["slot_fill_pct.tpcc"]["value"] <= 80
        assert metrics["wide_layout_ms.tpcc"]["value"] > 0


def _ctx(checkout: str, name: str, control=None):
    with open(os.path.join(checkout, "benchmark/configs/tiny_tpcc.json")) as f:
        config = json.load(f)
    with open(os.path.join(checkout, "benchmark/traffic",
                           TRAFFIC + ".json")) as f:
        traffic = json.load(f)
    workdir = os.path.join(checkout, ".bench_work", name)
    os.makedirs(workdir)
    return types.SimpleNamespace(
        root=checkout, t0=time.perf_counter(), workload=CELL, config=config,
        config_path=os.path.join(checkout,
                                 "benchmark/configs/tiny_tpcc.json"),
        traffic=traffic, seed=int(SEED), seconds=1.0, trace=False,
        workdir=workdir, control=control, fixture=None, log=lambda _m: None)


@pytest.mark.time_limit(400)
def test_a_verdict_altered_where_it_arrives_comes_out_not_correct(
        checkout, monkeypatch):
    import benchmark.run as bench_run
    from benchmark.drivers import resolver_replay_mako, resolver_replay_tpcc

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       rehearsal.environment()["JAX_COMPILATION_CACHE_DIR"])
    real_pump = resolver_replay_mako.pump

    async def broken_pump(*args, **kwargs):
        rows = await real_pump(*args, **kwargs)
        rows[len(rows) // 2][3][5] ^= 1
        return rows

    monkeypatch.setattr(resolver_replay_mako, "pump", broken_pump)
    ctx = _ctx(checkout, "broken")
    try:
        result = resolver_replay_tpcc.run(ctx)
    finally:
        shutil.rmtree(ctx.workdir, ignore_errors=True)
    assert ("verdicts_wrong", 1, 0) in result["checks"]
    assert bench_run.judge(result["checks"]) is False


def test_true_ranges_sent_as_points_come_out_not_correct(checkout):
    """`--control ranges_as_points`: the reference judges the stream as it
    was dealt, the role is sent every true range as its begin's point. The
    role counts no true range, and a delivery whose district another one
    emptied first is let through."""
    r = run_cell(checkout, CELL, 0, "--control", "ranges_as_points")
    line = last_line(r)
    assert line["correct"] is False and line["failed"] > 0
    checks = dict(ln[len("check "):].split(": ", 1)
                  for ln in r.stdout.splitlines() if ln.startswith("check "))
    assert checks["true_ranges_not_received"].endswith("NOT CORRECT")
    assert checks["verdicts_wrong"].endswith("NOT CORRECT")
    assert checks["ranges_not_received"] == "0 (limit 0) ok"
    # an exact engine sent points is wrong in just the verdicts that the
    # reference reckons to rest on a true range
    assert checks["verdicts_wrong"].split()[0] == checks[
        "reference_verdicts_on_true_ranges"] != "0"
    assert _generator(r)["since_boot"]["true_ranges_received"] == 0


def test_a_program_that_counts_no_true_range_is_refused_at_once(
        checkout, monkeypatch):
    """The parent of this driver's PR: its resolver's `get_metrics()` has
    no `true_ranges_received` (nor `slots_filled`). The run ends by itself,
    at once, with no process started."""
    from benchmark.drivers import resolver_replay_tpcc
    from foundationdb_tpu.runtime.flow import Loop
    from foundationdb_tpu.runtime.resolver import Resolver

    assert resolver_replay_tpcc.counts_true_ranges(Loop(seed=1))
    real = Resolver.get_metrics

    async def the_parents(self):
        m = await real(self)
        return {k: v for k, v in m.items()
                if k not in ("true_ranges_received", "slots_filled")}

    monkeypatch.setattr(Resolver, "get_metrics", the_parents)
    assert not resolver_replay_tpcc.counts_true_ranges(Loop(seed=1))
    ctx = _ctx(checkout, "refused")
    t0 = time.perf_counter()
    try:
        with pytest.raises(RuntimeError, match="true_ranges_received"):
            resolver_replay_tpcc.run(ctx)
        assert os.listdir(ctx.workdir) == []
    finally:
        shutil.rmtree(ctx.workdir, ignore_errors=True)
    assert time.perf_counter() - t0 < 5.0
