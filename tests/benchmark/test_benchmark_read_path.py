"""The per-layer metrics PR 38 adds over the stages PR 38 adds to the
program (foundationdb_tpu/obs/span.py: the read path's stages,
`rpc_inbound:<service>.<method>`, `loop_busy:<role>` / `loop_idle:<role>`):
each metric file reads its stage, 0.0 over a program that lacks it (the
parent commit) and nothing untraced; the one new reader on hand-made
histograms; and the traced CPU rehearsals of both closed deployments, which
must print every one of them with a sample.

The four-resolver cell: `test_benchmark_cluster_nr.py` holds
`ycsb_f_closed_4r`'s per-layer list to eleven `.4r` entries, and only a
`benchmark` PR may edit it, so BENCHMARK.json lists these metrics for the
one-resolver cell and the share cells alone. The rehearsal here appends the
tiny four-resolver cell to their `workloads` in its throw-away copy: the
metric files need no twin to read four resolvers' merged spans, only that
one-line edit."""

import json
import os
import re

import pytest

from benchmark.lib import contract
from benchmark.readers import span_share_or_zero
from tests.benchmark import rehearsal
from tests.benchmark import test_benchmark_rehearsal_4r as four
from tests.benchmark.test_benchmark_rehearsal import last_line, run_cell
from tests.benchmark.test_benchmark_scopes import hist_of

REPO = rehearsal.REPO
BM = contract.load_benchmark(REPO)
CLOSED = ["ycsb_f_closed"]
SHARE = ["resolver_share_f", "mako_share_g8ui"]

# metric -> (layer, stage or (busy, idle), statistic, cells)
SPAN_METRICS = {
    "grv_rtt_ms": ("client", "grv_rtt", "mean"),
    "read_rpc_ms": ("client", "read_rpc", "mean"),
    "grv_queue_ms": ("grv proxy", "grv_proxy_queue", "mean"),
    "grv_queue_p95_ms": ("grv proxy", "grv_proxy_queue", "p95"),
    "grv_sequencer_ms": ("grv proxy", "grv_sequencer_rtt", "mean"),
    "resolve_inbound_ms": ("resolver role", "rpc_inbound:resolver.resolve",
                           "mean"),
    "storage_inbound_ms": ("storage", "rpc_inbound:storage.get", "mean"),
    "storage_version_wait_ms": ("storage", "storage_version_wait", "mean"),
    "storage_version_wait_p95_ms": ("storage", "storage_version_wait",
                                    "p95"),
    "storage_lookup_ms": ("storage", "storage_lookup", "mean"),
    "storage_version_lag_p95_ms": ("storage", "storage_version_lag", "p95"),
    "tlog_inbound_ms": ("tlog", "rpc_inbound:tlog.push", "mean"),
}
SHARE_METRICS = {
    "client_loop_busy_share": ("client", "client", CLOSED),
    "proxy_loop_busy_share": ("commit proxy", "proxy", CLOSED),
    "resolver_loop_busy_share": ("resolver role", "resolver", CLOSED),
    "resolver_loop_busy_share.share": ("resolver role", "resolver", SHARE),
    "storage_loop_busy_share": ("storage", "storage", CLOSED),
    "tlog_loop_busy_share": ("tlog", "tlog", CLOSED),
}
NEW_METRICS = sorted(SPAN_METRICS) + sorted(SHARE_METRICS)


def metric_file(name: str) -> dict:
    with open(os.path.join(REPO, "benchmark", "metrics",
                           name + ".json")) as f:
        return json.load(f)


def test_the_new_entries_are_appended_and_the_benchmark_is_valid():
    names = [m["name"] for m in BM["per_layer"]]
    assert sorted(names[-len(NEW_METRICS):]) == sorted(NEW_METRICS)
    contract.validate_benchmark(BM, REPO)
    # the four-resolver cell's list is as the accepted benchmark pins it
    assert len(contract.declared_metrics(BM, "ycsb_f_closed_4r")[1]) == 11


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_new_metric_reads_its_stage_zero_without_it_nothing_untraced(
        metric):
    from benchmark import run as bench_run

    entry = contract.find(BM["per_layer"], metric, "metric")
    spec = metric_file(metric)
    assert entry["source"] == "program_span" and entry["better"] == "lower"
    other = {"device_dispatch": hist_of(80.0)}
    if metric in SPAN_METRICS:
        layer, stage, stat = SPAN_METRICS[metric]
        assert spec == {"reader": "span_or_zero",
                        "params": {"stage": stage, "stat": stat}}
        assert (entry["unit"], entry["workloads"]) == ("ms", CLOSED)
        spans = dict(other, **{stage: hist_of(2.0, 4.0)})
        # a p95 is the upper edge of the bin that holds the sample
        want = 3.0 if stat == "mean" else hist_of(2.0, 4.0).percentile(95)
        assert 3.0 <= want <= 4.0 * 1.05
    else:
        layer, role, cells = SHARE_METRICS[metric]
        assert spec == {"reader": "span_share_or_zero", "params": {
            "num": "loop_busy:" + role, "den": "loop_idle:" + role}}
        assert (entry["unit"], entry["workloads"]) == ("ratio", cells)
        spans = dict(other, **{"loop_busy:" + role: hist_of(30.0, 50.0),
                               "loop_idle:" + role: hist_of(70.0, 50.0)})
        want = 0.4
    assert entry["layer"] == layer
    assert entry["moves"] == ("resolved_per_s" if entry["workloads"] == SHARE
                              else "commits_per_s")
    read = lambda sources: bench_run.read_metric(  # noqa: E731
        metric, {"sources": sources})
    assert read({"spans": spans}) == pytest.approx(want)
    assert read({"spans": other}) == 0.0  # the parent: no such stage
    assert read({}) is None  # an untraced run


def test_span_share_or_zero_divides_the_sums_not_the_counts():
    params = {"num": "loop_busy:resolver", "den": "loop_idle:resolver"}
    spans = {"loop_busy:resolver": hist_of(90.0, 95.0, 100.0),  # 285 ms
             "loop_idle:resolver": hist_of(15.0)}  # one sample, 15 ms
    read = span_share_or_zero.read
    assert read(params, {"sources": {"spans": spans}}) == \
        pytest.approx(285.0 / 300.0)
    # a role that never waited, and one that did nothing else
    assert read(params, {"sources": {"spans": {
        "loop_busy:resolver": hist_of(100.0)}}}) == 1.0
    assert read(params, {"sources": {"spans": {
        "loop_idle:resolver": hist_of(100.0)}}}) == 0.0
    assert read(params, {"sources": {"spans": {}}}) == 0.0
    assert read(params, {"sources": {}}) is None


# -- the traced rehearsals of both closed deployments -------------------------

CLOSED_METRICS = [m for m in NEW_METRICS if not m.endswith(".share")]


@pytest.fixture(scope="module")
def checkout_1r(tmp_path_factory):
    return rehearsal.build(str(tmp_path_factory.mktemp("bench38") / "root"))


@pytest.fixture(scope="module")
def checkout_4r(tmp_path_factory):
    """test_benchmark_rehearsal_4r's copy, built as its fixture builds it,
    with the tiny four-resolver cell also appended to the new metrics'
    `workloads` (this file's docstring)."""
    root = rehearsal.build(str(tmp_path_factory.mktemp("bench38_4r") / "root"))
    with open(os.path.join(root, "benchmark", "configs",
                           four.BASE_CONFIG + ".json")) as f:
        config = json.load(f)
    config.update(recordcount=four.RECORDS, load_width=8, load_in_flight=16)
    config["deployment"]["resolver_splits"] = four._quartile_keys(
        four.RECORDS)
    with open(os.path.join(root, "benchmark", "configs",
                           four.CONFIG + ".json"), "w") as f:
        json.dump(config, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bm = json.load(f)
    bm["configs"].append(dict(
        contract.find(bm["configs"], four.BASE_CONFIG, "config"),
        name=four.CONFIG, file=f"benchmark/configs/{four.CONFIG}.json"))
    bm["workloads"].append(dict(
        contract.find(bm["workloads"], four.BASE_CELL, "workload"),
        name=four.CELL, config=four.CONFIG, traffic="tiny_closed"))
    for m in bm["end_to_end"] + bm["per_layer"]:
        if four.BASE_CELL in m.get("workloads", ()) \
                or m["name"] in CLOSED_METRICS:
            m["workloads"].append(four.CELL)
    with open(path, "w") as f:
        json.dump(bm, f)
    contract.validate_benchmark(bm, root)
    return root


def _traced_metrics(checkout: str, cell: str) -> tuple:
    """(metric -> value of the traced run's last line, its `generator`)."""
    r = run_cell(checkout, cell, 1)
    line = last_line(r)
    contract.validate_last_line(line, contract.load_benchmark(checkout), cell,
                                True, cpu_allowed=True)
    assert line["correct"] is True and line["failed"] == 0
    gen = json.loads(re.search(r"^generator (.*)$", r.stdout, re.M).group(1))
    return {k: v["value"] for k, v in line["metrics"].items()}, gen


def _held(got: dict) -> None:
    """Every new metric printed with a sample, the shares inside (0, 1),
    and the stages that nest in one another in their order."""
    assert set(CLOSED_METRICS) <= set(got)
    for name in CLOSED_METRICS:
        if name.startswith("storage_version_wait"):
            assert got[name] >= 0.0  # every read may be under the version
        else:
            assert got[name] > 0.0, name
        if name.endswith("_share"):
            assert got[name] < 1.0, name
    assert got["grv_sequencer_ms"] <= got["grv_queue_ms"] <= got["grv_rtt_ms"]
    assert got["storage_version_wait_ms"] + got["storage_lookup_ms"] \
        <= got["read_rpc_ms"]
    assert got["grv_queue_ms"] <= got["grv_queue_p95_ms"]


def test_the_traced_closed_cell_prints_every_new_metric(checkout_1r):
    got, gen = _traced_metrics(checkout_1r, "tiny_f_closed")
    _held(got)
    # the named parts of resolve_wait are now more than the engine's bracket
    assert got["resolve_inbound_ms"] + got["coalesce_queue_ms"] \
        + got["host_pack_ms"] + got["device_dispatch_ms"] > \
        got["host_pack_ms"] + got["device_dispatch_ms"]
    assert gen["reads_per_s"] > 0


def test_the_traced_four_resolver_cell_prints_every_new_metric(checkout_4r):
    got, _gen = _traced_metrics(checkout_4r, four.CELL)
    _held(got)
    assert got["resolve_straggle_ms.4r"] > 0
