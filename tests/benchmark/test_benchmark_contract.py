"""The last-line validator (benchmark/lib/contract.py), fed good and bad
lines for both trace modes, and BENCHMARK.json held to the contract's
limits. PR 22 was refused for a traced line the harness printed unchecked."""

import copy
import json
import os

import pytest

from benchmark.lib import contract

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BM = contract.load_benchmark(REPO)
CELLS = [w["name"] for w in BM["workloads"]]
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
          "memory_peak_bytes": 74924544}


def good_line(cell: str, trace: bool) -> dict:
    e2e, per_layer = contract.declared_metrics(BM, cell)
    due = e2e + per_layer if trace else e2e
    line = {"correct": True, "attempted": 400, "failed": 0,
            "metrics": {m["name"]: {"value": 1.5, "unit": m["unit"]}
                        for m in due},
            "device": dict(DEVICE)}
    if trace:
        line["device"].update(window_s=3.0, busy_s=0.25)
        line["breakdown"] = {"device_ops": [["fusion.1", 0.2]],
                             "idle_gaps": [["PjitFunction(f)", 1.0]]}
    return line


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("cell", CELLS)
def test_a_good_line_passes(cell, trace):
    line = json.loads(json.dumps(good_line(cell, trace)))
    contract.validate_last_line(line, BM, cell, trace)


def _drop(path):
    def f(line):
        node = line
        for p in path[:-1]:
            node = node[p]
        del node[path[-1]]
    return f


def _set(path, value):
    def f(line):
        node = line
        for p in path[:-1]:
            node = node[p]
        node[path[-1]] = value
    return f


def _first_metric(line):
    return next(iter(line["metrics"]))


BAD_TRACED = {
    "busy_s missing": (_drop(["device", "busy_s"]), "busy_s"),
    "window_s missing": (_drop(["device", "window_s"]), "window_s"),
    "busy_s is 0": (_set(["device", "busy_s"], 0.0), "busy_s is 0"),
    "busy_s above window_s": (_set(["device", "busy_s"], 3.5), "above"),
    "busy_s is null": (_set(["device", "busy_s"], None), "finite"),
    "an end-to-end metric missing from a traced line":
        (lambda ln: ln["metrics"].pop(_first_metric(ln)), "is missing"),
    "breakdown too long": (
        _set(["breakdown", "device_ops"], [["op", 0.1]] * 11), "breakdown"),
}
BAD_EITHER = {
    "a metric without a unit":
        (lambda ln: ln["metrics"][_first_metric(ln)].pop("unit"), "value, unit"),
    "a metric as a bare number":
        (lambda ln: ln["metrics"].__setitem__(_first_metric(ln), 2.0),
         "value, unit"),
    "a declared metric missing":
        (lambda ln: ln["metrics"].pop(list(ln["metrics"])[-1]), "is missing"),
    "a NaN": (lambda ln: ln["metrics"][_first_metric(ln)].__setitem__(
        "value", float("nan")), "finite"),
    "a null value": (lambda ln: ln["metrics"][_first_metric(ln)].__setitem__(
        "value", None), "finite"),
    "a wrong unit": (lambda ln: ln["metrics"][_first_metric(ln)].__setitem__(
        "unit", "furlongs"), "unit"),
    "an undeclared metric": (lambda ln: ln["metrics"].__setitem__(
        "made_up", {"value": 1.0, "unit": "ms"}), "not declared"),
    "no device": (_drop(["device"]), "device"),
    "no memory peak": (_drop(["device", "memory_peak_bytes"]), "memory_peak"),
    "memory peak 0 on a tpu": (_set(["device", "memory_peak_bytes"], 0),
                               "memory_peak"),
    "platform cpu": (_set(["device", "platform"], "cpu"), "platform"),
    "four devices in a one-chip cell": (_set(["device", "count"], 4), "count"),
    "correct as a string": (_set(["correct"], "true"), "correct"),
    "failed above attempted": (_set(["failed"], 401), "failed"),
    "attempted missing": (_drop(["attempted"]), "attempted"),
}


@pytest.mark.parametrize("what", sorted(BAD_TRACED))
@pytest.mark.parametrize("cell", CELLS)
def test_a_bad_traced_line_is_refused_by_name(cell, what):
    line = good_line(cell, True)
    mutate, clause = BAD_TRACED[what]
    mutate(line)
    with pytest.raises(contract.ContractError, match=clause):
        contract.validate_last_line(line, BM, cell, True)


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("what", sorted(BAD_EITHER))
def test_a_bad_line_is_refused_by_name(what, trace):
    line = good_line(CELLS[0], trace)
    mutate, clause = BAD_EITHER[what]
    mutate(line)
    with pytest.raises(contract.ContractError, match=clause):
        contract.validate_last_line(line, BM, CELLS[0], trace)


def test_a_cpu_rehearsal_is_taken_only_when_it_was_asked_for():
    line = good_line(CELLS[0], True)
    line["device"].update(platform="cpu", kind="cpu", memory_peak_bytes=0)
    contract.validate_last_line(line, BM, CELLS[0], True, cpu_allowed=True)
    with pytest.raises(contract.ContractError, match="platform"):
        contract.validate_last_line(line, BM, CELLS[0], True)


def test_benchmark_json_is_inside_the_contracts_limits():
    contract.validate_benchmark(BM, REPO)


BAD_BENCHMARKS = {
    "an extra key": lambda bm: bm.__setitem__("notes", "x"),
    "a why on a metric": lambda bm: bm["per_layer"][0].__setitem__("why", "x"),
    "a bound over a quarter":
        lambda bm: bm["end_to_end"][0].__setitem__("bound", 0.3),
    "a unit with a space":
        lambda bm: bm["end_to_end"][0].__setitem__("unit", "txn per s"),
    "a name with a slash":
        lambda bm: bm["workloads"][0].__setitem__("name", "a/b"),
    "a why of two lines":
        lambda bm: bm["workloads"][0].__setitem__("why", "a\nb"),
    "no setup_s": lambda bm: bm["end_to_end"].pop(),
    "run_seconds too long": lambda bm: bm.__setitem__("run_seconds", 52),
    "a config file outside paths":
        lambda bm: bm["configs"][0].__setitem__("file", "README.md"),
    "a metric that moves nothing":
        lambda bm: bm["per_layer"][0].__setitem__("moves", "nothing"),
    "an end-to-end metric read from the program":
        lambda bm: bm["end_to_end"][0].__setitem__("source", "program_span"),
    "a command outside paths":
        lambda bm: bm.__setitem__("command", ["python3", "bench.py"]),
}


@pytest.mark.parametrize("what", sorted(BAD_BENCHMARKS))
def test_a_benchmark_json_outside_the_limits_is_refused(what):
    bm = copy.deepcopy(BM)
    BAD_BENCHMARKS[what](bm)
    with pytest.raises(contract.ContractError):
        contract.validate_benchmark(bm, REPO)


@pytest.mark.parametrize(
    "metric", [m["name"] for m in BM["end_to_end"] + BM["per_layer"]])
def test_every_metric_has_a_file_that_names_a_reader(metric):
    import importlib

    with open(os.path.join(REPO, "benchmark", "metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    assert callable(reader.read)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_has_its_configuration_driver_and_traffic(cell):
    import importlib

    w = contract.find(BM["workloads"], cell, "workload")
    c = contract.find(BM["configs"], w["config"], "config")
    with open(os.path.join(REPO, c["file"])) as f:
        config = json.load(f)
    assert callable(importlib.import_module(
        "benchmark.drivers." + config["driver"]).run)
    assert config["chips"] == w["chips"]
    assert sorted(config["reduced"]) == sorted(c["reduced"])
    with open(os.path.join(REPO, "benchmark", "traffic",
                           w["traffic"] + ".json")) as f:
        assert isinstance(json.load(f), dict)
