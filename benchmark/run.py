#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Driven by data: the cell is an entry of BENCHMARK.json's `workloads`; its
configuration is the file BENCHMARK.json names (`benchmark/configs/`), whose
`driver` key names a module of `benchmark/drivers/`; its traffic mix is
`benchmark/traffic/<traffic>.json`; every metric it reports is
`benchmark/metrics/<metric>.json`, which names a reader of
`benchmark/readers/` and its parameters. This file knows no cell,
configuration or metric by name: a later PR adds one by adding files and
entries.

This process never loads JAX: the chip belongs to the resolver process it
starts, which traces itself and reports on its device
(benchmark/lib/resolver_proc.py). The last line of standard output is the
result, validated against the contract before it is printed
(benchmark/lib/contract.py); a run that cannot print a valid one exits
non-zero and prints none.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import tempfile
import time
import types

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WORK = ".bench_work"  # inside the checkout, listed in .gitignore


def log(msg: str) -> None:
    print(f"[benchmark {time.perf_counter() - T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def read_metric(name: str, result: dict):
    """The value of metric `name` in this run, or None where its reader
    finds nothing to read."""
    spec = load_json("benchmark", "metrics", name + ".json")
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    return reader.read(spec.get("params", {}), result)


def judge(checks: list) -> bool:
    """Print each number compared beside its limit; `correct` is whether
    every one is inside its limit. A check with no limit is a count of
    what was compared, printed for the reader."""
    correct = True
    for name, value, limit in checks:
        ok = limit is None or value <= limit
        correct = correct and ok
        print(f"check {name}: {value}"
              + ("" if limit is None else
                 f" (limit {limit}) {'ok' if ok else 'NOT CORRECT'}"))
    return correct


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    # For the controls of benchmark/tests and PERF.md only; the driver of
    # the check never passes them.
    ap.add_argument("--control", default=None)
    ap.add_argument("--fixture", default=None,
                    help="also write the trace, cut down, to this file")
    args = ap.parse_args(argv)
    trace = bool(args.trace)

    from benchmark.lib import contract

    try:
        import foundationdb_tpu  # noqa: F401
    except ImportError:
        log("the program (foundationdb_tpu) is not in this directory")
        return 2
    bm = contract.load_benchmark(ROOT)
    cell = contract.find(bm["workloads"], args.workload, "workload")
    config_entry = contract.find(bm["configs"], cell["config"], "config")
    config = load_json(config_entry["file"])
    traffic = load_json("benchmark", "traffic", cell["traffic"] + ".json")
    driver = importlib.import_module("benchmark.drivers." + config["driver"])

    cpu_on_purpose = os.environ.get("JAX_PLATFORMS") == "cpu"
    if not trace:
        os.environ.pop("FDB_TPU_OBS", None)  # end-to-end numbers untraced
    os.makedirs(os.path.join(ROOT, WORK), exist_ok=True)
    workdir = tempfile.mkdtemp(prefix=args.workload + ".",
                               dir=os.path.join(ROOT, WORK))
    ctx = types.SimpleNamespace(
        root=ROOT, t0=T0, workload=args.workload, config=config,
        config_path=os.path.join(ROOT, config_entry["file"]),
        traffic=traffic, seed=args.seed, seconds=args.seconds, trace=trace,
        workdir=workdir, control=args.control, fixture=args.fixture, log=log)
    try:
        result = driver.run(ctx)
    except Exception as e:  # noqa: BLE001 — a failed run prints no result
        import traceback

        traceback.print_exc()
        log(f"the run failed ({type(e).__name__}); logs are in {workdir}")
        return 1
    shutil.rmtree(workdir, ignore_errors=True)

    correct = judge(result["checks"])
    print("generator " + json.dumps(result["generator"]))

    e2e, per_layer = contract.declared_metrics(bm, args.workload)
    metrics = {}
    for m in (e2e + per_layer) if trace else e2e:
        value = read_metric(m["name"], result)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(result["device"])
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics, "device": device}
    if trace:
        tr = result["sources"]["trace"]
        device.update(window_s=tr["window_s"], busy_s=tr["busy_s"])
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
        print("trace " + json.dumps({k: tr[k] for k in (
            "device_planes", "stand_in", "modules")}))
    try:
        contract.validate_last_line(line, bm, args.workload, trace,
                                    cpu_allowed=cpu_on_purpose)
    except contract.ContractError as e:
        log(f"the result does not meet the contract: {e}")
        log("it was: " + json.dumps(line))
        return 1
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
