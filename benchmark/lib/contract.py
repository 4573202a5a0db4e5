"""BENCHMARK.json look-ups and the validator of a run's last line.

The validator is written from the contract's text: the last line of standard
output is one JSON object with the keys ``correct``, ``attempted``,
``failed``, ``metrics`` and ``device``; ``metrics`` gives each metric the
cell declares for the run's mode as ``{"value": <finite number>, "unit":
<its unit>}``; ``device`` has ``platform``, ``kind``, ``count`` and
``memory_peak_bytes`` and, in a traced run, ``window_s`` and ``busy_s`` with
``0 < busy_s <= window_s``. A run validates its own line before printing it,
and prints nothing when it does not pass.
"""

from __future__ import annotations

import json
import math
import os
import re

BENCHMARK_FILE = "BENCHMARK.json"


class ContractError(ValueError):
    """A last line (or BENCHMARK.json look-up) that the contract refuses.
    The message names the clause."""


def load_benchmark(root: str) -> dict:
    with open(os.path.join(root, BENCHMARK_FILE)) as f:
        return json.load(f)


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise ContractError(
        f"BENCHMARK.json has no {what} named {name!r}; "
        f"it has {[e['name'] for e in entries]}")


def _applies(metric: dict, cell: dict, e2e_names: set) -> bool:
    listed = metric.get("workloads")
    if listed is not None:
        return cell["name"] in listed
    moves = metric.get("moves")
    # A per-layer metric without `workloads` is due in every cell that
    # reports the end-to-end metric it moves.
    return moves is None or moves in e2e_names


def declared_metrics(bm: dict, workload: str) -> tuple[list, list]:
    """(end-to-end, per-layer) metric entries that cell `workload` reports."""
    cell = find(bm["workloads"], workload, "workload")
    e2e = [m for m in bm["end_to_end"] if _applies(m, cell, set())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"] if _applies(m, cell, names)]
    return e2e, per_layer


def _number(x) -> bool:
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def validate_last_line(line: dict, bm: dict, workload: str, trace: bool,
                       cpu_allowed: bool = False) -> None:
    """Raise ContractError, naming the clause, unless `line` is a result
    the contract takes for cell `workload` in this mode. ``cpu_allowed``:
    the run was told JAX_PLATFORMS=cpu on purpose (a rehearsal); only then
    may `platform` be "cpu" and `memory_peak_bytes` be 0."""
    if not isinstance(line, dict):
        raise ContractError("the last line is not a JSON object")
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        if key not in line:
            raise ContractError(f"key {key!r} is missing")
    if not isinstance(line["correct"], bool):
        raise ContractError("`correct` is not true or false")
    for key in ("attempted", "failed"):
        if not (isinstance(line[key], int) and not isinstance(line[key], bool)
                and line[key] >= 0):
            raise ContractError(f"`{key}` is not a whole number >= 0")
    if line["failed"] > line["attempted"]:
        raise ContractError("`failed` is above `attempted`")

    e2e, per_layer = declared_metrics(bm, workload)
    due = e2e + per_layer if trace else e2e
    known = {m["name"]: m for m in e2e + per_layer}
    metrics = line["metrics"]
    if not isinstance(metrics, dict):
        raise ContractError("`metrics` is not an object")
    on_cpu = cpu_allowed and isinstance(line["device"], dict) \
        and line["device"].get("platform") == "cpu"
    for m in due:
        if on_cpu and m["source"] == "device_trace":
            continue  # a rehearsal on the CPU backend has no device trace
        if m["name"] not in metrics:
            raise ContractError(
                f"metric {m['name']!r}, declared for {workload} with "
                f"--trace {int(trace)}, is missing")
    for name, got in metrics.items():
        if name not in known:
            raise ContractError(
                f"metric {name!r} is not declared for {workload}")
        if not (isinstance(got, dict) and "value" in got and "unit" in got):
            raise ContractError(
                f"metric {name!r} is not given as {{value, unit}}")
        if not _number(got["value"]):
            raise ContractError(
                f"metric {name!r} has the value {got['value']!r}, "
                "not a finite number")
        if got["unit"] != known[name]["unit"]:
            raise ContractError(
                f"metric {name!r} has the unit {got['unit']!r}, "
                f"BENCHMARK.json says {known[name]['unit']!r}")

    dev = line["device"]
    if not isinstance(dev, dict):
        raise ContractError("`device` is not an object")
    for key in ("platform", "kind", "count", "memory_peak_bytes"):
        if key not in dev:
            raise ContractError(f"device.{key} is missing")
    if not (isinstance(dev["platform"], str) and isinstance(dev["kind"], str)):
        raise ContractError("device.platform and device.kind are strings")
    if dev["platform"] != "tpu" and not (
            cpu_allowed and dev["platform"] == "cpu"):
        raise ContractError(
            f"device.platform is {dev['platform']!r}, not 'tpu' (a CPU run "
            "is taken only where JAX_PLATFORMS=cpu was set on purpose)")
    cell = find(bm["workloads"], workload, "workload")
    if dev["count"] != cell["chips"] and dev["platform"] == "tpu":
        raise ContractError(
            f"device.count is {dev['count']!r}, the cell asks for "
            f"{cell['chips']} chip(s)")
    peak = dev["memory_peak_bytes"]
    if not (isinstance(peak, int) and not isinstance(peak, bool)):
        raise ContractError("device.memory_peak_bytes is not a whole number")
    if peak <= 0 and not (peak == 0 and dev["platform"] == "cpu"):
        raise ContractError(
            "device.memory_peak_bytes is not above 0 (0 is taken only from "
            "the CPU backend, which reports no memory)")
    if trace:
        for key in ("window_s", "busy_s"):
            if key not in dev:
                raise ContractError(f"device.{key} is missing in a traced run")
            if not _number(dev[key]):
                raise ContractError(f"device.{key} is not a finite number")
        if not dev["busy_s"] > 0:
            raise ContractError(
                "device.busy_s is 0: no operation ran on the device in the "
                "traced window, or the trace was not taken in the process "
                "that holds the chip")
        if dev["busy_s"] > dev["window_s"]:
            raise ContractError("device.busy_s is above device.window_s")
    if "breakdown" in line:
        bd = line["breakdown"]
        if not isinstance(bd, dict):
            raise ContractError("`breakdown` is not an object")
        for key, rows in bd.items():
            if key not in ("device_ops", "idle_gaps"):
                raise ContractError(f"breakdown.{key} is not a known list")
            if not (isinstance(rows, list) and len(rows) <= 10 and all(
                    isinstance(r, list) and len(r) == 2
                    and isinstance(r[0], str) and _number(r[1])
                    for r in rows)):
                raise ContractError(
                    f"breakdown.{key} is not at most 10 [name, seconds] pairs")


# -- BENCHMARK.json itself ----------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
MAX_RUN_SECONDS = 51
CHECK_BUDGET_S = 43200


def _line(s, what: str) -> None:
    if not (isinstance(s, str) and 1 <= len(s) <= 200
            and "\n" not in s and "\t" not in s):
        raise ContractError(f"{what} is not 1 to 200 characters on one line")


def validate_benchmark(bm: dict, root: str) -> None:
    """The limits the contract sets on BENCHMARK.json, checked before any
    run; raises ContractError naming the first one that is broken."""
    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(bm) != keys:
        raise ContractError(f"BENCHMARK.json has the keys {sorted(bm)}, "
                            f"not exactly {sorted(keys)}")
    if not 1 <= len(bm["paths"]) <= 16:
        raise ContractError("`paths` has not 1 to 16 directories")
    for p in bm["paths"]:
        if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            raise ContractError(f"path {p!r} is not a relative path of the "
                                "allowed characters")
    if not 1 <= len(bm["command"]) <= 32:
        raise ContractError("`command` has not 1 to 32 words")
    for word in bm["command"]:
        _line(word, f"command word {word!r}")
        if word.startswith("/") or ".." in word.split("/"):
            raise ContractError(f"command word {word!r} leaves the repo")
        if os.path.exists(os.path.join(root, word)) and not any(
                word == p or word.startswith(p + "/") for p in bm["paths"]):
            raise ContractError(f"command names {word!r}, outside `paths`")
    rs = bm["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= MAX_RUN_SECONDS):
        raise ContractError("`run_seconds` is not a whole number 1 to 51")
    if (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 > CHECK_BUDGET_S:
        raise ContractError("a full check of 24 cells would not fit")

    def under_paths(f: str) -> bool:
        return any(f.startswith(p + "/") for p in bm["paths"])

    def unique(entries, what):
        names = [e["name"] for e in entries]
        for n in names:
            if not NAME.match(n):
                raise ContractError(f"{what} name {n!r} is not a name")
        if len(set(names)) != len(names):
            raise ContractError(f"two {what}s have the same name")
        return set(names)

    if not 1 <= len(bm["configs"]) <= 24:
        raise ContractError("`configs` has not 1 to 24 entries")
    configs = unique(bm["configs"], "configuration")
    files = set()
    for c in bm["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            raise ContractError(f"configuration {c['name']} has other keys")
        _line(c["source"], f"{c['name']}.source")
        _line(c["why"], f"{c['name']}.why")
        if not (PATH.match(c["file"]) and under_paths(c["file"])):
            raise ContractError(f"{c['file']} is not under `paths`")
        if c["file"] in files or not os.path.isfile(
                os.path.join(root, c["file"])):
            raise ContractError(f"{c['file']} is missing or used twice")
        files.add(c["file"])
        if len(c["reduced"]) > 16 or not all(
                NAME.match(k) for k in c["reduced"]):
            raise ContractError(f"{c['name']}.reduced is not <= 16 names")
    if not 1 <= len(bm["workloads"]) <= 24:
        raise ContractError("`workloads` has not 1 to 24 cells")
    cells = unique(bm["workloads"], "workload")
    pairs = set()
    for w in bm["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            raise ContractError(f"workload {w['name']} has other keys")
        _line(w["why"], f"{w['name']}.why")
        if w["config"] not in configs:
            raise ContractError(f"{w['name']} names no configuration")
        if not NAME.match(w["traffic"]) or w["chips"] not in (1, 4):
            raise ContractError(f"{w['name']}: traffic or chips is wrong")
        if (w["config"], w["traffic"]) in pairs:
            raise ContractError(f"{w['name']}: the pair appears twice")
        pairs.add((w["config"], w["traffic"]))
    if configs - {w["config"] for w in bm["workloads"]}:
        raise ContractError("a configuration is used by no cell")
    four = sum(1 for w in bm["workloads"] if w["chips"] == 4)
    if four > max(1, len(bm["workloads"]) // 2):
        raise ContractError("too many cells ask for 4 chips")

    if not 1 <= len(bm["end_to_end"]) <= 16:
        raise ContractError("`end_to_end` has not 1 to 16 metrics")
    if not 1 <= len(bm["per_layer"]) <= 128:
        raise ContractError("`per_layer` has not 1 to 128 metrics")
    unique(bm["end_to_end"] + bm["per_layer"], "metric")
    e2e_names = {m["name"] for m in bm["end_to_end"]}
    if "setup_s" not in e2e_names:
        raise ContractError("no `setup_s` among the end-to-end metrics")
    for m in bm["end_to_end"] + bm["per_layer"]:
        e2e = m["name"] in e2e_names
        want = {"name", "unit", "better", "source"} | (
            {"bound"} if e2e else {"layer", "moves"})
        if set(m) - {"workloads"} != want:
            raise ContractError(f"metric {m['name']} has other keys")
        if not UNIT.match(m["unit"]) or m["better"] not in ("lower", "higher"):
            raise ContractError(f"metric {m['name']}: unit or better")
        allowed = ("host_clock", "device_trace") if e2e else SOURCES
        if m["source"] not in allowed:
            raise ContractError(f"metric {m['name']}: source {m['source']!r}")
        if e2e and not (_number(m["bound"]) and 0.01 <= m["bound"] <= 0.25):
            raise ContractError(f"metric {m['name']}: bound")
        if not e2e:
            _line(m["layer"], f"{m['name']}.layer")
            if m["moves"] not in e2e_names:
                raise ContractError(f"metric {m['name']} moves no "
                                    "end-to-end metric")
        for w in m.get("workloads", ()):
            if w not in cells:
                raise ContractError(f"metric {m['name']} lists {w!r}")
    for w in bm["workloads"]:
        e2e, per_layer = declared_metrics(bm, w["name"])
        if len(e2e) < 2 or not per_layer:
            raise ContractError(
                f"{w['name']} must report setup_s, one more end-to-end "
                "metric and a per-layer metric")
        for m in per_layer:
            if m["moves"] not in {e["name"] for e in e2e}:
                raise ContractError(
                    f"{m['name']} moves {m['moves']}, which {w['name']} "
                    "does not report")
    if len(json.dumps(bm)) > 64 * 1024:
        raise ContractError("BENCHMARK.json is over 64 KiB")
    for p in bm["paths"]:
        for dirpath, _dirs, names in os.walk(os.path.join(root, p)):
            if "__pycache__" in dirpath:
                continue
            for n in names:
                if not re.match(r"^[A-Za-z0-9_.\-]+$", n):
                    raise ContractError(f"file name {n!r} under `paths`")
