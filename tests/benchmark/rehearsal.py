"""A throw-away copy of the benchmark with tiny cells dropped in, for the CPU
rehearsal tests: the benchmark's own files are copied untouched, and the tiny
cells, configurations, traffic mixes and a per-layer metric are ADDED as new
files and new BENCHMARK.json entries — which is also the proof that a later
PR can add each without editing a file that is there."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_CONFIGS = {
    "tiny_cluster": ("ycsb_cluster_1r", {
        "recordcount": 400, "load_width": 8, "load_in_flight": 16}),
    "tiny_share": ("ycsb_resolver_share", {
        "nominal_rate_per_s": 2048, "key_universe": 1 << 12,
        "engine": {"capacity": 1 << 14, "dict_capacity": 1 << 15,
                   "batch_size": 64, "max_read_ranges": 8,
                   "max_write_ranges": 8, "max_key_bytes": 32}}),
}
TINY_TRAFFIC = {
    "tiny_closed": ("f_closed_64", {
        "clients": 8, "warm_up_s": 0.5, "sample_untouched": 50,
        "trace_s": 0.5, "obs_sample": 1}),
    "tiny_depth": ("share_depth8", {"trace_s": 0.5}),
}
TINY_CELLS = {
    "tiny_f_closed": ("ycsb_f_closed", "tiny_cluster", "tiny_closed"),
    "tiny_share_f": ("resolver_share_f", "tiny_share", "tiny_depth"),
}
# A per-layer metric of the copy's own: a reader's parameters in a new file
# and a new BENCHMARK.json entry, read by the readers that are there.
TINY_METRICS = {
    "tiny_read_p95_ms": (
        {"reader": "value", "params": {"path": "generator.read_p95_ms"}},
        {"unit": "ms", "better": "lower", "source": "host_clock",
         "layer": "storage", "moves": "commits_per_s",
         "workloads": ["tiny_f_closed"]}),
}


def _derive(src: str, dst: str, changes: dict) -> None:
    with open(src) as f:
        doc = json.load(f)
    doc.update(changes)
    with open(dst, "w") as f:
        json.dump(doc, f)


def build(root: str) -> str:
    """Make `root` a checkout that holds the program (a link), the
    benchmark (a copy) and the tiny cells (added files). Returns `root`."""
    os.makedirs(root, exist_ok=True)
    os.symlink(os.path.join(REPO, "foundationdb_tpu"),
               os.path.join(root, "foundationdb_tpu"))
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bm = json.load(f)
    b = os.path.join(root, "benchmark")
    for name, (base, changes) in TINY_CONFIGS.items():
        _derive(os.path.join(b, "configs", base + ".json"),
                os.path.join(b, "configs", name + ".json"), changes)
        entry = dict(next(c for c in bm["configs"] if c["name"] == base),
                     name=name, file=f"benchmark/configs/{name}.json")
        bm["configs"].append(entry)
    for name, (base, changes) in TINY_TRAFFIC.items():
        _derive(os.path.join(b, "traffic", base + ".json"),
                os.path.join(b, "traffic", name + ".json"), changes)
    for name, (base, config, traffic) in TINY_CELLS.items():
        bm["workloads"].append(dict(
            next(w for w in bm["workloads"] if w["name"] == base),
            name=name, config=config, traffic=traffic))
        for m in bm["end_to_end"] + bm["per_layer"]:
            if base in m.get("workloads", ()):
                m["workloads"].append(name)
    for name, (spec, entry) in TINY_METRICS.items():
        with open(os.path.join(b, "metrics", name + ".json"), "w") as f:
            json.dump(spec, f)
        bm["per_layer"].append(dict(entry, name=name))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return root


def environment() -> dict:
    """The rehearsal's environment: the CPU backend on purpose, and the
    repo's compile cache so that a second test run compiles nothing."""
    return dict(os.environ, JAX_PLATFORMS="cpu",
                JAX_COMPILATION_CACHE_DIR=os.path.join(REPO, ".jax_cache"))
