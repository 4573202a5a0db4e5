"""Perf-trajectory table: fold committed bench artifacts into one view.

``python -m foundationdb_tpu.obs --bench-history`` scans the repo root
for the committed ``BENCH_*.json`` / ``*_AB.json`` round artifacts and
folds them into one time-ordered regression table: (artifact, round,
metric, headline value, honesty flags) per row, ordered by the round
number embedded in the filename (``_rNN``; round-less artifacts sort
last by name). Drift check: for artifacts sharing a metric across
rounds, the latest/previous ratio is computed ONLY between records both
marked ``valid`` — a ``valid:false`` record (CPU fallback, failed gate,
harness error) appears in the table with its reasons but is REFUSED as
a ratio endpoint, never silently averaged in.
"""

from __future__ import annotations

import glob
import json
import os
import re

#: headline-value extraction per artifact metric name: (key, unit).
#: Artifacts not listed fall back to a "value"/"unit" pair if present.
HEADLINE_KEYS = {
    "resolved_txns_per_sec_per_chip": ("value", "txns/sec/chip"),
    "obs_sampling_overhead_ab": ("overhead_frac", "frac"),
    "wave_commit_ab": ("value", "goodput ratio"),
    "wave_mesh_ab": ("value", "goodput ratio"),
    "admission_ab": ("naive_ratio_mean", "goodput ratio"),
    "resident_ab_dictionary": ("host_pack_ratio", "pack ratio"),
    "sched_ab_fixed_vs_adaptive": ("p99_cut_x", "p99 cut"),
    "open_loop_scaleout": ("past_saturation_observed", "bool"),
    "deployed_chaos": ("ok", "bool"),
    "kernel_ab_packed_vs_unpacked": ("value", "ratio"),
}

#: drift beyond this fraction between consecutive VALID rounds of the
#: same metric is flagged (informational unless --gate).
DRIFT_FRAC = 0.20


def _round_of(name: str) -> "int | None":
    m = re.search(r"_r(\d+)", name)
    return int(m.group(1)) if m else None


def _load_record(path: str) -> "dict | None":
    """Whole-file JSON, else the last parseable JSON line. Wrapper dicts
    (the autopilot's {cmd, rc, tail, parsed} capture) unwrap to their
    `parsed` payload; a null payload means the round never produced a
    record — reported as unparsed, not dropped."""
    try:
        with open(path, "r", encoding="utf-8") as f:
            text = f.read()
    except OSError:
        return None
    rec = None
    try:
        rec = json.loads(text)
    except ValueError:
        for line in reversed(text.strip().splitlines()):
            try:
                rec = json.loads(line)
                break
            except ValueError:
                continue
    if isinstance(rec, dict) and set(rec) >= {"cmd", "rc"}:
        rec = rec.get("parsed")
    return rec if isinstance(rec, dict) else None


def _row(path: str, rec: "dict | None") -> dict:
    name = os.path.basename(path)
    row: dict = {"artifact": name, "round": _round_of(name)}
    if rec is None:
        row.update(parsed=False, valid=False,
                   note="no JSON record (failed/incomplete round)")
        return row
    metric = rec.get("metric")
    key, unit = HEADLINE_KEYS.get(metric, ("value", rec.get("unit")))
    value = rec.get(key)
    row.update(
        parsed=True,
        metric=metric,
        value=value,
        value_key=key,
        unit=unit,
        valid=bool(rec.get("valid", rec.get("ok", False))),
        cpu_fallback=rec.get("cpu_fallback"),
        p99_quotable=rec.get("p99_quotable"),
        backend=rec.get("backend"),
    )
    reasons = rec.get("invalid_reasons") or rec.get("problems")
    if reasons:
        row["invalid_reasons"] = reasons[:3]
    return row


def bench_history(root: str = ".",
                  drift_frac: float = DRIFT_FRAC) -> dict:
    """The one-JSON-line record (metric ``bench_history``): the table,
    plus per-metric drift ratios between consecutive valid rounds."""
    paths = sorted(
        set(glob.glob(os.path.join(root, "BENCH_*.json")))
        | set(glob.glob(os.path.join(root, "*_AB.json"))))
    # THIS tool's output may be kept as BENCH_HISTORY_*.json
    # in the same root — folding a previous trajectory record in as a
    # bench row would make every table self-referential.
    paths = [p for p in paths
             if not os.path.basename(p).startswith("BENCH_HISTORY")]
    rows = [_row(p, _load_record(p)) for p in paths]
    # Time order: round number first (round-less last), then name.
    rows.sort(key=lambda r: (r["round"] is None, r["round"] or 0,
                             r["artifact"]))
    drift: list[dict] = []
    refused: list[dict] = []
    by_metric: dict[str, list[dict]] = {}
    for r in rows:
        if r.get("parsed") and r.get("metric") and isinstance(
                r.get("value"), (int, float)) and not isinstance(
                r.get("value"), bool):
            by_metric.setdefault(r["metric"], []).append(r)
    for metric, series in by_metric.items():
        valid = [r for r in series if r["valid"]]
        for r in series:
            if not r["valid"]:
                refused.append({"artifact": r["artifact"], "metric": metric,
                                "why": "valid:false — refused as a ratio "
                                       "endpoint"})
        for prev, cur in zip(valid, valid[1:]):
            if not prev["value"]:
                continue
            ratio = cur["value"] / prev["value"]
            drift.append({
                "metric": metric,
                "from": prev["artifact"],
                "to": cur["artifact"],
                "ratio": round(ratio, 4),
                "drifted": abs(ratio - 1.0) > drift_frac,
            })
    return {
        "metric": "bench_history",
        "ok": True,  # the scan itself; drift is the reader's signal
        "artifacts": len(rows),
        "parsed": sum(1 for r in rows if r.get("parsed")),
        "valid": sum(1 for r in rows if r.get("valid")),
        "rows": rows,
        "drift": drift,
        "drifted": [d for d in drift if d["drifted"]],
        "refused_for_ratio": refused,
        "drift_frac": drift_frac,
    }


def format_table(record: dict) -> str:
    """Human-readable trajectory table (stderr companion to the JSON)."""
    lines = [f"{'round':>5}  {'artifact':<28} {'metric':<32} "
             f"{'value':>12}  flags"]
    for r in record["rows"]:
        flags = []
        if not r.get("parsed"):
            flags.append("UNPARSED")
        if r.get("valid"):
            flags.append("valid")
        else:
            flags.append("INVALID")
        if r.get("cpu_fallback"):
            flags.append("cpu_fallback")
        if r.get("p99_quotable") is False:
            flags.append("p99!quotable")
        val = r.get("value")
        val = (f"{val:.4g}" if isinstance(val, (int, float))
               and not isinstance(val, bool) else str(val))
        lines.append(
            f"{str(r.get('round') or '-'):>5}  {r['artifact']:<28} "
            f"{str(r.get('metric') or '-'):<32} {val:>12}  "
            f"{','.join(flags)}")
    for d in record["drifted"]:
        lines.append(f"DRIFT {d['metric']}: {d['from']} -> {d['to']} "
                     f"ratio {d['ratio']}")
    return "\n".join(lines)
