"""The share of two obs span stages' TIME that the first took, over the
measured window: `sum_ms(num) / (sum_ms(num) + sum_ms(den))`, every
process's histograms merged, window end minus window start. Made for a
process's busy share, `loop_busy:<role>` over `loop_busy:<role>` +
`loop_idle:<role>` (foundationdb_tpu/obs/span.py: one sample each a slice
of the role's loop, so the sums, not the counts, are the seconds).

0.0 where the program recorded neither stage in the window (the parent
commit, which lacks them: `span_or_zero` says why that is no error and no
None here); None in an untraced run, which reports no per-layer metric."""


def read(params: dict, result: dict):
    spans = result.get("sources", {}).get("spans")
    if spans is None:
        return None
    num, den = (spans.get(params[k]) for k in ("num", "den"))
    num_ms = num.sum_ms if num is not None else 0.0
    total_ms = num_ms + (den.sum_ms if den is not None else 0.0)
    return num_ms / total_ms if total_ms > 0 else 0.0
