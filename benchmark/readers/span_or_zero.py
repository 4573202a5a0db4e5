"""The `span` reader for a stage that a program may not record: one
statistic of one obs span stage over the measured window, in ms, and 0.0 —
the time the program attributed to the stage — where it recorded no sample
of it in the window.

Why not an error, as in `span`: the benchmark's files also run over the
parent commit, whose program lacks the stages a PR adds, and this
benchmark's own contract (lib/contract.py) refuses a traced line that lacks
a declared metric, so a reader that returned nothing there would fail the
parent's traced run. Why not None: the same clause. A later `benchmark` PR
that lets a traced line leave out a metric whose reader found nothing should
turn this 0.0 into None (PERF.md section 7). Until then read a 0.0 as "no
sample", never as "fast": every stage read through here costs tens of
microseconds at the least."""

from benchmark.readers import span


def read(params: dict, result: dict):
    spans = result.get("sources", {}).get("spans")
    if spans is None:
        return None  # an untraced run reports no per-layer metric
    hist = spans.get(params["stage"])
    if hist is None or hist.count == 0:
        return 0.0
    return span.read(params, result)
