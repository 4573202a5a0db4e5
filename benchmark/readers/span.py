"""One statistic of one obs span stage (foundationdb_tpu/obs/span.py
TXN_STAGES / SUB_STAGES) over the measured window, in ms: the stage's
histograms of every process, merged, window end minus window start. A traced
run in which the stage has no sample is an error of that run, not a 0."""


def read(params: dict, result: dict):
    spans = result.get("sources", {}).get("spans")
    if spans is None:
        return None
    hist = spans.get(params["stage"])
    if hist is None or hist.count == 0:
        raise RuntimeError(
            f"span stage {params['stage']!r} has no sample in this window")
    stat = params["stat"]
    return hist.mean() if stat == "mean" else hist.percentile(
        float(stat.lstrip("p")))
