"""The ratio of two counters' differences over the window
(`sources.counters.<num>` / `sources.counters.<den>`): new dictionary keys
per dispatch, say. 0 over 0 is 0; None where the program has no such
counter (an older program, an untraced run)."""


def read(params: dict, result: dict):
    counters = result.get("sources", {}).get("counters")
    if counters is None or params["num"] not in counters \
            or params["den"] not in counters:
        return None
    num, den = float(counters[params["num"]]), float(counters[params["den"]])
    return num / den if den else 0.0
