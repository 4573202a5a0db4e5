"""Device busy time per resolved batch, in ms, both taken from the one
profiler trace: the union of the intervals in which an operation ran on the
device, over the executions of the program whose name matches `module` (one
execution resolves one batch)."""

import re


def executions(trace: dict, pattern: str) -> int:
    return sum(count for name, (count, _s) in trace["modules"].items()
               if re.search(pattern, name))


def read(params: dict, result: dict):
    trace = result.get("sources", {}).get("trace")
    if trace is None or trace["stand_in"]:
        return None  # a CPU number never goes under a device metric's name
    n = executions(trace, params["module"])
    if n == 0:
        raise RuntimeError(
            f"no program matching {params['module']!r} ran in the traced "
            f"window; it holds {sorted(trace['modules'])}")
    return trace["busy_s"] / n * 1e3
