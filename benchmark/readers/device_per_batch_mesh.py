"""Device busy time per resolved batch where ONE program runs on several
chips at once, in ms: the planes' busy seconds, averaged, over the
executions a plane of the program whose name matches `module` (every chip
runs every execution). Read from the trace's `mesh.planes`
(benchmark/lib/mesh_proc.py); None where the trace has none: the CPU
backend's stand-in, an untraced run, a launcher without it."""


def planes_of(result: dict) -> "list | None":
    trace = result.get("sources", {}).get("trace")
    if trace is None or trace.get("stand_in"):
        return None  # a CPU number never goes under a device metric's name
    planes = (trace.get("mesh") or {}).get("planes")
    return [p for p in planes if p["executions"] > 0] if planes else None


def read(params: dict, result: dict):
    planes = planes_of(result)
    if not planes:
        return None
    busy = sum(p["busy_s"] for p in planes) / len(planes)
    runs = sum(p["executions"] for p in planes) / len(planes)
    return busy / runs * 1e3
