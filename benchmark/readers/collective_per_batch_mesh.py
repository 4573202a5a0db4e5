"""The cross-chip reductions' own device time per resolved batch, in ms, on
the BUSIEST chip: the summed durations of the collective operations
(`all-gather`, `all-reduce`, ...; the wait for the slowest chip is inside
them) on that chip's `XLA Ops` line, over its executions of the resolve
program. Read from the trace's `mesh.planes` (benchmark/lib/mesh_proc.py);
None where the trace has none."""

from benchmark.readers.device_per_batch_mesh import planes_of


def read(params: dict, result: dict):
    planes = planes_of(result)
    if not planes:
        return None
    busiest = max(planes, key=lambda p: p["busy_s"])
    return busiest["collective_s"] / busiest["executions"] * 1e3
