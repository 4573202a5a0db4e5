"""Device SELF time of one kernel phase per resolved batch, in ms: the
seconds of every device operation whose `jax` name path lies in one of the
`scopes` (`jax.named_scope` names of foundationdb_tpu/models/
conflict_kernel.py; an operation's duration minus the operations nested in
it, so phases never count twice), over the executions of the program whose
name matches `module`. Read from the trace's `device_scopes`
(benchmark/lib/trace_scopes.py); None where the trace has none — a program
without named scopes, the CPU backend's stand-in, an untraced run."""

from benchmark.readers.device_per_batch import executions


def read(params: dict, result: dict):
    trace = result.get("sources", {}).get("trace")
    if trace is None or trace.get("stand_in") or \
            "device_scopes" not in trace:
        return None
    n = executions(trace, params.get("module", "resolve"))
    if n == 0:
        return None
    scopes = trace["device_scopes"]
    return sum(scopes.get(s, 0.0) for s in params["scopes"]) / n * 1e3
