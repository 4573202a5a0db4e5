"""The benchmark's resolver launcher: the process that holds the chip, and
therefore the one that traces it and reads its memory.

    python -m benchmark.lib.resolver_proc --ctl DIR served  <server.py's arguments>
    python -m benchmark.lib.resolver_proc --ctl DIR replay  --config FILE --port N

`served` runs the program's own `foundationdb_tpu.server.main(argv)`,
unchanged. `replay` builds one resolver role by hand, out of the same pieces
and with constructor arguments the classes already take, at the engine sizes
the configuration file states (the cluster spec has no key for them yet).
Either way a control thread watches `DIR` (benchmark/lib/control.py):

- `start`: `jax.profiler.start_trace`, with the Python tracer on: without
  it the idle gaps have no name (the runtime's own TraceMes cover almost none
  of the host's time), and what the host does between dispatches is what
  the next optimisation needs to know. It slows the role while it traces,
  which is why the trace is a few seconds of a run of its own;
- `stop`: `stop_trace`; the reply names the `.xplane.pb`;
- `reduce`: benchmark/lib/trace_reduce.py over that file, in this process;
- `report`: platform, `device_kind`, device count and
  `memory_stats()["peak_bytes_in_use"]` of the fullest device (0 on the CPU
  backend, which reports none).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import threading
import time
import traceback

from benchmark.lib.control import POLL_S, write_atomic


class ControlThread(threading.Thread):
    def __init__(self, directory: str):
        super().__init__(name="benchmark.control", daemon=True)
        self.dir = directory
        self.trace_dir = os.path.join(directory, "trace")
        self._t_start = None
        self._window_s = None
        self._next = 1

    def run(self) -> None:
        while True:
            cmd = os.path.join(self.dir, f"{self._next}.cmd.json")
            if not os.path.exists(cmd):
                time.sleep(POLL_S)
                continue
            with open(cmd) as f:
                doc = json.load(f)
            try:
                reply = getattr(self, "op_" + doc["op"])(doc)
            except Exception:  # noqa: BLE001 — the harness reports it
                reply = {"error": traceback.format_exc()[-4000:]}
            write_atomic(os.path.join(self.dir, f"{self._next}.reply.json"),
                         reply)
            self._next += 1

    def op_start(self, _doc) -> dict:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 1
        options.host_tracer_level = 2
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self._t_start = time.perf_counter()
        return {"started": True}

    def op_stop(self, _doc) -> dict:
        import jax

        self._window_s = time.perf_counter() - self._t_start
        jax.profiler.stop_trace()
        found = sorted(glob.glob(os.path.join(
            self.trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise RuntimeError(f"no .xplane.pb under {self.trace_dir}")
        return {"xplane": found[-1], "window_s": self._window_s,
                "bytes": os.path.getsize(found[-1])}

    def op_reduce(self, doc) -> dict:
        from benchmark.lib.trace_reduce import dump_planes, reduce_xplane

        out = reduce_xplane(doc["xplane"], doc["window_s"])
        if doc.get("fixture"):
            with open(doc["fixture"], "w") as f:
                json.dump(dump_planes(doc["xplane"]), f)
        return out

    def op_report(self, _doc) -> dict:
        import jax

        devices = jax.devices()
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in devices]
        return {"platform": devices[0].platform,
                "kind": devices[0].device_kind, "count": len(devices),
                "memory_peak_bytes": int(max(peaks))}


def replay_main(config_path: str, port: int) -> None:
    """One resolver role on this process's device, as `server.main` would
    serve it, at the configuration's engine sizes."""
    from foundationdb_tpu.obs.span import SpanSink, obs_env_default
    from foundationdb_tpu.runtime.flow import Promise, rpc
    from foundationdb_tpu.runtime.net import NetTransport, RealLoop
    from foundationdb_tpu.utils import enable_compilation_cache, require_tpu

    with open(config_path) as f:
        engine = json.load(f)["engine"]
    loop = RealLoop()
    sink = SpanSink(loop) if obs_env_default() else None
    t = NetTransport(loop, host="127.0.0.1", port=port)
    enable_compilation_cache()
    dev = require_tpu("the benchmark's replayed resolver")
    from foundationdb_tpu.models.conflict_set import TPUConflictSet
    from foundationdb_tpu.runtime.resolver import Resolver

    cs = TPUConflictSet(**{k: engine[k] for k in (
        "capacity", "dict_capacity", "batch_size", "max_read_ranges",
        "max_write_ranges", "max_key_bytes")})
    warm = cs.warm_up()
    print(f"device resolver0 engine=tpu platform={dev['platform']} "
          f"device_kind={dev['device_kind']!r} count={dev['count']} "
          f"warm_up_s={json.dumps(warm)}", flush=True)
    t.serve("resolver", Resolver(loop, cs))

    class Admin:
        def __init__(self):
            self.stopped = Promise()

        @rpc
        async def shutdown(self) -> str:
            loop.spawn(self._finish(), name="admin.shutdown")
            return "shutting down"

        @rpc
        async def obs_snapshot(self) -> dict:
            if sink is None:
                return {"enabled": False}
            return {"enabled": True, "dump": sink.dump()}

        async def _finish(self):
            await loop.sleep(0)
            self.stopped.send(None)

    admin = Admin()
    t.serve("admin", admin)
    print(f"ready resolver0 on {t.addr[0]}:{t.addr[1]}", flush=True)

    async def until_shutdown():
        await admin.stopped.future
        await loop.sleep(0.05)  # one select() round: reply bytes on the wire

    try:
        loop.run(until_shutdown(), timeout=float("inf"))
    finally:
        t.close()


def main(argv: "list[str] | None" = None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) < 3 or argv[0] != "--ctl" or argv[2] not in (
            "served", "replay"):
        raise SystemExit(__doc__)
    ctl, mode, rest = argv[1], argv[2], argv[3:]
    ControlThread(ctl).start()
    if mode == "served":
        from foundationdb_tpu.server import main as server_main

        server_main(rest)
        return
    rp = argparse.ArgumentParser()
    rp.add_argument("--config", required=True)
    rp.add_argument("--port", type=int, required=True)
    r = rp.parse_args(rest)
    replay_main(r.config, r.port)


if __name__ == "__main__":
    sys.exit(main())
