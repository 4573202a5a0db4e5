"""The harness's side of the resolver launcher's control directory.

The chip belongs to the resolver process, so the harness asks it, through
files, to trace itself and to report on its device: the harness writes
`<n>.cmd.json`, the launcher's control thread (benchmark/lib/resolver_proc.py)
answers with `<n>.reply.json`. Files, because the harness must stay off JAX
and the served resolver's RPC surface belongs to the program.
"""

from __future__ import annotations

import json
import os
import time

POLL_S = 0.02


class ControlError(RuntimeError):
    pass


def write_atomic(path: str, doc: dict) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)


class Control:
    def __init__(self, directory: str):
        self.dir = directory
        os.makedirs(directory, exist_ok=True)
        self._n = 0

    def send(self, op: str, **args) -> str:
        """Write one command; returns the path its reply will appear at."""
        self._n += 1
        write_atomic(os.path.join(self.dir, f"{self._n}.cmd.json"),
                     dict(args, op=op))
        return os.path.join(self.dir, f"{self._n}.reply.json")

    @staticmethod
    def take(reply_path: str) -> "dict | None":
        """The reply, once it is there."""
        if not os.path.exists(reply_path):
            return None
        with open(reply_path) as f:
            reply = json.load(f)
        if "error" in reply:
            raise ControlError(reply["error"])
        return reply

    def call(self, op: str, timeout_s: float = 120.0, **args) -> dict:
        """Blocking form, for use outside the event loop."""
        path = self.send(op, **args)
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            reply = self.take(path)
            if reply is not None:
                return reply
            time.sleep(POLL_S)
        raise ControlError(f"the resolver process did not answer {op!r} "
                           f"within {timeout_s:.0f}s")

    async def acall(self, loop, op: str, timeout_s: float = 120.0,
                    **args) -> dict:
        """The same from a coroutine of the program's event loop, which
        keeps serving the generator while it waits."""
        path = self.send(op, **args)
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            reply = self.take(path)
            if reply is not None:
                return reply
            await loop.sleep(POLL_S)
        raise ControlError(f"the resolver process did not answer {op!r} "
                           f"within {timeout_s:.0f}s")
