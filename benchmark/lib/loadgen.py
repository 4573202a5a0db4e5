"""The general load generator: a closed loop over the program's event loop,
one process, one thread.

It drives `op(k)`, an async callable that performs operation `k` of the
run's plan and returns `(status, retries)`, and returns one row per operation
with the host clock's readings; every statistic is worked out afterwards
from the rows (benchmark/lib/hist.py), never while the system is under load.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

OK = "ok"

pc = time.perf_counter


@dataclass
class Rows:
    """One row per operation: plan index, when it was sent, when it ended
    (host clock, seconds), how it ended, and its retries."""

    k: list = field(default_factory=list)
    sent: list = field(default_factory=list)
    end: list = field(default_factory=list)
    status: list = field(default_factory=list)
    retries: list = field(default_factory=list)

    def add(self, k, sent, end, status, retries) -> None:
        self.k.append(k)
        self.sent.append(sent)
        self.end.append(end)
        self.status.append(status)
        self.retries.append(retries)


async def closed_loop(loop, op, n_clients: int, t_stop: float) -> Rows:
    """`n_clients` clients, each sending its next operation when the last
    is answered, until the host clock passes `t_stop`. Operations are taken
    from one shared counter, so the plan is consumed in order."""
    rows = Rows()
    state = {"next": 0}

    async def client() -> None:
        while pc() < t_stop:
            k = state["next"]
            state["next"] = k + 1
            t0 = pc()
            status, retries = await op(k)
            rows.add(k, t0, pc(), status, retries)

    from foundationdb_tpu.runtime.flow import all_of

    await all_of([loop.spawn(client(), name=f"bench.client{c}")
                  for c in range(n_clients)])
    return rows
