"""The served driver's own arithmetic (benchmark/drivers/cluster.py), with
the program's client library replaced by a stand-in: what a commit whose
result is unknown does to the reference, and the generator's statistics."""

import asyncio
import types

import numpy as np
import pytest

from benchmark.drivers import cluster
from benchmark.lib import loadgen, reference, ycsb


class _Tr:
    """One transaction of a stand-in database: reads the record as loaded,
    and ends its commit the way `outcomes` says, one entry per attempt."""

    def __init__(self, records, outcomes):
        self.records, self.outcomes = records, outcomes

    def set_option(self, *_a):
        pass

    async def get(self, key, snapshot=False):
        if self.outcomes and self.outcomes[0] == "get_times_out":
            from foundationdb_tpu.core.errors import TransactionTimedOut
            raise TransactionTimedOut("timed out")
        return self.records.value(self.records.keys.index(key))

    def set(self, key, value):
        pass

    async def commit(self):
        from foundationdb_tpu.core import errors

        how = self.outcomes.pop(0) if self.outcomes else "ok"
        if how == "conflict":
            raise errors.NotCommitted("conflict")
        if how == "commit_times_out":
            raise errors.TransactionTimedOut("timed out")
        if how == "connection_lost":
            raise ConnectionError("lost")

    async def on_error(self, e):
        from foundationdb_tpu.core.errors import TransactionTimedOut

        if isinstance(e, TransactionTimedOut):
            raise e  # not retryable, as the program's own on_error has it


def _client(outcomes):
    records = ycsb.Records(20, seed=3)
    replay = reference.CounterReplay(records)
    tr = _Tr(records, outcomes)
    db = types.SimpleNamespace(transaction=lambda: tr)
    kinds = np.array([ycsb.RMW], np.int8)
    return cluster.YcsbClient(db, records, replay, kinds, np.array([7]),
                              timeout_ms=5000, retry_limit=None), replay


@pytest.mark.parametrize("outcomes,status,acked,unknown", [
    (["ok"], loadgen.OK, {7: 1}, {}),
    (["conflict", "conflict", "ok"], loadgen.OK, {7: 1}, {}),
    # the commit may have landed: the key's allowed set widens by one
    (["commit_times_out"], cluster.UNKNOWN, {}, {7: 1}),
    (["conflict", "connection_lost"], cluster.UNKNOWN, {}, {7: 1}),
    # a read that times out has committed nothing
    (["get_times_out"], cluster.TIMED_OUT, {}, {}),
])
def test_a_commit_whose_result_is_unknown_widens_its_record(
        outcomes, status, acked, unknown):
    retries_due = sum(o in ("conflict", "get_times_out") for o in outcomes)
    client, replay = _client(list(outcomes))
    got, retries = asyncio.run(client.op(0))
    assert (got, retries) == (status, retries_due)
    assert (replay.acked, replay.unknown) == (acked, unknown)


def test_the_share_inside_the_limit_counts_failures_as_outside():
    rows = loadgen.Rows()
    kinds = np.array([ycsb.RMW, ycsb.READ], np.int8)
    # ten read-modify-writes ending in the window: 6 fast, 2 slow, one that
    # timed out fast, one that failed fast; a read; one RMW after the window
    for n, (ms, status) in enumerate(
            [(100, loadgen.OK)] * 6 + [(1500, loadgen.OK)] * 2
            + [(50, cluster.TIMED_OUT), (50, cluster.FAILED)]):
        rows.add(2 * n, 10.0, 10.0 + ms / 1e3, status, 1)
    rows.add(1, 10.0, 10.2, loadgen.OK, 0)
    rows.add(40, 19.0, 21.0, loadgen.OK, 0)
    out = cluster.summarize(rows, kinds, 10.0, 20.0, commit_limit_ms=1000)
    assert out["commits"] == 8 and out["commits_per_s"] == 0.8
    assert out["attempted"] == 11 and out["failed"] == 2
    assert out["commits_by_10s"] == [8]
    assert out["commit_in_limit_pct"] == 60.0
    assert cluster.summarize(rows, kinds, 10.0, 20.0, commit_limit_ms=1500)[
        "commit_in_limit_pct"] == 80.0
    assert out["retries_per_commit"] == 10 / 8
    assert out["read_p50_ms"] == pytest.approx(200.0)
