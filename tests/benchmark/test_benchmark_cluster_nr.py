"""The deployment with four resolvers (configuration `ycsb_cluster_4r`,
driver `cluster_nr`) on the CPU: the program's proxy over four resolver
roles against the plain split-and-AND reference (benchmark/lib/
reference_nr.py), the configuration's stated splits against the keys, the
range-share check against today's first-byte split, and the merge of the
four processes' counters and reports."""

import bisect
import json
import os

import numpy as np
import pytest

from benchmark.lib import observe_nr, reference, reference_nr, ycsb
from tests.benchmark.test_benchmark_scopes import hist_of

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(REPO, "benchmark", "configs",
                       "ycsb_cluster_4r.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(REPO, "benchmark", "traffic", "f_closed_64.json")) as f:
    TRAFFIC = json.load(f)
SPLITS = [s.encode() for s in CONFIG["deployment"]["resolver_splits"]]
LIMIT = CONFIG["checks"]["ranges_share_fullest_resolver_pct"]["limit"]


# -- the program's four resolvers against the plain reference ----------------

KEY_SPLITS = [b"k25", b"k50", b"k75"]
STEP = 1_000_000  # versions a batch: the 5 s MVCC window holds five


def _key(n: int) -> bytes:
    return b"k%02d" % n


def _stream(seed: int, single_key: bool):
    """Batches of [(read version, read ranges, write ranges)] over keys
    k00..k99, the read version up to seven batches back (so some are too
    old). Unless `single_key`, ranges span several keys and most cross a
    shard bound."""
    rng = np.random.default_rng(seed)

    def a_range():
        lo = int(rng.integers(0, 100))
        if single_key:
            return (_key(lo), _key(lo) + b"\x00")
        return (_key(lo), _key(min(99, lo + int(rng.integers(0, 40))))
                + b"\x00")

    for b in range(1, 15):
        version = b * STEP
        txns = []
        for _ in range(int(rng.integers(1, 17))):
            rv = max(0, version - int(rng.integers(1, 8 * STEP)))
            if single_key:
                r = a_range()
                txns.append((rv, [r], [r]))
            else:
                txns.append((
                    rv, [a_range() for _ in range(int(rng.integers(0, 3)))],
                    [a_range() for _ in range(int(rng.integers(0, 3)))]))
        yield version, txns


def _program_verdicts(stream, splits):
    """The stream through CommitProxy._resolve over one Resolver role an
    engine, `len(splits) + 1` of them, on the CPU backend."""
    from foundationdb_tpu.core.types import KeyRange, TxnConflictInfo
    from foundationdb_tpu.models.conflict_set import TPUConflictSet
    from foundationdb_tpu.runtime.commit_proxy import CommitProxy
    from foundationdb_tpu.runtime.flow import Loop
    from foundationdb_tpu.runtime.resolver import Resolver
    from foundationdb_tpu.runtime.shardmap import KeyShardMap

    n = len(splits) + 1
    loop = Loop(seed=0)
    resolvers = [Resolver(loop, TPUConflictSet(
        capacity=1 << 11, batch_size=16, max_read_ranges=2,
        max_write_ranges=2, max_key_bytes=8)) for _ in range(n)]
    proxy = CommitProxy(loop, None, resolvers,
                        KeyShardMap(splits, tags=list(range(n))), [],
                        KeyShardMap.uniform(1))

    async def drive():
        out, prev = [], 0
        for version, txns in stream:
            batch = [(TxnConflictInfo(
                read_version=rv,
                read_ranges=[KeyRange(b, e) for b, e in reads],
                write_ranges=[KeyRange(b, e) for b, e in writes]), None)
                for rv, reads, writes in txns]
            verdicts, _conf, fail_safe, _wave = await proxy._resolve(
                batch, prev, version)
            assert not fail_safe
            out.append([int(v) for v in verdicts])
            prev = version
        return out

    got = loop.run(drive(), timeout=600)
    sent = [r.ranges_received for r in resolvers]
    return got, sent


def _oldest(version: int) -> int:
    return max(0, version - 5 * STEP)  # sequencer.MVCC_WINDOW_VERSIONS


def test_the_verdict_codes_are_the_programs():
    from foundationdb_tpu.core.types import Verdict

    assert (int(Verdict.COMMITTED), int(Verdict.CONFLICT),
            int(Verdict.TOO_OLD)) == (reference.COMMITTED,
                                      reference.CONFLICT, reference.TOO_OLD)


@pytest.mark.parametrize("seed", [11, 12, 2 ** 31 + 13])
def test_four_resolvers_give_the_split_and_and_references_verdicts(seed):
    ref = reference_nr.SplitResolvers(KEY_SPLITS)
    want = [ref.resolve(txns, version, _oldest(version))
            for version, txns in _stream(seed, single_key=False)]
    got, sent = _program_verdicts(_stream(seed, single_key=False),
                                  KEY_SPLITS)
    assert got == want
    kinds = {v for batch in want for v in batch}
    assert kinds == {reference.COMMITTED, reference.CONFLICT,
                     reference.TOO_OLD}
    assert min(sent) > 0  # every resolver had ranges to check
    # and the split matters: one resolver holding every key paints nothing
    # of a transaction it rejects, so it refuses fewer
    one = reference_nr.SplitResolvers([])
    unsplit = [one.resolve(txns, version, _oldest(version))
               for version, txns in _stream(seed, single_key=False)]
    assert unsplit != want


@pytest.mark.parametrize("seed", [21, 22])
def test_single_key_transactions_also_equal_the_one_resolver_reference(seed):
    """A transaction on one key is one resolver's alone, so the split and
    the painting of rejected writes cannot show."""
    last_write: dict = {}
    want = [reference.point_verdicts(
        last_write, [reads[0][0] for _rv, reads, _w in txns],
        [rv for rv, _r, _w in txns], version, _oldest(version))
        for version, txns in _stream(seed, single_key=True)]
    ref = reference_nr.SplitResolvers(KEY_SPLITS)
    assert [ref.resolve(txns, version, _oldest(version))
            for version, txns in _stream(seed, single_key=True)] == want
    got, _sent = _program_verdicts(_stream(seed, single_key=True),
                                   KEY_SPLITS)
    assert got == want


def test_the_reference_paints_what_another_resolver_rejected():
    """By hand: T1 writes a and z; T2 reads a (written since) and writes
    z. Resolver 0 rejects T2, resolver 1 accepts and PAINTS its z; T3
    reads z after T1 and is refused for a write that never committed."""
    a, z = (b"a", b"a\x00"), (b"z", b"z\x00")
    ref = reference_nr.SplitResolvers([b"m"])
    assert ref.resolve([(0, [], [a, z])], 10, 0) == [reference.COMMITTED]
    assert ref.resolve([(5, [a], [z])], 20, 0) == [reference.CONFLICT]
    assert ref.resolve([(15, [z], [])], 30, 0) == [reference.CONFLICT]
    one = reference_nr.SplitResolvers([])
    one.resolve([(0, [], [a, z])], 10, 0)
    one.resolve([(5, [a], [z])], 20, 0)
    assert one.resolve([(15, [z], [])], 30, 0) == [reference.COMMITTED]


# -- the configuration's splits, and the check that holds a run to them ------

@pytest.fixture(scope="module")
def keys():
    return ycsb.Records(CONFIG["recordcount"], seed=7).keys


def test_the_stated_splits_are_the_quartiles_of_the_loaded_keys(keys):
    ordered = sorted(keys)
    n = len(ordered)
    assert SPLITS == [ordered[n // 4], ordered[n // 2], ordered[3 * n // 4]]
    assert SPLITS == [b"user3077411578082041711", b"user5150756240885596906",
                      b"user7224432303514507081"]
    held = np.bincount([bisect.bisect_right(SPLITS, k) for k in keys],
                       minlength=4)
    assert all(abs(int(h) - 12_500) <= 1 for h in held), held
    # the keys do not depend on the seed
    assert ycsb.Records(CONFIG["recordcount"], seed=2 ** 31 + 5).keys == keys


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 77])
def test_the_planned_load_a_quarter_is_what_the_configuration_says(
        keys, seed):
    kinds, items = ycsb.plan(len(keys), 200_000, TRAFFIC["rmw_share"], seed,
                             TRAFFIC["base_seed"])
    shard = np.array([bisect.bisect_right(SPLITS, k) for k in keys])
    rmw = np.bincount(shard[items[kinds == ycsb.RMW]], minlength=4)
    share = rmw / rmw.sum() * 100.0
    assert [round(float(s), 1) for s in share] == [24.0, 28.4, 23.5, 24.1]
    assert observe_nr.share_fullest_pct(rmw.tolist()) <= LIMIT


def test_the_configuration_keeps_what_ycsb_cluster_1r_fixes():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "ycsb_cluster_1r.json")) as f:
        one = json.load(f)
    for key in ("guarantees", "reduced", "recordcount", "load_width",
                "load_in_flight"):
        assert CONFIG[key] == one[key], key
    for key, value in one["fixed_by_the_source"].items():
        assert CONFIG["fixed_by_the_source"][key] == value
    dep = dict(CONFIG["deployment"])
    assert dep.pop("resolvers") == 4 and len(dep.pop("resolver_splits")) == 3
    for key in ("resolver_engine", "resolver_splits_note"):
        dep.pop(key)
    want = dict(one["deployment"])
    del want["resolvers"], want["resolver_engine"]
    assert dep == want
    assert one["assumed"]["counter"] == CONFIG["assumed"]["counter"]


def test_under_todays_uniform_map_the_check_reads_100_and_fails(keys):
    """KeyShardMap.uniform(4) splits by first byte: every "user..." key is
    resolver 1's, so the run did not exercise the stated deployment."""
    from foundationdb_tpu.runtime.shardmap import KeyShardMap

    m = KeyShardMap.uniform(4)
    sent = np.bincount([m.tag_for_key(k) for k in keys[:5000]],
                       minlength=4).tolist()
    assert sent == [0, 5000, 0, 0]
    assert observe_nr.share_fullest_pct(sent) == 100.0 > LIMIT
    assert observe_nr.share_fullest_pct([0, 0, 0, 0]) == 100.0
    assert observe_nr.share_fullest_pct([24, 28, 24, 24]) == 28.0 <= LIMIT


# -- the merge of what four processes report ---------------------------------

def _metrics(i: int) -> dict:
    return {"batches_resolved": 10, "txns_resolved": 40, "txns_conflicted": i,
            "overflow_events": 0, "txns_rejected_fail_safe": 0,
            "resolve_failures": 0, "ranges_received": 10 * (i + 1),
            "txns_with_ranges": 5 * (i + 1),
            "engine": {"full_repacks": 1, "evictions": 0,
                       "auto_reshards": 0}}


class _Ep:
    def __init__(self, i):
        self.i, self.calls = i, 0

    async def get_metrics(self):
        self.calls += 1
        m = _metrics(self.i)
        m["ranges_received"] *= self.calls
        return m


def test_counters_are_summed_over_the_roles_and_kept_a_role():
    import asyncio

    obs = observe_nr.ObserverNR(None, [], [_Ep(i) for i in range(4)], [])
    first = asyncio.run(obs.counters())
    last = asyncio.run(obs.counters())
    assert first["batches_resolved"] == 40 and first["full_repacks"] == 4
    assert first["txns_conflicted"] == 6 and first["ranges_received"] == 100
    assert first["per_role"]["ranges_received"] == [10, 20, 30, 40]
    between = observe_nr.counters_between(first, last)
    assert between["ranges_received"] == 100 and between["evictions"] == 0
    assert between["per_role"] == {"ranges_received": [10, 20, 30, 40],
                                   "txns_with_ranges": [0, 0, 0, 0]}
    assert observe_nr.share_fullest_pct(
        between["per_role"]["ranges_received"]) == 40.0


def test_the_device_is_four_chips_of_one_kind_and_the_fullest_peak():
    rep = [{"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
            "memory_peak_bytes": 90_000_000 + i} for i in (2, 9, 4, 1)]
    assert observe_nr.merge_reports(rep) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4,
        "memory_peak_bytes": 90_000_009}
    rep[2]["platform"] = "cpu"
    with pytest.raises(RuntimeError, match="unlike"):
        observe_nr.merge_reports(rep)


def test_all_launchers_are_asked_at_once_and_answer_in_order(tmp_path):
    import threading

    from benchmark.lib.control import Control, ControlError, write_atomic

    controls = [Control(str(tmp_path / str(i))) for i in range(3)]

    def answer():
        import time
        time.sleep(0.1)
        for i in reversed(range(3)):  # the last launcher answers first
            cmd = tmp_path / str(i) / "1.cmd.json"
            doc = json.loads(cmd.read_text())
            write_atomic(str(tmp_path / str(i) / "1.reply.json"),
                         {"i": i, "op": doc["op"], "arg": doc.get("arg")})

    th = threading.Thread(target=answer)
    th.start()
    got = observe_nr.call_all(controls, "reduce", timeout_s=10,
                              args=[{"arg": i * i} for i in range(3)])
    th.join()
    assert got == [{"i": i, "op": "reduce", "arg": i * i} for i in range(3)]
    with pytest.raises(ControlError, match="did not answer 'report'"):
        observe_nr.call_all(controls, "report", timeout_s=0.1)


# -- the four traces of a chip run, merged (fixture: PR 26's chip run) -------

@pytest.fixture(scope="module")
def chip_traces():
    from benchmark.lib import trace_reduce

    with open(os.path.join(REPO, "benchmark", "fixtures",
                           "trace_ycsb_f_closed_4r.json")) as f:
        chips = json.load(f)
    assert len(chips) == 4
    return [trace_reduce.reduce_planes(planes, 3.0) for planes in chips]


REPORTS = [{"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
            "memory_peak_bytes": 90_217_984 + i} for i in (0, 3, 1, 2)]


def test_the_four_traces_merge_to_the_busiest_chip_whole(chip_traces):
    trace, chips = observe_nr.merge_traces(chip_traces, REPORTS)
    busy = [tr["busy_s"] for tr in chip_traces]
    assert min(busy) > 0 and len(set(busy)) == 4
    busiest = busy.index(max(busy))
    assert trace["busiest"] == busiest
    for key in ("window_s", "busy_s", "modules", "device_ops", "idle_gaps",
                "stand_in"):
        assert trace[key] == chip_traces[busiest][key]
    assert trace["stand_in"] is False
    assert 0 < trace["busy_s"] <= trace["window_s"]
    assert trace["device_planes"] == [
        f"resolver{i}:/device:TPU:0" for i in range(4)]
    assert [c["resolver"] for c in chips] == [0, 1, 2, 3]
    assert [c["busy_s"] for c in chips] == busy
    assert all(c["executions"] >= 130 for c in chips)
    assert [c["memory_peak_bytes"] for c in chips] == [
        r["memory_peak_bytes"] for r in REPORTS]
    device = observe_nr.merge_reports(REPORTS)
    assert device["count"] == 4
    assert device["memory_peak_bytes"] == 90_217_987


def test_every_4r_metric_is_read_from_a_merged_run(chip_traces):
    import benchmark.run as bench_run
    from benchmark.lib import contract

    bm = contract.load_benchmark(REPO)
    e2e, per_layer = contract.declared_metrics(bm, "ycsb_f_closed_4r")
    assert sorted(m["name"] for m in e2e) == [
        "commit_in_limit_pct", "commits_per_s", "setup_s"]
    assert len(per_layer) == 11 and all(
        m["name"].endswith(".4r") and m["workloads"] == ["ycsb_f_closed_4r"]
        and m["moves"] == "commits_per_s" for m in per_layer)
    trace, chips = observe_nr.merge_traces(chip_traces, REPORTS)
    busy = [c["busy_s"] for c in chips]
    result = {
        "setup_s": 51.2,
        "generator": {"commits_per_s": 201.5, "commit_in_limit_pct": 98.4,
                      "retries_per_commit": 0.21, "commit_p50_ms": 137.1,
                      "commit_p95_ms": 206.6},
        "sources": {
            "spans": {s: hist_of(2.0, 4.0) for s in (
                "resolve_wait", "resolve_straggle", "device_dispatch",
                "verdict_wait", "tlog_durable")},
            "counters": {}, "trace": trace, "chips": chips,
            "ranges_share_fullest_pct": observe_nr.share_fullest_pct(
                [2990, 5714, 2568, 2714]),
            "chip_busy_least_over_most": min(busy) / max(busy)}}
    got = {m["name"]: bench_run.read_metric(m["name"], result)
           for m in e2e + per_layer}
    assert all(isinstance(v, float) for v in got.values()), got
    assert got["resolve_straggle_ms.4r"] == pytest.approx(3.0)
    assert got["ranges_share_fullest.4r"] == pytest.approx(40.855, abs=1e-3)
    assert got["device_ms_per_batch.4r"] == pytest.approx(
        trace["busy_s"] / chips[trace["busiest"]]["executions"] * 1e3)
    assert 0 < got["chip_busy_least_over_most.4r"] <= 1.0
    # an untraced run has no sources: nothing is read from spans, counters
    # or the trace (and run.py asks for no per-layer metric at all)
    del result["sources"]
    assert sorted(m["name"] for m in per_layer
                  if bench_run.read_metric(m["name"], result) is None) == sorted(
        m["name"] for m in per_layer if m["layer"] != "client")
