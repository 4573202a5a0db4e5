"""Observability subsystem (foundationdb_tpu/obs): commit-path span
trees, stage-sum-vs-e2e reconciliation, sim determinism, the unified
metrics scrape + name audit, tracer file retention, and the CI surfaces.
"""

import json
import os
import subprocess
import sys

import pytest

from foundationdb_tpu.obs.registry import (
    MetricsPoller,
    MetricsRegistry,
    scrape_sim,
)
from foundationdb_tpu.obs.selfcheck import (
    _drive,
    _new_cluster,
    latency_probe,
    run_overhead_ab,
    run_selfcheck,
    span_records,
)
from foundationdb_tpu.obs.span import (
    SUB_STAGES,
    TXN_STAGES,
    SpanSink,
    check_txn_tree,
)
from foundationdb_tpu.runtime.flow import Loop

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- sampling / sink mechanics ------------------------------------------------


def test_sampling_is_counter_based_1_in_n():
    sink = SpanSink(Loop(seed=1), sample_every=4)
    hits = [sink.sample() is not None for _ in range(12)]
    assert hits == [False, False, False, True] * 3
    assert sink.txns_sampled == 3 and sink.txns_seen == 12
    # Trace ids are sequential and unique (sim: no pid salt).
    sink2 = SpanSink(Loop(seed=1), sample_every=1)
    tids = [sink2.sample().tid for _ in range(5)]
    assert tids == sorted(set(tids))


def test_record_txn_identity_and_tree_check():
    sink = SpanSink(Loop(seed=1), sample_every=1)
    ctx = sink.sample()
    stages = [
        ("grv_wait", 0.0, 0.002),
        ("proxy_admit", 0.003, 0.001),
        ("batch_form", 0.004, 0.001),
        ("resolve_wait", 0.005, 0.002),
        ("wave_apply", 0.007, 0.0),
        ("tlog_durable", 0.007, 0.001),
        ("commit_publish", 0.008, 0.001),
        ("reply", 0.002, 0.0005),
    ]
    resid = sink.record_txn(ctx.tid, 0.0095, stages)
    assert resid == pytest.approx(0.0095 - 0.0085)
    spans = sink.spans_for(ctx.tid)
    assert check_txn_tree(spans) == []
    # A missing stage and a chain gap are both reported.
    broken = [s for s in spans if s["name"] != "tlog_durable"]
    assert any("missing stage: tlog_durable" in p
               for p in check_txn_tree(broken))


def test_stage_tick_samples_1_in_n_with_weights():
    sink = SpanSink(Loop(seed=1), sample_every=4)
    for _ in range(8):
        sink.stage_tick("tlog_fsync", 0.001, n=3)
    h = sink.stage_hists["tlog_fsync"]
    assert h.count == 6  # 2 ticks recorded, weight 3 each
    assert h.sum_ms == pytest.approx(6.0)


def test_ring_eviction_excludes_possibly_truncated_oldest_tid():
    """Front-eviction can truncate only the OLDEST surviving tid's block
    (record_txn appends one txn's spans contiguously): completeness
    gates use complete_only=True so scale never manufactures a spurious
    missing-stage failure."""
    sink = SpanSink(Loop(seed=1), sample_every=1, ring_size=30)
    for _ in range(10):  # 4 spans per txn -> 40 > ring 30
        ctx = sink.sample()
        sink.record_txn(ctx.tid, 0.01, [("grv_wait", 0.0, 0.001),
                                        ("reply", 0.001, 0.001)])
    assert sink._spans_dropped > 0
    tids = sink.sampled_tids()
    assert sink.sampled_tids(complete_only=True) == tids[1:]
    # Without eviction, complete_only drops nothing.
    sink.reset()
    ctx = sink.sample()
    sink.record_txn(ctx.tid, 0.01, [("grv_wait", 0.0, 0.001)])
    assert sink.sampled_tids(complete_only=True) == [ctx.tid]


def test_breakdown_merge_dumps_sums_histograms():
    a, b = SpanSink(Loop(seed=1), sample_every=1), None
    ctx = a.sample()
    a.record_txn(ctx.tid, 0.010, [("grv_wait", 0.0, 0.004)])
    b = SpanSink(Loop(seed=2), sample_every=1)
    ctx2 = b.sample()
    b.record_txn(ctx2.tid, 0.020, [("grv_wait", 0.0, 0.006)])
    merged = SpanSink.merge_dumps([a.dump(), b.dump()])
    assert merged["e2e"]["count"] == 2
    assert merged["stages"]["grv_wait"]["count"] == 2
    assert merged["attributed_ms"] == pytest.approx(10.0)
    assert merged["unattributed_ms"] == pytest.approx(20.0)


# -- sim cluster end to end ---------------------------------------------------


class TestSimClusterTracing:
    def test_span_trees_complete_and_identity_holds(self):
        c = _new_cluster(21, obs=True, sample_every=3)
        _drive(c, 96)
        sink = c.loop.span_sink
        trees = 0
        for tid in sink.sampled_tids():
            spans = sink.spans_for(tid)
            if not any(s["name"] == "e2e" for s in spans):
                continue
            trees += 1
            assert check_txn_tree(spans) == [], spans
        assert trees >= 20
        b = sink.breakdown()
        # Population reconciliation: residue bounded and never dropped.
        assert b["unattributed_frac"] <= 0.10
        assert abs(b["e2e"]["sum_ms"] - b["attributed_ms"]
                   - b["unattributed_ms"]) < 1e-6
        for s in TXN_STAGES:
            if s != "shaped_park":
                assert s in b["stages"], s

    def test_resolver_and_tlog_substages_populate(self):
        c = _new_cluster(22, obs=True, sample_every=1)
        _drive(c, 64)
        hists = c.loop.span_sink.stage_hists
        for s in ("grv_proxy_queue", "coalesce_queue", "device_dispatch",
                  "tlog_fsync"):
            assert s in SUB_STAGES and s in hists and hists[s].count > 0, s

    def test_host_pack_stamp_cleared_for_non_packing_batches(self):
        """A batch that never packs (fail-safe path skips cs.resolve)
        must not re-record the previous batch's host-pack time."""
        from foundationdb_tpu.core.types import KeyRange, TxnConflictInfo
        from foundationdb_tpu.runtime.resolver import Resolver
        from foundationdb_tpu.sim.oracle import OracleConflictSet

        loop = Loop(seed=9)
        cs = OracleConflictSet()
        sink = SpanSink(loop, sample_every=1)
        r = Resolver(loop, cs)
        txns = [TxnConflictInfo(read_version=0,
                                read_ranges=[KeyRange(b"a", b"b")],
                                write_ranges=[KeyRange(b"a", b"b")])]
        # a stale record from a previous batch
        cs.last_stage_s = {"host_pack": 0.005}
        loop.run(r.resolve(0, 10, txns), timeout=60)
        assert "host_pack" not in sink.stage_hists  # cleared, not reused

    def test_shaped_park_stage_under_admission(self):
        c = _new_cluster(3, obs=True, sample_every=1, admission=True)
        _drive(c, 160, conflicting=True)
        sink = c.loop.span_sink
        shaped_committed = sum(
            p.admission.metrics()["shaped_committed"]
            for p in c.commit_proxies)
        assert shaped_committed > 0  # the workload actually shaped txns
        park = sink.stage_hists.get("shaped_park")
        assert park is not None and park.count == shaped_committed
        # Shaped trees are still gap-free (the park is carved out of the
        # admit->version window, never double-counted).
        for tid in sink.sampled_tids():
            spans = sink.spans_for(tid)
            if any(s["name"] == "shaped_park" for s in spans):
                assert check_txn_tree(spans) == []
                break
        else:
            pytest.fail("no sampled shaped txn produced a tree")

    def test_same_seed_byte_identical_span_records(self):
        assert span_records(5, txns=64) == span_records(5, txns=64)
        assert span_records(5, txns=64) != span_records(6, txns=64)

    def test_off_by_default_no_sink_no_spans(self):
        c = _new_cluster(23, obs=False, sample_every=1)
        assert not hasattr(c.loop, "span_sink")
        _drive(c, 16)
        assert not hasattr(c.loop, "span_sink")

    def test_status_json_carries_latency_breakdown(self):
        from foundationdb_tpu.runtime.status import fetch_status

        c = _new_cluster(24, obs=True, sample_every=2)
        _drive(c, 48)
        doc = c.loop.run(fetch_status(c), timeout=600)
        lb = doc["workload"]["latency_breakdown"]
        assert lb["enabled"] and lb["txns_sampled"] > 0
        assert "resolve_wait" in lb["stages"]
        # Off cluster: the section says so instead of vanishing.
        c2 = _new_cluster(24, obs=False, sample_every=2)
        doc2 = c2.loop.run(fetch_status(c2), timeout=600)
        assert doc2["workload"]["latency_breakdown"] == {"enabled": False}


# -- metrics registry ---------------------------------------------------------


class TestMetricsRegistry:
    def test_scrape_audit_clean_and_documented_counters_exist(self):
        c = _new_cluster(31, obs=True, sample_every=2)
        _drive(c, 48)
        # CamelCase TraceEvent TYPE names ride the scrape as labels and
        # are exempt from the snake_case rule — an audit that reddened
        # the CI stage the first time any event fired would be a false
        # alarm (events always fire under faults/recoveries).
        c.loop.tracer.event("MasterRecoveryTriggered")
        reg = c.loop.run(scrape_sim(c), timeout=600)
        assert "trace.events.MasterRecoveryTriggered" in reg.values
        assert reg.audit() == []
        assert reg.missing_documented() == []
        agg = reg.aggregated()
        assert agg["commit_proxy.txns_committed"] >= 48
        assert agg["resolver.txns_resolved"] >= 48
        assert agg["grv_proxy.grvs_served"] >= 48

    def test_prometheus_text_format(self):
        c = _new_cluster(32, obs=False, sample_every=2)
        _drive(c, 16)
        reg = c.loop.run(scrape_sim(c), timeout=600)
        text = reg.to_prometheus()
        assert "# TYPE fdb_tpu_commit_proxy_txns_committed gauge" in text
        line = next(l for l in text.splitlines()
                    if l.startswith("fdb_tpu_commit_proxy_txns_committed"))
        assert 'process="commit_proxy0"' in line
        assert float(line.rsplit(" ", 1)[1]) >= 16
        doc = json.loads(reg.to_json_line())
        assert doc["metric"] == "obs_scrape"
        assert doc["metrics"]["commit_proxy.txns_committed"] >= 16

    def test_collision_and_snake_case_detection(self):
        reg = MetricsRegistry()
        reg.add("role", "p0", {"good_name": 1, "BadName": 2})
        problems = reg.audit()
        assert any("not snake_case" in p and "BadName" in p
                   for p in problems)
        # Same full key from two different scrape sources = collision
        # (one role's truth would silently overwrite another's).
        reg2 = MetricsRegistry()
        reg2.add("role", "p0", {"x": 1})
        reg2.add("role", "p0", {"x": 2})
        assert any("collision" in p and "role.x#p0" in p
                   for p in reg2.audit())

    def test_metrics_poller_appends_jsonl(self, tmp_path):
        c = _new_cluster(33, obs=False, sample_every=2)
        path = str(tmp_path / "metrics.jsonl")
        poller = MetricsPoller(c.loop, lambda: scrape_sim(c), path,
                               interval_s=1.0)
        c.loop.spawn(poller.run(), process="metrics_poller",
                     name="poller.run")
        _drive(c, 32)  # advances virtual time well past a few intervals

        async def settle():
            await c.loop.sleep(3.0)

        c.loop.run(settle(), timeout=600)
        lines = [json.loads(l) for l in open(path)]
        assert len(lines) >= 2 and poller.snapshots_written >= 2
        assert all(l["metric"] == "obs_scrape" for l in lines)
        # A time series, not one snapshot repeated.
        assert lines[0]["t"] < lines[-1]["t"]


# -- timeline export ----------------------------------------------------------


def test_chrome_trace_export_structure():
    c = _new_cluster(41, obs=True, sample_every=2)
    _drive(c, 48)
    doc = c.loop.span_sink.to_chrome_trace()
    evs = doc["traceEvents"]
    assert evs and all(e["ph"] == "X" for e in evs)
    names = {e["name"] for e in evs}
    assert {"grv_wait", "resolve_wait", "tlog_durable", "e2e"} <= names
    ex = next(e for e in evs if e["name"] == "resolve_wait")
    assert ex["dur"] >= 0 and isinstance(ex["ts"], float)
    assert doc["metadata"]["processes"]  # pid -> process name map


# -- tracer file-sink retention (satellite) -----------------------------------


class TestTracerRetention:
    def _mk(self, tmp_path, max_files):
        from foundationdb_tpu.runtime.trace import Tracer

        loop = Loop(seed=4)
        return Tracer(loop, trace_dir=str(tmp_path), process="proxy1",
                      roll_bytes=120, max_files=max_files)

    def test_oldest_rolled_files_deleted_beyond_cap(self, tmp_path):
        t = self._mk(tmp_path, max_files=3)
        for i in range(40):
            t.event("E", I=i)
        t.close()
        files = sorted(os.listdir(tmp_path))
        assert len(files) <= 3
        recs = []
        for f in files:
            recs += [json.loads(line) for line in open(tmp_path / f)]
        # The NEWEST records survive; the deleted ones are the oldest.
        assert recs[-1]["I"] == 39
        assert recs[0]["I"] > 0

    def test_rotation_boundary_exact_cap_keeps_all(self, tmp_path):
        t = self._mk(tmp_path, max_files=3)
        # Each event (~90 bytes vs roll_bytes=120) closes its file after
        # two writes; step until exactly 3 files exist.
        i = 0
        while len(os.listdir(tmp_path)) < 3:
            t.event("E", I=i)
            i += 1
        assert len(os.listdir(tmp_path)) == 3  # at cap: nothing deleted
        first = min(os.listdir(tmp_path))
        for _ in range(4):  # force at least one more roll
            t.event("E", I=i)
            i += 1
        t.close()
        files = sorted(os.listdir(tmp_path))
        assert len(files) <= 3 and first not in files

    def test_unlimited_by_default(self, tmp_path):
        t = self._mk(tmp_path, max_files=None)
        for i in range(40):
            t.event("E", I=i)
        t.close()
        assert len(os.listdir(tmp_path)) > 3  # historical behavior


# -- open-loop embed ----------------------------------------------------------


def test_open_loop_result_embeds_obs_dump():
    from foundationdb_tpu.client.ryw import open_database
    from foundationdb_tpu.loadgen.arrivals import poisson_schedule
    from foundationdb_tpu.loadgen.harness import run_open_loop
    from foundationdb_tpu.sim.cluster import SimCluster

    c = SimCluster(seed=11, obs=True, obs_sample_every=2)
    db = open_database(c)
    sched = poisson_schedule(150.0, 1.5, seed=5)

    async def txn_fn(tr, k):
        tr.set(b"ol/%d" % (k % 32), b"v")

    async def main():
        return await run_open_loop(c.loop, db, sched, txn_fn,
                                   n_clients=16, timeout_ms=None)

    res = c.loop.run(main(), timeout=600)
    assert res.committed == res.offered
    d = res.to_dict()["obs"]
    assert d["txns_sampled"] > 0 and "resolve_wait" in d["stages"]
    merged = SpanSink.merge_dumps([d, d])
    assert merged["e2e"]["count"] == 2 * d["e2e"]["bins"][0][1] or \
        merged["txns_sampled"] == 2 * d["txns_sampled"]
    # The sink reset: a second run starts a fresh window.
    assert c.loop.span_sink.txns_sampled == 0


# -- CI surfaces --------------------------------------------------------------


def test_selfcheck_passes_inline():
    rec = run_selfcheck(txns=96)
    assert rec["ok"], rec["problems"]
    assert rec["unattributed_frac"] <= 0.10
    assert rec["span_trees_checked"] > 0


def test_selfcheck_main_one_json_line():
    out = subprocess.run(
        [sys.executable, "-m", "foundationdb_tpu.obs", "--txns", "96"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "obs_selfcheck" and rec["ok"]


def test_overhead_ab_record_shape():
    # Shape only (a loaded CI host makes the 2% gate itself noisy —
    # OBS_AB.json is the quotable artifact, produced by scripts/obs_ab.sh
    # on a quiet host).
    rec = run_overhead_ab(txns=96, reps=1)
    assert rec["metric"] == "obs_sampling_overhead_ab"
    assert rec["sample_every"] == 64 and rec["gate_frac"] == 0.02
    assert isinstance(rec["overhead_frac"], float)
    assert rec["cpu_fallback"] is False
    assert rec["best_off_tps"] > 0 and rec["best_on_tps"] > 0


def test_deployed_scrape_and_obs_snapshot(tmp_path):
    """Real-socket slice: the unified scrape over TCP endpoints passes
    the audit, and an FDB_TPU_OBS-armed server process answers the
    admin obs_snapshot RPC with its sink's breakdown."""
    from foundationdb_tpu.loadgen.deploy import SocketCluster
    from foundationdb_tpu.obs.registry import scrape_deployed
    from foundationdb_tpu.runtime.net import NetTransport, RealLoop
    from foundationdb_tpu.server import load_spec, parse_addr

    with SocketCluster(str(tmp_path / "c"), proxies=1,
                       env={"FDB_TPU_OBS": "1"}) as cluster:
        loop = RealLoop()
        t = NetTransport(loop)
        try:
            spec = load_spec(cluster.spec_path)
            reg = scrape_deployed(loop, t, spec)
            assert reg.audit() == []
            agg = reg.aggregated()
            assert "tlog.queue_bytes" in agg
            assert "grv_proxy.grvs_served" in agg
            assert "fdb_tpu_tlog_queue_bytes" in reg.to_prometheus()
            ep = t.endpoint(parse_addr(spec["proxy"][0]), "admin")
            snap = loop.run(ep.obs_snapshot(), timeout=10.0)
            assert snap["enabled"] is True
            assert snap["breakdown"]["sample_every"] >= 1
        finally:
            t.close()


def test_latency_probe_warns_on_untraced_servers(tmp_path):
    """Against a deployed cluster whose servers run WITHOUT
    FDB_TPU_OBS=1, the probe still attributes the client-side stages,
    reports the commit round trip as unattributed, and says why."""
    from foundationdb_tpu.cli import open_cluster
    from foundationdb_tpu.loadgen.deploy import SocketCluster

    with SocketCluster(str(tmp_path / "c"), proxies=1) as cluster:
        loop, t, db = open_cluster(cluster.spec_path)
        try:
            report = loop.run(latency_probe(db, loop, n=8), timeout=60.0)
            assert report["warning"].startswith("server-side tracing")
            assert "resolve_wait" not in report["stages"]
            assert report["stages"]["grv_wait"]["count"] == 8
            assert report["unattributed_frac"] > 0.3
        finally:
            t.close()


def test_latency_probe_always_samples_and_restores_sink():
    from foundationdb_tpu.client.ryw import open_database

    c = _new_cluster(51, obs=False, sample_every=2)
    db = open_database(c)
    report = c.loop.run(latency_probe(db, c.loop, n=12), timeout=600)
    assert report["txns_sampled"] == 12
    assert report["unattributed_frac"] <= 0.10
    assert "tlog_durable" in report["stages"]
    assert not hasattr(c.loop, "span_sink")  # probe sink removed


# -- the span clock, the wait in front of a loop, a process's busy share ------
# (PR 38: obs/span.py span_now, rpc_inbound:<service>.<method>,
# loop_busy:<role> / loop_idle:<role>, READ_PATH_STAGES)


def _conflict_txns():
    from foundationdb_tpu.core.types import KeyRange, TxnConflictInfo

    return [TxnConflictInfo(read_version=0,
                            read_ranges=[KeyRange(b"a", b"b")],
                            write_ranges=[KeyRange(b"a", b"b")])]


def test_a_wait_inside_one_loop_turn_is_seen_by_the_span_clock():
    """Two batches admitted and dispatched in ONE pump turn, with 30 ms of
    somebody else's synchronous work before the group's dispatch and an
    engine that takes 20 ms a batch: `loop.now` stands still through the
    turn (the parent read coalesce_queue 0.0 here, in every line of the
    ledger); the span clock does not, and the second batch's wait holds
    the first one's bracket."""
    import time

    from foundationdb_tpu.runtime.net import RealLoop
    from foundationdb_tpu.runtime.resolver import Resolver
    from foundationdb_tpu.sim.oracle import OracleConflictSet

    class SlowEngine(OracleConflictSet):
        def resolve(self, *args, **kwargs):
            time.sleep(0.02)
            return super().resolve(*args, **kwargs)

    loop = RealLoop()
    sink = SpanSink(loop, sample_every=1)
    resolver = Resolver(loop, SlowEngine())
    loop_clock = []

    async def blocker():
        loop_clock.append(loop.now)
        time.sleep(0.03)
        loop_clock.append(loop.now)

    async def main():
        asked = [loop.spawn(resolver.resolve(v - 10, v, _conflict_txns()),
                            name="ask") for v in (10, 20)]
        loop.spawn(blocker(), name="blocker")  # runs before the dispatch
        for a in asked:
            await a
        loop_clock.append(loop.now)

    loop.run(main(), timeout=30)
    assert len(set(loop_clock)) == 1  # one turn: the loop's clock stood
    queued = sink.stage_hists["coalesce_queue"]
    assert queued.count == 2
    # 30 ms for the first, 30 + the first's 20 ms for the second
    assert queued.sum_ms >= 30.0 + 50.0 and queued.max_ms >= 50.0


#: sha256 of `span_records(seed, txns=64)` without the read path's stages.
#: A sim loop's span clock is its virtual `loop.now`, so a change that only
#: ADDS spans (PR 38 did: the read path's stages are new records beside
#: these) writes every record again, byte for byte, and so does a second
#: run. A change to WHEN things happen moves them, and says so here: PR 39
#: (the tlogs are told of a commit at its acknowledgement, one RPC more a
#: batch; GRV buckets refill by elapsed time; a proxy's batch interval
#: follows its resolve latency) re-pinned both; before it
#: they were b468b160...0c8bb8 and 10895abd...c8ba8eb (a8e48d7, 528e60b).
PARENT_SPAN_RECORDS = {
    5: "36476c973d35b6299405ebb0851351cd157787a6c9ae9028ae150284e7696ec2",
    38: "c492c73660ea04b37e5a2c257e8191859e2a2b7be74b32a8be9888bf7b0586c3",
}


@pytest.mark.parametrize("seed", sorted(PARENT_SPAN_RECORDS))
def test_sim_span_records_are_the_parents_byte_for_byte(seed):
    import hashlib

    from foundationdb_tpu.obs.span import READ_PATH_STAGES

    records = json.loads(span_records(seed, txns=64))
    new = [r for r in records if r["name"] in READ_PATH_STAGES]
    kept = json.dumps([r for r in records if r["name"] not in
                       READ_PATH_STAGES], sort_keys=True)
    assert hashlib.sha256(kept.encode()).hexdigest() == \
        PARENT_SPAN_RECORDS[seed]
    assert {r["name"] for r in new} >= {
        "grv_rtt", "read_rpc", "grv_sequencer_rtt", "storage_version_wait",
        "storage_lookup"}
    assert all(r.get("version") is not None for r in new)


class _Echo:
    from foundationdb_tpu.runtime.flow import rpc as _rpc

    @_rpc
    async def echo(self, x):
        return x


@pytest.mark.parametrize("sender_sink,receiver_sink", [
    (True, True), (True, False), (False, True)])
def test_rpc_inbound_needs_a_sink_at_both_ends(sender_sink, receiver_sink):
    """Two loops in one thread, pumped by hand: the request sits in the
    socket for 30 ms while 'the receiver's thread is busy', then the
    receiver's loop gets its turn. The stamp rides the frame only while
    the sender traces; an unstamped frame records nothing, and is no
    error."""
    import time

    from foundationdb_tpu.runtime import wire
    from foundationdb_tpu.runtime.net import NetTransport, RealLoop

    c_loop, s_loop = RealLoop(), RealLoop()
    if sender_sink:
        SpanSink(c_loop, sample_every=1)
    s_sink = SpanSink(s_loop, sample_every=1) if receiver_sink else None
    server, client = NetTransport(s_loop), NetTransport(c_loop)
    server.serve("echo", _Echo())
    frames = []
    on_frame = server._on_frame
    server._on_frame = lambda conn, frame: (
        frames.append(wire.loads(frame)), on_frame(conn, frame))[1]
    try:
        fut = client.endpoint(server.addr, "echo").echo(7)
        c_loop.run_until(c_loop.sleep(0.02), timeout=5)  # connect, flush
        time.sleep(0.03)  # the receiver is busy with something else
        for _ in range(200):
            s_loop.run_until(s_loop.sleep(0.002), timeout=5)
            c_loop.run_until(c_loop.sleep(0.002), timeout=5)
            if fut.done():
                break
        assert fut.result() == 7
    finally:
        server.close()
        client.close()
    (request,) = frames
    # no new wire element when the sender has no sink
    assert len(request) == (7 if sender_sink else 5)
    if s_sink is None:
        return
    assert s_sink.stage_hists["rpc_decode"].count == 1
    if sender_sink:
        inbound = s_sink.stage_hists["rpc_inbound:echo.echo"]
        assert inbound.count == 1 and inbound.sum_ms >= 30.0
        assert s_loop.role == "echo"  # the first service it serves
    else:
        assert not [s for s in s_sink.stage_hists
                    if s.startswith("rpc_inbound")]


def test_loop_busy_and_idle_sum_to_the_wall_time_of_a_run():
    import time

    from foundationdb_tpu.runtime.net import RealLoop

    loop = RealLoop()
    sink = SpanSink(loop, sample_every=64)  # these two are never sampled

    async def main():
        for _ in range(10):
            time.sleep(0.02)  # synchronous work: busy
            await loop.sleep(0.05)  # a timer set on the turn's start: idle

    t0 = time.perf_counter()
    loop.run(main(), timeout=30)
    wall_ms = (time.perf_counter() - t0) * 1e3
    busy = sink.stage_hists["loop_busy:client"]  # it serves nothing
    idle = sink.stage_hists["loop_idle:client"]
    assert busy.count == idle.count >= 3  # one sample each a >= 100 ms slice
    assert busy.sum_ms + idle.sum_ms == pytest.approx(wall_ms, rel=0.05)
    assert busy.sum_ms >= 200.0 * 0.95 and idle.sum_ms >= 200.0


def test_a_loop_without_a_sink_reads_no_clock_in_the_pump(monkeypatch):
    import time
    import types

    from foundationdb_tpu.runtime import net

    def no_clock():
        raise AssertionError("the pump read the span clock with no sink")

    monkeypatch.setattr(net, "time", types.SimpleNamespace(
        monotonic=time.monotonic, sleep=time.sleep, time=time.time,
        perf_counter=no_clock))
    loop = net.RealLoop()

    async def main():
        for _ in range(3):
            await loop.sleep(0.005)
        return "done"

    assert loop.run(main(), timeout=30) == "done"
    assert not hasattr(loop, "span_sink")


@pytest.mark.parametrize("named,services,role", [
    ("storage", ["admin", "storage", "worker"], "storage"),  # server.py
    (None, ["resolver", "admin"], "resolver"),  # the share launcher's order
    (None, ["admin", "tlog"], "tlog"),
    (None, [], None),  # a client: the pump says `client`
])
def test_a_loop_is_named_for_its_process(named, services, role):
    from foundationdb_tpu.runtime.net import NetTransport, RealLoop

    loop = RealLoop()
    loop.role = named
    t = NetTransport(loop)
    try:
        for name in services:
            t.serve(name, _Echo())
    finally:
        t.close()
    assert loop.role == role


@pytest.mark.parametrize("ahead", [False, True])
def test_storage_version_wait_is_zero_under_the_applied_version_and_the_park_above(
        ahead):
    from foundationdb_tpu.runtime.storage import StorageServer

    loop = Loop(seed=3)
    sink = SpanSink(loop, sample_every=1)
    ss = StorageServer(loop, tag=0, tlog_ep=None)
    ss._advance(100)

    async def main():
        if not ahead:
            return await ss.get(b"k", 100)
        read = loop.spawn(ss.get(b"k", 200), name="read")
        await loop.sleep(0.25)  # the pull loop is a quarter second behind
        ss._advance(200)
        return await read

    assert loop.run(main(), timeout=60) is None
    wait = sink.stage_hists["storage_version_wait"]
    assert wait.count == 1
    assert wait.sum_ms == pytest.approx(250.0 if ahead else 0.0)
    assert sink.stage_hists["storage_lookup"].count == 1
    by_name = {s["name"]: s for s in sink.spans}
    assert by_name["storage_version_wait"]["version"] == \
        by_name["storage_lookup"]["version"] == (200 if ahead else 100)


def test_a_read_only_sampled_transaction_leaves_its_grv_and_its_read():
    from foundationdb_tpu.client.ryw import open_database

    c = _new_cluster(31, obs=True, sample_every=1)
    db = open_database(c)

    async def read_only():
        tr = db.transaction()
        assert await tr.get(b"obs/none") is None
        return await tr.commit()  # read-only: nothing goes to a proxy

    version = c.loop.run(read_only(), timeout=600)
    sink = c.loop.span_sink
    # (the sim's one sink also holds the timekeeper's transactions)
    mine = [s for s in sink.spans if s["process"] == "<main>"]
    assert len({s["tid"] for s in mine}) == 1 and mine[0]["tid"] is not None
    # no grv_wait: that one is the commit identity's, and nothing committed
    assert sorted((s["name"], s["version"]) for s in mine) == [
        ("grv_rtt", version), ("read_rpc", version)]
    assert sink.stage_hists["grv_rtt"].count >= 1
    assert sink.stage_hists["read_rpc"].count == 1
    # the GRV proxy's and the storage's stages of the same read carry it
    for stage in ("grv_sequencer_rtt", "storage_version_wait",
                  "storage_lookup"):
        assert version in {s["version"] for s in sink.spans
                           if s["name"] == stage}, stage


def test_the_scrape_keeps_process_and_endpoint_stages_snake_case():
    from foundationdb_tpu.obs.registry import add_span_sink

    sink = SpanSink(Loop(seed=1), sample_every=1)
    sink.record_stage("loop_busy:proxy", 0.07)
    sink.record_stage("rpc_inbound:resolver.resolve", 0.03)
    reg = MetricsRegistry()
    add_span_sink(reg, sink)
    assert reg.audit() == []
    snap = reg.snapshot()
    assert snap["obs.stage_sum_ms.loop_busy_proxy"] == pytest.approx(70.0)
    assert snap["obs.stage_count.rpc_inbound_resolver_resolve"] == 1
