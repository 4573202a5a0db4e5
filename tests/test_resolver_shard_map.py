"""The deployed cluster's resolver map (server.resolver_shard_map): where
the spec states `resolver_splits` the resolvers' ranges follow them, where
it states none they are KeyShardMap.uniform as before; the static and the
managed wiring build the same map; a bad spec fails the boot. And what the
split is read by: the resolver's `ranges_received` / `txns_with_ranges`
counters and the proxy's `resolve_straggle` span."""

import json

import pytest

from foundationdb_tpu.core.types import KeyRange, TxnConflictInfo, Verdict
from foundationdb_tpu.loadgen.deploy import SocketCluster, build_spec
from foundationdb_tpu.runtime.shardmap import MAX_KEY, KeyShardMap
from foundationdb_tpu.server import load_spec, resolver_shard_map

SPLITS = [b"user3", b"user5", b"user7"]


def bounds(m: KeyShardMap) -> list:
    return [(s.range.begin, s.range.end, s.team) for s in m.shards]


@pytest.mark.parametrize("n", [1, 2, 4])
def test_a_spec_without_splits_gives_the_uniform_map_as_before(n):
    spec = build_spec(resolvers=n)
    assert "resolver_splits" not in spec
    assert bounds(resolver_shard_map(spec)) == bounds(KeyShardMap.uniform(n))


def test_a_spec_with_splits_gives_the_ranges_it_states():
    spec = build_spec(resolvers=4, resolver_splits=SPLITS)
    assert spec["resolver_splits"] == [k.hex() for k in SPLITS]
    json.dumps(spec)  # the cluster file is JSON: hex, not bytes
    assert bounds(resolver_shard_map(spec)) == [
        (b"", b"user3", (0,)), (b"user3", b"user5", (1,)),
        (b"user5", b"user7", (2,)), (b"user7", MAX_KEY, (3,))]


def test_uniform_splits_by_first_byte_so_one_prefix_is_one_resolvers():
    """Today's defect, pinned: every YCSB key starts with 0x75."""
    m = KeyShardMap.uniform(4)
    assert [s.range.begin for s in m.shards] == [b"", b"\x40", b"\x80",
                                                 b"\xc0"]
    assert {m.tag_for_key(b"user%d" % i) for i in range(0, 10**19,
                                                       10**17)} == {1}


@pytest.mark.parametrize("n_live,want", [
    (4, SPLITS), (3, [b"user3", b"user5"]), (2, [b"user5"]), (1, [])])
def test_a_generation_over_fewer_live_resolvers_merges_neighbours(
        n_live, want):
    spec = build_spec(resolvers=4, resolver_splits=SPLITS)
    m = resolver_shard_map(spec, n_live)
    assert m.n_shards == n_live
    assert [s.range.begin for s in m.shards][1:] == want


@pytest.mark.parametrize("splits,why", [
    ([b"user5", b"user3", b"user7"], "ascending"),
    ([b"user3", b"user3", b"user7"], "ascending"),
    ([b"user3", b"user5"], "need 3"),
    ([b"user3", b"user5", b"user7", b"user9"], "need 3"),
    ([b"", b"user5", b"user7"], "ascending"),
    ([b"user3", b"user5", MAX_KEY], "ascending"),
])
def test_bad_splits_fail_the_boot(tmp_path, splits, why):
    spec = build_spec(resolvers=4, resolver_splits=splits)
    with pytest.raises(ValueError, match=why):
        resolver_shard_map(spec)
    path = tmp_path / "cluster.json"
    path.write_text(json.dumps(spec))
    with pytest.raises(ValueError, match=why):
        load_spec(str(path))  # every role's and every client's first step


def test_splits_that_are_not_hex_fail_the_boot():
    spec = dict(build_spec(resolvers=2), resolver_splits=["user5"])
    with pytest.raises(ValueError, match="hex"):
        resolver_shard_map(spec)


def test_the_launcher_writes_the_splits_into_the_cluster_file(tmp_path):
    c = SocketCluster(str(tmp_path), resolvers=4, resolver_splits=SPLITS)
    assert bounds(resolver_shard_map(load_spec(c.spec_path))) == bounds(
        KeyShardMap(SPLITS, tags=[0, 1, 2, 3]))


def test_the_static_and_the_managed_wiring_build_the_same_map():
    from foundationdb_tpu.runtime.net import NetTransport, RealLoop
    from foundationdb_tpu.server import Worker, build_role, parse_addr

    spec = build_spec(resolvers=4, tlogs=2, storages=2, managed=True,
                      resolver_splits=SPLITS)
    loop = RealLoop()
    t = NetTransport(loop)
    try:
        static = dict(spec)
        del static["controller"]
        build_role(loop, t, static, "proxy", 0, None)
        proxy = t._services["commit_proxy"][0]
        worker = Worker(loop, t, spec, "proxy", 0, None)
        loop.run(worker.recruit_proxy(
            1, [list(parse_addr(a)) for a in spec["tlog"]],
            [list(parse_addr(a)) for a in spec["resolver"]]), timeout=30)
        recruited = worker._commit_proxy
        assert recruited is not proxy
        want = bounds(KeyShardMap(SPLITS, tags=[0, 1, 2, 3]))
        assert bounds(proxy.resolver_map) == want
        assert bounds(recruited.resolver_map) == want
    finally:
        t.close()


def _txn(reads, writes, rv=0):
    return TxnConflictInfo(
        read_version=rv,
        read_ranges=[KeyRange(b, e) for b, e in reads],
        write_ranges=[KeyRange(b, e) for b, e in writes])


def test_a_resolver_counts_the_ranges_it_was_sent():
    from foundationdb_tpu.runtime.flow import Loop
    from foundationdb_tpu.runtime.resolver import Resolver
    from foundationdb_tpu.sim.oracle import OracleConflictSet

    loop = Loop(seed=0)
    r = Resolver(loop, OracleConflictSet())
    batch = [_txn([(b"a", b"b")], [(b"a", b"b")]), _txn([], []),
             _txn([(b"c", b"d"), (b"e", b"f")], [])]

    async def drive():
        await r.resolve(0, 10, batch)
        await r.resolve(10, 20, [_txn([], [])])
        return await r.get_metrics()

    m = loop.run(drive(), timeout=60)
    assert (m["batches_resolved"], m["txns_resolved"]) == (2, 4)
    assert (m["ranges_received"], m["txns_with_ranges"]) == (4, 2)


def test_the_proxy_records_how_far_apart_the_resolvers_replies_land():
    from foundationdb_tpu.obs.span import SUB_STAGES, SpanSink
    from foundationdb_tpu.runtime.commit_proxy import CommitProxy
    from foundationdb_tpu.runtime.flow import Loop

    class Slow:
        def __init__(self, loop, delay_s):
            self.loop, self.delay_s = loop, delay_s

        async def resolve(self, prev_version, version, txns):
            await self.loop.sleep(self.delay_s)
            return ([Verdict.COMMITTED] * len(txns), {}, False, None)

    assert "resolve_straggle" in SUB_STAGES
    for delays, want_ms in (([0.010], 0.0), ([0.010, 0.050, 0.020], 40.0)):
        loop = Loop(seed=0)
        sink = SpanSink(loop, sample_every=1)
        n = len(delays)
        proxy = CommitProxy(
            loop, None, [Slow(loop, d) for d in delays],
            KeyShardMap.uniform(n), [], KeyShardMap.uniform(1))
        req = _txn([(b"a", b"b")], [(b"a", b"b")])

        async def drive():
            for v in (1, 2):
                verdicts, *_ = await proxy._resolve(
                    [(req, None), (req, None)], v - 1, v)
                assert verdicts == [Verdict.COMMITTED] * 2

        loop.run(drive(), timeout=60)
        h = sink.stage_hists["resolve_straggle"]
        assert h.count == 4  # two batches of two, txn-weighted
        assert h.mean() == pytest.approx(want_ms, abs=1.0)
