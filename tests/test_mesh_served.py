"""`resolvers=4` as ONE resolver over a four-chip mesh, through the path that
is served: the factory (`server.make_conflict_set`, the spec's
`resolver_mesh`), the resolver role over its engine, a socket cluster.

On the CPU backend over four of the eight host devices tests/conftest.py
provides. The plain references are the benchmark's own, which import
nothing of the program: `reference.point_verdicts` (one history, one key a
transaction: workload F), `reference_ranges.range_verdicts` (the same rule
over lists of ranges) and, for the case that tells the two four-resolver
semantics apart, `reference_nr.SplitResolvers` (every resolver paints what
IT accepted). The mesh sums the shards' conflict bits on the device before
anything is painted, so its verdicts are ONE history's.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import chip_smoke
from benchmark.lib import reference, reference_nr, reference_ranges, ycsb
from foundationdb_tpu.core.types import KeyRange, TxnConflictInfo, Verdict
from foundationdb_tpu.models.conflict_set import TPUConflictSet
from foundationdb_tpu.parallel import sharded_resolver as sr
from foundationdb_tpu.parallel.sharded_resolver import ShardedConflictSet
from foundationdb_tpu.runtime.flow import Loop
from foundationdb_tpu.runtime.resolver import Resolver
from foundationdb_tpu.server import (
    load_spec,
    make_conflict_set,
    make_engine,
    resolver_mesh,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAMES = {reference.COMMITTED: Verdict.COMMITTED,
         reference.CONFLICT: Verdict.CONFLICT,
         reference.TOO_OLD: Verdict.TOO_OLD}
STEP = 100  # versions a batch


def mesh_role(seed=3, **sizes):
    """The served role over the FACTORY's mesh engine, at a small size."""
    args = dict(capacity=1024, batch_size=32, max_read_ranges=4,
                max_write_ranges=4, max_key_bytes=16)
    args.update(sizes)
    cs = make_conflict_set("tpu", mesh=4, **args)
    loop = Loop(seed=seed)
    return loop, cs, Resolver(loop, cs)


def drive(loop, res, prev, version, txns, oldest):
    verdicts, _conflicting, fail_safe, _wave = loop.run(
        res.resolve(prev, version, txns, oldest_version=oldest))
    return verdicts, fail_safe


def info(read_version, reads, writes):
    return TxnConflictInfo(
        read_version=read_version,
        read_ranges=[KeyRange(b, e) for b, e in reads],
        write_ranges=[KeyRange(b, e) for b, e in writes])


def point(key):
    return (key, key + b"\x00")


class Compared:
    """Batches through the role and through a one-history reference,
    verdict for verdict. A batch the capacity fail-safe rejected is all
    CONFLICT and paints nothing, so the reference is not shown it."""

    def __init__(self, loop, res, judge):
        self.loop, self.res, self.judge = loop, res, judge
        self.prev, self.version = 0, 1000
        self.judged = self.fail_safe_batches = 0
        self.seen = {v: 0 for v in Verdict}

    def batch(self, txns, window):
        """`txns`: [(read_version, reads, writes)], ranges as byte pairs."""
        version = self.version
        oldest = max(0, version - window)
        got, fail_safe = drive(
            self.loop, self.res, self.prev, version,
            [info(*t) for t in txns], oldest)
        if fail_safe:
            assert got == [Verdict.CONFLICT] * len(txns)
            self.fail_safe_batches += 1
        else:
            want = [NAMES[v] for v in self.judge(txns, version, oldest)]
            assert got == want, (
                f"version {version}: first difference at "
                f"{next(i for i, (g, w) in enumerate(zip(got, want)) if g != w)}")
            self.judged += len(txns)
            for v in got:
                self.seen[v] += 1
        self.prev, self.version = version, version + STEP
        return got


def point_judge():
    last_write: dict = {}

    def judge(txns, version, oldest):
        return reference.point_verdicts(
            last_write, [r[0][0] for _rv, r, _w in txns],
            [rv for rv, _r, _w in txns], version, oldest)

    return judge


def range_judge():
    history = reference_ranges.RangeHistory()
    return lambda txns, version, oldest: reference_ranges.range_verdicts(
        history, txns, version, oldest)


# -- the factory and the spec ------------------------------------------------


def test_without_the_key_the_factory_builds_what_it_built():
    cs = make_conflict_set("tpu", n_resolvers=1)
    assert type(cs) is TPUConflictSet
    assert (cs.capacity, cs.batch_size, cs.max_read_ranges,
            cs.max_write_ranges, cs.codec.max_key_bytes) == (
        1 << 16, 512, 8, 8, 32)
    spec = {"engine": "tpu", "resolver": ["127.0.0.1:1"]}
    assert resolver_mesh(spec) is None


def test_with_the_key_the_factory_builds_the_mesh_at_the_served_sizes():
    cs = make_conflict_set("tpu", n_resolvers=1, mesh=4)
    assert type(cs) is ShardedConflictSet and cs.n_shards == 4
    assert (cs.capacity, cs.batch_size, cs.max_read_ranges,
            cs.max_write_ranges, cs.codec.max_key_bytes) == (
        1 << 16, 512, 8, 8, 32)  # capacity is a SHARD's
    assert cs.auto_reshard
    assert cs.reshard_interval == sr.AUTO_RESHARD_INTERVAL == 8
    assert cs.reshard_skew == sr.AUTO_RESHARD_SKEW == 4.0
    assert cs.device_info()["count"] == 4
    assert resolver_mesh({"engine": "tpu", "resolver": ["127.0.0.1:1"],
                          "resolver_mesh": 4}) == 4


def test_make_engine_reads_the_key_from_the_spec(capsys):
    spec = {"engine": "tpu", "resolver": ["127.0.0.1:1"], "resolver_mesh": 2}
    cs = make_engine(spec, "resolver0")
    assert type(cs) is ShardedConflictSet and cs.n_shards == 2
    assert "count=2" in capsys.readouterr().out  # the role's `device` line


def _spec(tmp_path, **changes):
    """A cluster file with the key; a change of None drops that key."""
    spec = {"sequencer": ["127.0.0.1:4500"], "resolver": ["127.0.0.1:4501"],
            "tlog": ["127.0.0.1:4502"], "storage": ["127.0.0.1:4503"],
            "proxy": ["127.0.0.1:4504"], "engine": "tpu",
            "resolver_mesh": 4}
    spec.update(changes)
    spec = {k: v for k, v in spec.items() if v is not None}
    path = tmp_path / "cluster.json"
    path.write_text(json.dumps(spec))
    return str(path)


REFUSED = {
    "two_resolver_addresses": dict(
        resolver=["127.0.0.1:4501", "127.0.0.1:4505"]),
    "engine_cpu": dict(engine="cpu"),
    "no_engine": dict(engine=None),
    "stated_splits_beside_it": dict(resolver_splits=[]),
    "one_chip": dict(resolver_mesh=1),
    "not_a_number": dict(resolver_mesh="4"),
    "a_flag": dict(resolver_mesh=True),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_a_spec_the_mesh_cannot_have_is_refused_by_name(tmp_path, case):
    with pytest.raises(ValueError, match="resolver_mesh"):
        load_spec(_spec(tmp_path, **REFUSED[case]))


def test_the_spec_that_is_served_loads(tmp_path):
    assert load_spec(_spec(tmp_path))["resolver_mesh"] == 4


@pytest.mark.parametrize("role", ["sequencer", "proxy", "resolver"])
def test_a_refused_spec_fails_the_boot_of_every_role(tmp_path, role):
    path = _spec(tmp_path, engine="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "foundationdb_tpu.server", "--cluster", path,
         "--role", role, "--index", "0"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "resolver_mesh=4 needs engine 'tpu'" in r.stderr
    assert "ready" not in r.stdout


def test_more_shards_than_chips_is_refused_by_name():
    with pytest.raises(ValueError, match=r"resolver_mesh=16 asks for 16 "
                                         r"chips and this process sees 8"):
        make_conflict_set("tpu", mesh=16)
    with pytest.raises(ValueError, match="resolver_mesh"):
        make_conflict_set("cpu", mesh=4)


def test_only_the_lone_tpu_resolver_can_reach_the_chips(tmp_path,
                                                        monkeypatch):
    """A spec with `resolver_mesh` counts on the lone resolver seeing every
    chip of the host: it keeps the caller's environment, unbound to any
    one chip, and every other role is pinned to the CPU."""
    from foundationdb_tpu.loadgen.deploy import SocketCluster

    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    cluster = SocketCluster(str(tmp_path), proxies=2, tlogs=2, storages=2,
                            engine="tpu", spec_extra={"resolver_mesh": 4})
    envs = {p.name: cluster._env_for(p) for p in cluster.procs}
    res = envs.pop("resolver0")
    assert res["JAX_PLATFORMS"] == "tpu,cpu"
    assert not any(k.startswith("TPU_") for k in res
                   if k not in os.environ)
    assert {e["JAX_PLATFORMS"] for e in envs.values()} == {"cpu"}


# -- the served role against the one-history reference -----------------------


def test_workload_f_through_the_role_equals_the_one_history_reference():
    """Workload F's own stream (one read and one write range on a scrambled
    Zipfian key), then never-seen keys until the fail-safe engages and
    releases, then the stream again on other records: TOO_OLD, a fail-safe
    rejection and two automatic re-splits on the way."""
    loop, cs, res = mesh_role(capacity=1024)
    run = Compared(loop, res, point_judge())
    records = ycsb.Records(600, seed=11)
    kinds, items = ycsb.plan(records.count, 4096, 0.5, seed=11,
                             base_seed=20100610)
    rmw = [int(i) for k, i in zip(kinds, items) if k == ycsb.RMW]
    window = 40 * STEP

    def f_batch(ids, n, window):
        v = run.version  # every ninth reads below the floor, once there is one
        return [((v - window - 50) if j % 9 == 8 and v > window + 50
                 else v - 150 - 10 * (j % 7),
                 [point(records.keys[i])], [point(records.keys[i])])
                for j, i in enumerate(ids[:n])]

    at = 0
    for _ in range(30):
        run.batch(f_batch(rmw[at:], 24, window), window)
        at += 24
    first = cs.auto_reshards
    assert first >= 1 and run.fail_safe_batches == 0
    # never-seen keys under one narrow prefix, 128 boundaries a batch: one
    # shard fills long before the window slides
    fresh = 0
    while run.fail_safe_batches == 0:
        v = run.version
        run.batch([(v - 120, [point(b"userzz%08d" % (fresh + j))],
                    [point(b"userzz%08d" % (fresh + j)),
                     point(b"userzy%08d" % (fresh + j))])
                   for j in range(32)], 12 * STEP)
        fresh += 32
        assert fresh < 32 * 40, "the fail-safe never engaged"
    assert res.overflow_events == 0
    for _ in range(40):  # the window slides, the fail-safe lets go
        run.batch(f_batch(rmw[at:], 24, 12 * STEP), 12 * STEP)
        at += 24
    assert cs.auto_reshards >= max(2, first + 1)
    assert run.seen[Verdict.TOO_OLD] > 0 and run.seen[Verdict.CONFLICT] > 0
    assert run.seen[Verdict.COMMITTED] > run.judged // 2
    assert res.txns_rejected_fail_safe > 0 and res.overflow_events == 0
    assert not cs.overflowed and run.judged > 1500


def test_ranges_over_two_to_four_shards_equal_the_one_history_reference():
    """Transactions whose read and write ranges each fall on two to four
    shards, over a population that moves twice (so the splits move under
    them): every verdict is one history's."""
    loop, cs, res = mesh_role(capacity=1024, seed=5)
    run = Compared(loop, res, range_judge())
    rng = np.random.default_rng(42)
    window = 30 * STEP

    def key(prefix, n):
        return prefix + b"%05d" % n

    def spanning(prefix, lo, hi):
        a = int(rng.integers(lo, hi - 600))
        return (key(prefix, a), key(prefix, a + int(rng.integers(300, 600))))

    spans = []
    for phase, prefix in enumerate((b"user", b"\x10acct", b"\xd0zone")):
        for _ in range(26):
            v = run.version
            txns = []
            for j in range(20):
                rv = v - 130 - 20 * (j % 5)
                if j % 10 == 9 and v > 2 * window:
                    rv = v - window - 500  # TOO_OLD
                reads = [spanning(prefix, 0, 4000)]
                writes = [point(key(prefix, int(rng.integers(0, 4000))))]
                if j % 4 == 0:
                    writes.append(spanning(prefix, 0, 4000))
                if j % 6 == 0:
                    # the whole keyspace: every shard, whatever the splits
                    reads.append((b"", b"\xff"))
                txns.append((rv, reads, writes))
            run.batch(txns, window)
            lo = [cs.codec.unpack(r) for r in cs._lo]
            for _rv, reads, writes in txns:
                for b, e in reads + writes:
                    spans.append(sum(1 for s, t in zip(lo, lo[1:] + [b"\xff\xff"])
                                     if b < t and s < e))
        assert cs.auto_reshards >= phase + 1, (phase, cs.shard_occupancy())
    assert max(spans) == 4 and sum(1 for n in spans if n >= 2) > 200
    assert run.fail_safe_batches == 0 and not cs.overflowed
    assert all(run.seen[v] > 0 for v in Verdict)
    assert run.judged == 3 * 26 * 20


def test_a_write_rejected_on_another_shard_is_never_painted():
    """T1 is rejected by the shard that holds its read, and its write lies
    on another shard; T2 then reads that write's key. Four role-level
    resolvers paint it (the other resolver accepted T1) and refuse T2; one
    history, and the mesh, never saw the write."""
    a, b = b"\x10a", b"\x90b"  # first-byte split: shards 0 and 2
    t0 = (5, [], [point(a)])
    t1 = (5, [point(a)], [point(b)])  # A was written at 10 > 5: CONFLICT
    t2 = (15, [point(b)], [])

    split = reference_nr.SplitResolvers(sr.interior_uniform(4))
    one = reference_ranges.RangeHistory()
    for txn, version in ((t0, 10), (t1, 20)):
        split.resolve([txn], version, 0)
        reference_ranges.range_verdicts(one, [txn], version, 0)
    assert split.resolve([t2], 30, 0) == [reference.CONFLICT]
    assert reference_ranges.range_verdicts(one, [t2], 30, 0) == [
        reference.COMMITTED]

    loop, cs, res = mesh_role()
    assert drive(loop, res, 0, 10, [info(*t0)], 0)[0] == [Verdict.COMMITTED]
    assert drive(loop, res, 10, 20, [info(*t1)], 0)[0] == [Verdict.CONFLICT]
    assert drive(loop, res, 20, 30, [info(*t2)], 0)[0] == [Verdict.COMMITTED]
    assert cs.auto_reshards == 0  # the bootstrap split throughout


def test_the_fullest_shard_bounds_the_roles_headroom():
    """Every write under one first byte, faster than the policy looks: the
    role rejects by the fail-safe before that shard overflows, while the
    mean occupancy is far under a shard's capacity."""
    loop, cs, res = mesh_role(capacity=256, max_write_ranges=2)
    prev, version, n = 0, 1000, 0
    while res.txns_rejected_fail_safe == 0:
        txns = [info(version - 50, [], [point(b"k%08d" % (n + j))])
                for j in range(32)]
        n += 32
        _v, fail_safe = drive(loop, res, prev, version, txns, 0)
        prev, version = version, version + STEP
        assert n <= 32 * 7, "the policy looked before the shard filled"
    assert fail_safe and res.overflow_events == 0 and not cs.overflowed
    occ = cs.shard_occupancy()
    assert max(occ) > 100 and sorted(occ)[:3] == [1, 1, 1]
    assert sum(occ) / 4 < cs.capacity / 4
    m = loop.run(res.get_metrics())
    # the role was served the fullest shard's reading, not the mean: its
    # rows in use and one more, its lower bound being the first row of its
    # base AND of its delta
    assert m["history_headroom"] == cs.headroom() == (
        cs.capacity - max(occ) - 1)
    assert m["history_headroom"] < cs.worst_case_growth(32)
    assert m["fail_safe_active"]


def test_the_bootstrap_split_is_left_before_the_lone_shard_fills():
    """Every key under one first byte, as after a bulk load of `"user..."`
    keys: at the first-byte split one shard takes all of it. The engine
    re-splits by itself, in time, and verdicts before, across and after
    the move are the reference's."""
    loop, cs, res = mesh_role(capacity=512, max_write_ranges=2)
    run = Compared(loop, res, point_judge())
    records = ycsb.Records(5000, seed=3)
    for b in range(30):
        v = run.version
        ids = range(b * 20, b * 20 + 20)
        # each batch writes 20 new records and re-reads 10 older ones at
        # a read version that some of their writes have passed
        txns = [(v - 150, [point(records.keys[i])],
                 [point(records.keys[i])]) for i in ids]
        txns += [(v - 250, [point(records.keys[i - 30])],
                  [point(records.keys[i - 30])])
                 for i in ids if i >= 30 and i % 2]
        if b == 7:
            assert cs.auto_reshards == 0
            assert sorted(cs.shard_occupancy())[:3] == [1, 1, 1]
        run.batch(txns, 10_000)
    occ = cs.shard_occupancy()
    assert cs.auto_reshards >= 1 and run.fail_safe_batches == 0
    assert sum(occ) > cs.capacity  # one shard could not have held it
    assert max(occ) < cs.capacity - cs.worst_case_growth(30)
    assert run.seen[Verdict.CONFLICT] > 0 and run.seen[Verdict.COMMITTED] > 0
    m = loop.run(res.get_metrics())["engine"]
    assert m["auto_reshards"] == cs.auto_reshards
    assert m["reshard_probes"] == 30 // 8 and m["reshard_probe_s"] > 0
    assert m["reshard_s"] > 0 and m["shard_rows_in_use"] == occ
    # the mesh keeps the window history a shard: the shards' merges, summed
    merges = np.asarray(cs._hist_core.merges)
    assert m["hist_merges"] == int(merges.sum()) > 0 and len(merges) == 4
    assert m["dispatches"] == 30


def reading_a_shard(cs):
    """The capacity reading's rule from the stacked leaves, in numpy: a
    shard's base as its next merge would leave it (one row where its
    versions, clamped at the floor, change) plus its delta's rows."""
    hc = cs._hist_core
    versions, floor, base_n, delta_n = (np.asarray(x) for x in (
        hc.base.versions, hc.delta.oldest, hc.base.n_used, hc.delta.n_used))
    out = []
    for d in range(cs.n_shards):
        v = np.where(versions[d] <= floor[d], versions[d].min(), versions[d])
        steps = 1 + int((v[1:] != v[:-1]).sum())
        out.append(min(int(base_n[d]), steps) + int(delta_n[d]))
    return out


def test_the_roles_headroom_and_merges_are_the_window_historys_a_shard():
    """`history_headroom` is the FULLEST shard's base as its next merge
    would leave it plus its delta's rows; `hist_merges` the shards' merges
    summed; both as of the batch the role collected last, whether it read
    them through the collector or, collected at once, through headroom()."""
    loop, cs, res = mesh_role(capacity=512, batch_size=16,
                              max_write_ranges=2)
    assert cs.delta_capacity == 66
    run = Compared(loop, res, point_judge())
    rng = np.random.default_rng(8)
    for b in range(30):
        v = run.version
        # three keys of four under the first bytes 0x40-0x7f: the splits
        # start by first byte and the policy moves them as it sees fit
        keys = [bytes([0x40 + int(rng.integers(0, 64)) if j % 4 else
                       int(rng.integers(0, 256))])
                + bytes(rng.integers(97, 123, 5).astype(np.uint8))
                for j in range(16)]
        run.batch([(v - 150, [point(k)], [point(k)]) for k in keys], 600)
        m = loop.run(res.get_metrics())
        per_shard = reading_a_shard(cs)
        assert m["history_headroom"] == cs.capacity - max(per_shard), b
        assert m["engine"]["hist_merges"] == int(
            np.asarray(cs._hist_core.merges).sum()), b
    merges = [int(x) for x in np.asarray(cs._hist_core.merges)]
    # every shard folded its delta in, by itself or for a re-split
    assert min(merges) >= 1 and run.fail_safe_batches == 0
    assert max(per_shard) > 40 and not cs.overflowed
    assert m["engine"]["dispatches"] == 30


def test_the_fail_safes_advance_merges_every_shard_and_frees_its_base():
    """The fail-safe advances in place of resolving: on the mesh that is
    ck.advance_hist a shard, a forced merge at the sliding floor. It is
    what lets the fullest shard's BASE go once the window has passed it,
    and the role out of the fail-safe."""
    loop, cs, res = mesh_role(capacity=256, max_write_ranges=2)
    cs.auto_reshard = False  # the bootstrap split stays: one shard fills
    prev, version, n = 0, 1000, 0
    while res.txns_rejected_fail_safe == 0:
        txns = [info(version - 50, [], [point(b"k%08d" % (n + j))])
                for j in range(32)]
        n += 32
        _v, fail_safe = drive(loop, res, prev, version, txns, 0)
        prev, version = version, version + STEP
        assert n <= 32 * 8
    assert fail_safe and not cs.overflowed
    before = [int(x) for x in np.asarray(cs._hist_core.merges)]
    base_rows = int(np.asarray(cs._hist_core.base.n_used)[1])
    assert base_rows > 100
    # the window slides past every write; each rejected batch advances
    advances = 0
    while fail_safe:
        txns = [info(version - 50, [], [point(b"k%08d" % (n + j))])
                for j in range(32)]
        n += 32
        _v, fail_safe = drive(loop, res, prev, version, txns, version - 200)
        prev, version = version, version + STEP
        advances += fail_safe
        assert advances < 12, "the fail-safe never let go"
    after = [int(x) for x in np.asarray(cs._hist_core.merges)]
    assert advances >= 1
    assert all(a - b >= advances for a, b in zip(after, before))
    assert int(np.asarray(cs._hist_core.base.n_used)[1]) < base_rows
    m = loop.run(res.get_metrics())
    assert m["engine"]["hist_merges"] == sum(after)
    assert m["history_headroom"] == cs.capacity - max(reading_a_shard(cs))
    assert res.overflow_events == 0 and not cs.overflowed


def test_the_policys_cost_is_a_stage_of_the_batch_that_paid_it():
    """`reshard_probe` and `reshard` are inside `device_dispatch`'s
    identity, under the version of the batch whose dispatch made them."""
    from foundationdb_tpu.obs.span import (
        ENGINE_STAGES,
        MESH_ENGINE_STAGES,
        SUB_STAGES,
        SpanSink,
    )
    from foundationdb_tpu.runtime.net import RealLoop

    assert MESH_ENGINE_STAGES == ("reshard_probe", "reshard")
    assert set(MESH_ENGINE_STAGES) <= set(SUB_STAGES)
    loop = RealLoop()
    sink = SpanSink(loop, sample_every=1)
    cs = make_conflict_set("tpu", mesh=4, capacity=512, batch_size=32,
                           max_read_ranges=4, max_write_ranges=4,
                           max_key_bytes=16)
    res = Resolver(loop, cs)

    async def main():
        prev = 0
        for b in range(16):
            v = 1000 + b * STEP
            await res.resolve(prev, v, [
                info(v - 50, [point(b"user%06d" % (b * 16 + j))],
                     [point(b"user%06d" % (b * 16 + j))])
                for j in range(16)], 0)
            prev = v

    loop.run(main(), timeout=200)
    by_version: dict = {}
    for s in sink.spans:
        if s.get("version") is not None:
            d = by_version.setdefault(s["version"], {})
            d[s["name"]] = d.get(s["name"], 0.0) + s["dur"]
    probed = [v for v, d in by_version.items() if "reshard_probe" in d]
    moved = [v for v, d in by_version.items() if "reshard" in d]
    assert probed == [1000 + 7 * STEP, 1000 + 15 * STEP]
    # keys in ascending order: what follows a re-split lands in the last
    # shard, and the next look moves the splits again
    assert moved[0] == 1000 + 7 * STEP and set(moved) <= set(probed)
    assert len(moved) == cs.auto_reshards
    for d in by_version.values():
        bracket = d["host_pack"] + d["device_dispatch"]
        parts = sum(d.get(s, 0.0)
                    for s in ENGINE_STAGES + MESH_ENGINE_STAGES) \
            + d["engine_unattributed"]
        assert abs(bracket - parts) < 1e-6


def test_the_mesh_body_names_what_it_adds():
    """`shard_clip` and `shard_psum` are named scopes of the lowered
    program, beside the kernel's own."""
    cs = make_conflict_set("tpu", mesh=4, capacity=256, batch_size=32,
                           max_read_ranges=2, max_write_ranges=2,
                           max_key_bytes=8)
    bt = cs._empty_batch()
    flat, dims = cs._flat_endpoints(bt)
    empty = cs._ranks_to_batch(bt, np.full(len(flat), sr.INT32_MAX, np.int32),
                               dims)
    text = cs._resolve_fn.lower(
        cs.state, empty, np.int32(0), np.int32(0)).as_text(debug_info=True)
    for scope in ("shard_clip", "shard_psum", "history_probe", "accept",
                  "paint_compact"):
        assert scope in text, scope


# -- a socket cluster booted from such a spec --------------------------------


def test_a_socket_cluster_with_the_key_serves_commits_on_the_mesh(tmp_path):
    """client -> GRV -> storage reads -> commit proxy -> the resolver role
    on ShardedConflictSet(n_shards=4) -> tlogs -> storages, on the CPU
    backend: loads, commits, conflicts one of a conflicting pair, reads
    every acknowledged write back from both replicas (served_phase raises
    on any of them)."""
    out = chip_smoke.served_phase(
        str(tmp_path), n_keys=1500, keys_per_txn=100, rate=50.0,
        duration_s=2.0, env={"JAX_PLATFORMS": "cpu"}, mesh=4)
    assert out["resolver"]["device"] == {
        "platform": "cpu", "device_kind": "cpu", "count": 4}
    device_line, ready_line = out["resolver_log"]
    assert device_line.startswith("device resolver0 engine=tpu platform=cpu")
    assert "count=4" in device_line
    assert out["roles_with_jax_mapped"] == ["resolver0"]
    assert out["conflicting_pair"] == ["committed", "not_committed"]
    for name in ("storage0", "storage1"):
        assert out["read_back"][name]["missing_or_wrong"] == 0
    assert out["read_back"]["acknowledged_keys"] >= 1500
    assert out["resolver"]["resolve_failures"] == 0
    assert out["resolver"]["txns_rejected_fail_safe"] == 0
    assert out["resolver"]["auto_reshards"] >= 1


def test_conflicting_keys_are_reported_as_on_one_chip():
    """A transaction that asks for its conflicting keys gets the read
    ranges that lost, exactly, from the mesh as from the one-chip engine:
    here one range of three, and it lies on another shard than the
    transaction's first range."""
    sizes = dict(capacity=256, batch_size=32, max_read_ranges=4,
                 max_write_ranges=4, max_key_bytes=16)
    mesh = make_conflict_set("tpu", mesh=4, **sizes)
    one = make_conflict_set("tpu", **sizes)
    writer = info(5, [], [point(b"\x90hot"), point(b"\x10cold")])
    asker = info(15, [point(b"\x10a"), (b"\x90h", b"\x90i"),
                      point(b"\xd0z")], [point(b"\x50w")])
    asker.report_conflicting_keys = True
    late = info(25, [point(b"\x10a"), point(b"\x90hot")], [])
    late.report_conflicting_keys = True
    got = {}
    for name, cs in (("mesh", mesh), ("one", one)):
        loop = Loop(seed=1)
        res = Resolver(loop, cs)
        loop.run(res.resolve(0, 20, [writer], oldest_version=0))
        got[name] = loop.run(
            res.resolve(20, 30, [asker, late], oldest_version=0))[:2]
    assert got["mesh"] == got["one"]
    verdicts, conflicting = got["mesh"]
    assert verdicts == [Verdict.CONFLICT, Verdict.COMMITTED]
    assert conflicting == {0: [(b"\x90h", b"\x90i")]}
