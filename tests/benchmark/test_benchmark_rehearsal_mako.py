"""The mako cell end to end on the CPU backend, at a tiny size: a copied
checkout (rehearsal.py), the tiny configuration and cell ADDED to it as new
files and entries, run traced and untraced, the last line held to the
contract. And the runs that must not give a result: one verdict altered
where it arrives, and a program whose resolver counts no wide transaction."""

import json
import os
import shutil
import time
import types

import pytest

from benchmark.lib import contract
from tests.benchmark import rehearsal
from tests.benchmark.test_benchmark_rehearsal import SEED, last_line, run_cell

CELL = "tiny_mako_g8ui"
# The configuration's own rule at a rate the CPU backend keeps up with:
# 40 x 512 boundaries -> 1<<15, 100 x 512 keys -> 1<<16.
TINY = {"nominal_rate_per_s": 512, "rows": 4096,
        "engine": {"capacity": 1 << 15, "dict_capacity": 1 << 16,
                   "batch_size": 64, "max_read_ranges": 8,
                   "max_write_ranges": 8, "max_key_bytes": 32}}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = rehearsal.build(str(tmp_path_factory.mktemp("mako") / "root"))
    b = os.path.join(root, "benchmark")
    rehearsal._derive(os.path.join(b, "configs", "mako_resolver_share.json"),
                      os.path.join(b, "configs", "tiny_mako.json"), TINY)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["configs"].append(dict(
        next(c for c in bm["configs"] if c["name"] == "mako_resolver_share"),
        name="tiny_mako", file="benchmark/configs/tiny_mako.json"))
    bm["workloads"].append(dict(
        next(w for w in bm["workloads"] if w["name"] == "mako_share_g8ui"),
        name=CELL, config="tiny_mako", traffic="tiny_depth"))
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "mako_share_g8ui" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    contract.validate_benchmark(bm, root)
    yield root
    shutil.rmtree(os.path.join(root, ".bench_work"), ignore_errors=True)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_and_its_last_line_meets_the_contract(checkout, trace):
    r = run_cell(checkout, CELL, trace)
    line = last_line(r)
    bm = contract.load_benchmark(checkout)
    contract.validate_last_line(line, bm, CELL, bool(trace), cpu_allowed=True)
    assert line["correct"] is True and line["attempted"] > 0
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    assert os.listdir(os.path.join(checkout, ".bench_work")) == []
    assert "check verdicts_wrong: 0 (limit 0) ok" in r.stdout
    generator = json.loads(next(
        ln for ln in r.stdout.splitlines() if ln.startswith("generator ")
    )[len("generator "):])
    # Set-up went past one MVCC window, and the batches were built ahead
    # of their turn (all of them on the chip; here, beside five other test
    # workers, the builder may lose a turn now and then).
    assert generator["prefill_batches"] >= generator[
        "prefill_batches_one_window"] == 40
    assert generator["built_late"] * 10 <= generator["batches"]
    assert generator["since_boot"]["wide_txns"] * 11 == generator[
        "since_boot"]["ranges_received"]
    if trace:
        metrics = line["metrics"]
        per_layer = contract.declared_metrics(bm, CELL)[1]
        assert {m["name"] for m in per_layer
                if m["source"] != "device_trace"} <= set(metrics)
        assert metrics["ranges_per_txn.mako"]["value"] == 11.0
        assert metrics["rows_per_txn.mako"]["value"] == 2.0


def _ctx(checkout: str, name: str):
    with open(os.path.join(checkout, "benchmark/configs/tiny_mako.json")) as f:
        config = json.load(f)
    with open(os.path.join(checkout, "benchmark/traffic/tiny_depth.json")) as f:
        traffic = json.load(f)
    workdir = os.path.join(checkout, ".bench_work", name)
    os.makedirs(workdir)
    return types.SimpleNamespace(
        root=checkout, t0=time.perf_counter(), workload=CELL, config=config,
        config_path=os.path.join(checkout,
                                 "benchmark/configs/tiny_mako.json"),
        traffic=traffic, seed=int(SEED), seconds=1.0, trace=False,
        workdir=workdir, control=None, fixture=None, log=lambda _m: None)


@pytest.mark.time_limit(400)
def test_a_verdict_altered_where_it_arrives_comes_out_not_correct(
        checkout, monkeypatch):
    import benchmark.run as bench_run
    from benchmark.drivers import resolver_replay_mako

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       rehearsal.environment()["JAX_COMPILATION_CACHE_DIR"])
    real_pump = resolver_replay_mako.pump

    async def broken_pump(*args, **kwargs):
        rows = await real_pump(*args, **kwargs)
        rows[len(rows) // 2][3][5] ^= 1
        return rows

    monkeypatch.setattr(resolver_replay_mako, "pump", broken_pump)
    ctx = _ctx(checkout, "broken")
    try:
        result = resolver_replay_mako.run(ctx)
    finally:
        shutil.rmtree(ctx.workdir, ignore_errors=True)
    assert ("verdicts_wrong", 1, 0) in result["checks"]
    assert bench_run.judge(result["checks"]) is False


def test_a_program_that_counts_no_wide_transaction_is_refused_at_once(
        checkout, monkeypatch):
    """The parent of this driver's PR: its resolver's `get_metrics()` has
    no `wide_txns` (nor `rows_dispatched`, which `rows_per_txn.mako`
    reads). The run ends by itself, at once, with no process started."""
    from benchmark.drivers import resolver_replay_mako
    from foundationdb_tpu.runtime.flow import Loop
    from foundationdb_tpu.runtime.resolver import Resolver

    assert resolver_replay_mako.counts_wide_txns(Loop(seed=1))
    real = Resolver.get_metrics

    async def the_parents(self):
        m = await real(self)
        return {k: v for k, v in m.items()
                if k not in ("wide_txns", "rows_dispatched")}

    monkeypatch.setattr(Resolver, "get_metrics", the_parents)
    assert not resolver_replay_mako.counts_wide_txns(Loop(seed=1))
    ctx = _ctx(checkout, "refused")
    t0 = time.perf_counter()
    try:
        with pytest.raises(RuntimeError, match="wide_txns"):
            resolver_replay_mako.run(ctx)
        assert os.listdir(ctx.workdir) == []
    finally:
        shutil.rmtree(ctx.workdir, ignore_errors=True)
    assert time.perf_counter() - t0 < 5.0


def test_keys_are_makos(checkout):
    from benchmark.drivers.resolver_replay_mako import Stream, key_of

    cfg = {"rows": 1_000_000, "keylen": 32}
    assert key_of(cfg, 42) == b"mako0000042" + b"x" * 21
    assert len(key_of(cfg, 999_999)) == len(key_of(cfg, 1_000_000)) == 32
    with open(os.path.join(checkout, "benchmark/configs/tiny_mako.json")) as f:
        config = json.load(f)
    with open(os.path.join(checkout, "benchmark/traffic/tiny_depth.json")) as f:
        traffic = json.load(f)
    a, b, c = (Stream(config, traffic, s, 4) for s in (5, 5, 2 ** 31 + 6))
    assert a.batch_pairs(1) == b.batch_pairs(1) != c.batch_pairs(1)
    inserts = set()
    for n in range(6):  # the picks wrap after 4 batches, the INSERTs never
        for rv, reads, writes in a.batch_pairs(n):
            assert rv == a.read_version(n)
            assert len(reads) == 9 and len(writes) == 2
            assert writes[0] == reads[8] and all(
                e == k + b"\x00" and len(k) == 32 for k, e in reads + writes)
            inserts.add(writes[1])
    assert len(inserts) == 6 * 64
    assert min(inserts) > (key_of(config, config["rows"] - 1),)
    assert [(t.read_version, [(r.begin, r.end) for r in t.read_ranges],
             [(w.begin, w.end) for w in t.write_ranges])
            for t in a.batch_txns(2)] == a.batch_pairs(2)
