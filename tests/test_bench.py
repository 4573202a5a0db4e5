"""bench.py unit coverage: the vectorized wire-stream builder must equal
encode_resolve_batch byte-for-byte, and a small stream must produce
identical verdicts on kernel / C++ / oracle — the same three-way parity
the ConflictRange workload asserts in the reference's simulation suite
(fdbserver/workloads/ConflictRange.actor.cpp)."""

import pytest

import bench
from foundationdb_tpu.core.types import KeyRange, TxnConflictInfo
from foundationdb_tpu.models.conflict_set import encode_resolve_batch
from foundationdb_tpu.sim.oracle import OracleConflictSet


def key_bytes(i: int) -> bytes:
    return int(i).to_bytes(8, "big")


def _stream_txns(n_batches):
    n = n_batches * bench.BATCH
    return bench.gen_workload(n, 512, seed=7)


def _object_txns(read_ids, write_ids, write_mask, lag, b, batch=None):
    """The object-path equivalent of wire batch b (for oracle/encode)."""
    batch = batch or bench.BATCH
    cv = b + 1
    txns = []
    for i in range(b * batch, (b + 1) * batch):
        rv = max(0, cv - 1 - int(lag[i]))
        reads = [KeyRange(key_bytes(k), key_bytes(k) + b"\x00")
                 for k in read_ids[i]]
        writes = ([KeyRange(key_bytes(k), key_bytes(k) + b"\x00")
                   for k in write_ids[i]]
                  if write_mask[i] else [])
        txns.append(TxnConflictInfo(rv, reads, writes))
    return txns


def test_wire_stream_matches_encode():
    n_batches = 1
    read_ids, write_ids, write_mask, lag = _stream_txns(n_batches)
    blob, ends = bench.build_wire_stream(
        read_ids, write_ids, write_mask, lag, n_batches
    )
    txns = _object_txns(read_ids, write_ids, write_mask, lag, 0)
    expect = encode_resolve_batch(txns)
    got = blob[int(ends[0]) : int(ends[bench.BATCH])].tobytes()
    assert got == expect


def test_bench_stream_three_way_parity():
    n_batches = 2
    read_ids, write_ids, write_mask, lag = _stream_txns(n_batches)

    # Production wire path, exactly as bench drives it.
    blob, ends = bench.build_wire_stream(
        read_ids, write_ids, write_mask, lag, n_batches
    )
    _, tpu_conf, overflowed, tpu_lat, _occ, _x = bench.run_tpu_wire(
        n_batches, 1 << 14, blob, ends, repeats=1
    )
    assert not overflowed

    # C++ path, exactly as bench drives it.
    cpu_batches = bench.marshal_cpu_batches(
        n_batches, read_ids, write_ids, write_mask, lag
    )
    _, cpu_conf, _cpu_lat, _v = bench.run_cpu(cpu_batches)

    # Oracle on the same stream.
    oracle = OracleConflictSet()
    oracle_conf = 0
    for b in range(n_batches):
        cv = b + 1
        txns = _object_txns(read_ids, write_ids, write_mask, lag, b)
        got = oracle.resolve(txns, cv, max(0, cv - bench.WINDOW))
        oracle_conf += sum(1 for v in got if v.name == "CONFLICT")

    assert tpu_conf == cpu_conf == oracle_conf


def test_mode_streams_three_way_parity():
    """Every bench mode's wire stream must match encode_resolve_batch and
    produce kernel/C++/oracle-identical verdicts (mako + tpcc shapes)."""
    for mode_name in ("mako", "tpcc"):
        mode = bench.MODES[mode_name]
        n_batches = 1
        n = n_batches * mode.batch
        read_ids, write_ids, write_mask, lag = bench.gen_workload(
            n, 256, seed=13, mode=mode
        )
        blob, ends = bench.build_wire_stream(
            read_ids, write_ids, write_mask, lag, n_batches, mode
        )
        txns = _object_txns(read_ids, write_ids, write_mask, lag, 0,
                            batch=mode.batch)
        assert blob[: int(ends[mode.batch])].tobytes() == \
            encode_resolve_batch(txns), mode_name

        _, tpu_conf, overflow, _lat, _occ, _x = bench.run_tpu_wire(
            n_batches, 1 << 14, blob, ends, repeats=1, mode=mode
        )
        assert not overflow
        cpu_batches = bench.marshal_cpu_batches(
            n_batches, read_ids, write_ids, write_mask, lag, mode
        )
        _, cpu_conf, _cpu_lat, _v = bench.run_cpu(cpu_batches, mode)
        oracle = OracleConflictSet()
        got = oracle.resolve(txns, 1, 0)
        oracle_conf = sum(1 for v in got if v.name == "CONFLICT")
        assert tpu_conf == cpu_conf == oracle_conf, mode_name


def test_sharded_resolver_mode_parity():
    """--resolvers N (mesh-sharded) must produce the same verdicts as the
    single-shard engine on the same stream."""
    mode = bench.MODES["ycsb"]
    n_batches = 2
    n = n_batches * mode.batch
    read_ids, write_ids, write_mask, lag = bench.gen_workload(
        n, 512, seed=17, mode=mode
    )
    blob, ends = bench.build_wire_stream(
        read_ids, write_ids, write_mask, lag, n_batches, mode
    )
    _, conf1, _, _l1, _o1, _x1 = bench.run_tpu_wire(
        n_batches, 1 << 14, blob, ends, repeats=1, mode=mode, n_resolvers=1
    )
    _, conf4, _, _l4, occ4, _x4 = bench.run_tpu_wire(
        n_batches, 1 << 14, blob, ends, repeats=1, mode=mode, n_resolvers=4
    )
    assert conf1 == conf4
    assert len(occ4) == 4  # sharded run reports occupancy


def test_adaptive_dispatch_parity_and_record_shape():
    """run_tpu_adaptive (sched subsystem) must produce the same verdicts
    as the fixed windowed path on the same stream, and its record must
    carry the scheduler telemetry sched_ab.sh extracts."""
    mode = bench.MODES["ycsb"]
    n_batches = 4
    n = n_batches * mode.batch
    read_ids, write_ids, write_mask, lag = bench.gen_workload(
        n, 512, seed=31, mode=mode
    )
    blob, ends = bench.build_wire_stream(
        read_ids, write_ids, write_mask, lag, n_batches, mode
    )
    _, fixed_conf, _, _lat, _occ, _x = bench.run_tpu_wire(
        n_batches, 1 << 14, blob, ends, repeats=1, mode=mode, window=2
    )
    rec = bench.run_tpu_adaptive(
        n_batches, 1 << 14, blob, ends, mode=mode,
        offered_tps=None,  # all-available: pure dispatch pipeline
        budget_ms=1000.0, max_window=2, threaded=True,
    )
    assert rec["conflicts"] == fixed_conf
    assert rec["txns"] == n
    assert rec["kept_up"] is True
    assert rec["windows"] == sum(rec["depth_hist"].values())
    assert rec["p99_ms"] > 0 and rec["value"] > 0
    assert rec["double_buffered"] is True


def test_latency_and_roofline_fields():
    """run_tpu_wire/run_cpu report per-dispatch latencies and
    roofline_estimate yields finite, positive bounds for every mode."""
    mode = bench.MODES["ycsb"]
    n_batches = 2
    n = n_batches * mode.batch
    read_ids, write_ids, write_mask, lag = bench.gen_workload(
        n, 512, seed=23, mode=mode
    )
    blob, ends = bench.build_wire_stream(
        read_ids, write_ids, write_mask, lag, n_batches, mode
    )
    _, _, _, lat, _occ, _x = bench.run_tpu_wire(
        n_batches, 1 << 14, blob, ends, repeats=1, mode=mode, window=1
    )
    assert len(lat) == n_batches and all(v > 0 for v in lat)
    cpu_batches = bench.marshal_cpu_batches(
        n_batches, read_ids, write_ids, write_mask, lag, mode
    )
    _, _, cpu_lat, _v = bench.run_cpu(cpu_batches, mode)
    assert len(cpu_lat) == n_batches and all(v > 0 for v in cpu_lat)
    for m in bench.MODES.values():
        r = bench.roofline_estimate(m, 1 << 18, bench.V5E_DEVICE_KIND)
        assert r["bound"] in ("vpu", "mxu", "hbm")
        assert r["projected_peak_txns_per_sec"] > 0
        assert all(r[k] > 0 for k in
                   ("int_ops_per_batch", "bytes_per_batch"))
        # Acceptance is pure VPU bitwise: no MXU flops in the model.
        assert r["mxu_flops_per_batch"] == 0
        with pytest.raises(ValueError, match="no published peaks"):
            bench.roofline_estimate(m, 1 << 18, "cpu")
