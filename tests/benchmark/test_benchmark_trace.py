"""The reduction from a profiler trace to busy time, time by operation and
device time per batch (benchmark/lib/trace_reduce.py, benchmark/readers/),
against a small trace recorded on the chip in PR 23 and kept as a fixture."""

import json
import os

import pytest

from benchmark.lib import trace_reduce
from benchmark.readers import device_per_batch

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmark", "fixtures")


def fixture(name: str):
    with open(os.path.join(FIXTURES, name)) as f:
        return json.load(f)


def test_busy_time_is_the_union_of_the_intervals():
    seconds, merged = trace_reduce.union_seconds(
        [(0, 10), (5, 20), (30, 40), (40, 45), (100, 101)])
    assert merged == [[0, 20], [30, 45], [100, 101]]
    assert seconds == pytest.approx(36e-9)


def test_a_hand_made_trace_reduces_to_what_it_holds():
    ms = 1_000_000
    planes = [
        ["/device:TPU:0", [
            ["XLA Modules", [["jit__resolve_res_jit(7)", 0, 4 * ms],
                             ["jit__resolve_res_jit(7)", 10 * ms, 4 * ms]]],
            ["XLA Ops", [["fusion.1", 0, 3 * ms], ["sort.2", 2 * ms, 2 * ms],
                         ["fusion.1", 10 * ms, 4 * ms]]]]],
        ["/host:CPU", [["python3", [
            ["PjitFunction(_resolve_res_jit)", 4 * ms, 5 * ms],
            ["outer", 0, 20 * ms]]]]],
    ]
    out = trace_reduce.reduce_planes(planes, window_s=0.02)
    assert out["stand_in"] is False
    assert out["busy_s"] == pytest.approx(0.008)  # [0,4] and [10,14] ms
    assert out["window_s"] == 0.02
    assert out["modules"] == {"jit__resolve_res_jit": [2, pytest.approx(0.008)]}
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(0.007)]
    # the one idle gap, 4..10 ms, by the innermost host event at its middle
    assert out["idle_gaps"] == [
        ["PjitFunction(_resolve_res_jit)", pytest.approx(0.006)]]
    result = {"sources": {"trace": out}}
    assert device_per_batch.read({"module": "resolve"}, result) == \
        pytest.approx(4.0)
    with pytest.raises(RuntimeError, match="no program matching"):
        device_per_batch.read({"module": "nothing_like_it"}, result)


def test_a_trace_with_no_device_plane_falls_back_to_the_cpu_stand_in():
    planes = [["/host:CPU", [
        ["tf_XLAPjRtCpuClient/1", [["dot.1", 100, 50], ["marker", 120, 0]]],
        ["python3", [["PjitFunction(f)", 90, 80]]]]]]
    out = trace_reduce.reduce_planes(planes, window_s=1e-6)
    assert out["stand_in"] is True and out["busy_s"] == pytest.approx(50e-9)
    assert out["modules"] == {"f": [1, pytest.approx(80e-9)]}
    # never a CPU number under a device metric's name
    assert device_per_batch.read(
        {"module": "f"}, {"sources": {"trace": out}}) is None


@pytest.mark.parametrize("name,window_s", [
    ("trace_ycsb_f_closed.json", 3.0), ("trace_resolver_share_f.json", 3.0)])
def test_the_trace_recorded_on_the_chip_reduces(name, window_s):
    out = trace_reduce.reduce_planes(fixture(name), window_s)
    assert out["stand_in"] is False
    assert out["device_planes"] == ["/device:TPU:0"]
    assert 0 < out["busy_s"] <= out["window_s"]
    assert any("resolve" in m for m in out["modules"])
    assert out["device_ops"] and out["idle_gaps"]
    per_batch = device_per_batch.read(
        {"module": "resolve"}, {"sources": {"trace": out}})
    assert per_batch > 0
