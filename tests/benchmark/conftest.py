"""The contract tests (test_benchmark_contract.py) build their good line
around ONE device; a cell that asks for four chips has a good line with
four. The file is the accepted benchmark's and is not edited by the PR that
adds the first four-chip cell, so the count is set here, per test, from the
cell the test is about."""

import pytest

from benchmark.lib import contract


@pytest.fixture(autouse=True)
def _device_count_of_the_cell(request, monkeypatch):
    callspec = getattr(request.node, "callspec", None)
    if callspec is None or "cell" not in callspec.params \
            or not hasattr(request.module, "DEVICE"):
        return
    cell = contract.find(request.module.BM["workloads"],
                         callspec.params["cell"], "workload")
    monkeypatch.setitem(request.module.DEVICE, "count", cell["chips"])
