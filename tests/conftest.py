"""Test config: run JAX on a virtual 8-device CPU mesh.

Must set the env vars before jax is imported anywhere (mirrors the driver's
dryrun harness, which uses xla_force_host_platform_device_count to validate
multi-chip sharding without real chips).
"""

import os

# Tests validate semantics and multi-device sharding on a virtual 8-device
# CPU mesh, whatever the session's environment says about a chip.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# A pytest plugin may have imported jax before this file ran, freezing its
# snapshot of the environment: set the platform in the config as well.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from foundationdb_tpu.utils import enable_compilation_cache  # noqa: E402

enable_compilation_cache()  # cuts repeat suite runs by minutes

import faulthandler  # noqa: E402
import signal  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# The one clock on every test, set-up and teardown included. Under the
# driver's six-worker command on an 8-core host the slowest healthy test
# takes 65 s (test_deployed_multiregion.py's
# test_partitioned_primary_fails_over_without_loss; PR 28's run, 234 s in
# all), and the limit is over three times that, so no healthy test meets
# it. A test that truly needs another value says so itself:
# @pytest.mark.time_limit(seconds).
TIME_LIMIT_S = 240.0
# After the limit a signal raises TimeoutError in the test (which ends
# socket waits, readline, loop.run). This much later the worker process is
# ended, for a block in C that no signal handler reaches: xdist reports
# that test failed, starts another worker, and the run reaches its end.
HARD_AFTER_S = 20.0
ROLE_READY_S = 90.0  # a role process: launch to its `ready` line

_stderr = None  # the worker's real stderr, not pytest's capture of it
_live_clusters: list = []  # started by cluster_factory, not yet torn down


def pytest_configure(config):
    global _stderr
    config.addinivalue_line(
        "markers", "slow: left out of tier-1 (-m 'not slow')")
    config.addinivalue_line(
        "markers", "time_limit(seconds): this test's own clock, in place "
        f"of the {TIME_LIMIT_S:.0f} s every test gets")
    # Capture is suspended while plugins are configured: fd 2 is the real
    # stderr here, and a dup of it stays so when a test's capture starts.
    _stderr = os.fdopen(os.dup(2), "w")
    signal.signal(signal.SIGALRM, _on_alarm)


def pytest_unconfigure(config):
    global _stderr
    if _stderr is not None:
        _stderr.close()
        _stderr = None


def _on_alarm(_signum, _frame):
    faulthandler.dump_traceback(file=_stderr, all_threads=True)
    raise TimeoutError("the test passed its time limit (tests/conftest.py)")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item):
    mark = item.get_closest_marker("time_limit")
    limit = float(mark.args[0]) if mark else TIME_LIMIT_S
    signal.setitimer(signal.ITIMER_REAL, limit)
    faulthandler.dump_traceback_later(limit + HARD_AFTER_S, exit=True,
                                      file=_stderr)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    report = (yield).get_result()
    if report.failed:
        for cluster in _live_clusters:
            for p in cluster.procs:
                report.sections.append(
                    (f"{p.name} log, {p.log_path}", cluster.log_tail(p.name)))


def _cluster_factory(tmp_path_factory):
    """THE way a test gets role processes: make(**kw) is a started
    loadgen.deploy.SocketCluster(**kw) in a tmp dir of its own. Ports come
    from deploy.free_ports, each role's output goes to a log file there,
    `ready` has a deadline and fails with the log's end, a failing test's
    report carries every role's, and teardown kills every process group
    and fails the test on anything left. make(start=False, ...) launches
    nothing: the test boots roles one by one (restart_role, wait_ready)."""
    from foundationdb_tpu.loadgen.deploy import SocketCluster

    made = []

    def make(start: bool = True, **kw) -> SocketCluster:
        cluster = SocketCluster(str(tmp_path_factory.mktemp("cluster")), **kw)
        cluster.BOOT_DEADLINE_S = cluster.READY_DEADLINE_S = ROLE_READY_S
        made.append(cluster)
        _live_clusters.append(cluster)
        return cluster.start() if start else cluster

    yield make
    leaks = []
    for cluster in made:
        _live_clusters.remove(cluster)
        report = cluster.kill()
        if report["ports_still_bound"] or report["orphan_groups"]:
            leaks.append(report)
    assert not leaks, f"role processes outlived their test: {leaks}"


@pytest.fixture
def cluster_factory(tmp_path_factory):
    yield from _cluster_factory(tmp_path_factory)


@pytest.fixture(scope="module")
def module_cluster_factory(tmp_path_factory):
    yield from _cluster_factory(tmp_path_factory)


@pytest.fixture
def rng():
    return np.random.default_rng(0)
