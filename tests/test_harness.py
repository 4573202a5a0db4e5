"""The tests' own harness (tests/conftest.py, loadgen/deploy.py): the clock
every test runs under, the one way to get role processes, and the ports
they are given."""

import json
import os
import re
import subprocess
import sys

import pytest

from foundationdb_tpu.loadgen.deploy import SocketCluster
from tests import conftest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CASES = os.path.join("tests", "harness_cases")


def inner_pytest(*args: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-p", "no:cacheprovider",
         "-p", "no:randomly", *args],
        cwd=REPO, capture_output=True, text=True, timeout=180)


def test_a_python_wait_past_the_limit_fails_and_the_next_test_runs():
    r = inner_pytest(os.path.join(CASES, "clock_cases.py"), "-k", "wait")
    assert r.returncode == 1, r.stdout + r.stderr
    assert "1 failed, 1 passed" in r.stdout, r.stdout
    assert re.search(r"FAILED .*test_python_wait - TimeoutError", r.stdout), \
        r.stdout
    # The soft stage's dump of every thread's stack, on the real stderr.
    assert "most recent call first" in r.stderr, r.stderr
    assert "test_python_wait" in r.stderr, r.stderr


def test_a_c_level_block_ends_its_worker_and_the_run_reaches_its_end():
    r = inner_pytest(os.path.join(CASES, "clock_cases.py"), "-k", "block",
                     "-p", "xdist", "-n", "2")
    assert r.returncode == 1, r.stdout + r.stderr
    assert "1 failed, 1 passed" in r.stdout, r.stdout
    assert re.search(r"worker 'gw\d' crashed while running "
                     r"'.*clock_cases.py::test_c_block'", r.stdout), r.stdout
    # The hard stage's dump names where the block is.
    assert "Timeout (" in r.stderr and "test_c_block" in r.stderr, r.stderr


FREE_PORTS_CHILD = """
import json, socket, sys
from foundationdb_tpu.loadgen.deploy import free_ports
ports = free_ports(50)
held = [socket.create_server(("127.0.0.1", p)) for p in ports]
print(json.dumps(ports), flush=True)
sys.stdin.read()  # hold the ports and the claims until every child has drawn
"""


def test_free_ports_gives_no_port_twice_across_processes():
    children = [
        subprocess.Popen([sys.executable, "-c", FREE_PORTS_CHILD], cwd=REPO,
                         stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                         text=True)
        for _ in range(8)]
    try:
        drawn = [json.loads(c.stdout.readline() or "[]") for c in children]
    finally:
        for c in children:
            c.communicate(timeout=30)
    assert [c.returncode for c in children] == [0] * 8
    assert [len(ports) for ports in drawn] == [50] * 8
    assert len({p for ports in drawn for p in ports}) == 400
    with open("/proc/sys/net/ipv4/ip_local_port_range") as f:
        assert max(max(ports) for ports in drawn) < int(f.read().split()[0])


def test_a_role_that_never_says_ready_fails_the_fixture_with_its_log(
        cluster_factory, monkeypatch):
    script = ("print('still warming up', flush=True); "
              "import time; time.sleep(600)")
    monkeypatch.setattr(SocketCluster, "_argv",
                        lambda self, p: [sys.executable, "-c", script])
    monkeypatch.setattr(conftest, "ROLE_READY_S", 2.0)
    with pytest.raises(RuntimeError) as e:
        cluster_factory(proxies=1, ratekeeper=False)
    assert re.search(r"(?s)timed out waiting for sequencer0 ready.*"
                     r"sequencer0\.log ends:\nstill warming up", str(e.value))


def test_a_failed_cluster_test_leaves_no_role_and_reports_their_logs():
    r = inner_pytest(os.path.join(CASES, "cluster_cases.py"))
    assert r.returncode == 1, r.stdout + r.stderr
    pids = json.loads(re.search(r"ROLE_PIDS (\[[\d, ]+\])", r.stdout).group(1))
    assert len(pids) == 5
    # Every role leads a process group of its own (its pid), zombies apart.
    assert not [pid for pid in pids if SocketCluster._pgid_running(pid)]
    for role in ("sequencer0", "resolver0", "tlog0", "storage0", "proxy0"):
        assert re.search(rf"(?s)-+ {role} log, .*{role}\.log -+\n"
                         rf".*ready {role} on 127\.0\.0\.1:\d+", r.stdout), \
            r.stdout
