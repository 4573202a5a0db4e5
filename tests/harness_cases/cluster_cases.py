"""A test that fails with its cluster up, for tests/test_harness.py to run
in a pytest of its own."""


def test_fails_with_its_cluster_up(cluster_factory):
    cluster = cluster_factory(proxies=1, ratekeeper=False)
    pids = [p.popen.pid for p in cluster.procs]
    assert not pids, f"ROLE_PIDS {pids}"
