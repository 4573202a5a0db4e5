"""Deployable cluster e2e: OS-process roles over real TCP + cli + C client.

The VERDICT r2 "ship a deployable cluster" milestone: boots the
fdbserver-analogue (`python -m foundationdb_tpu.server`) as separate OS
processes per role, then drives it three ways — the Python client library,
the cli (fdbcli analogue), and the native C client (netclient.cpp) — all
against the same running cluster. Reference shape:
fdbserver/fdbserver.actor.cpp + fdbcli/fdbcli.actor.cpp.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def cluster(module_cluster_factory):
    """1 sequencer, 1 resolver, 2 tlogs, 2 storages, 2 proxies, a
    ratekeeper — each an OS process; yields the spec path."""
    return module_cluster_factory(tlogs=2, storages=2).spec_path


def run_cli(spec_path: str, cmds: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-m", "foundationdb_tpu.cli",
         "--cluster", spec_path, "--exec", cmds],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=60,
    )


class TestDeployedCluster:
    def test_python_client_commit_read(self, cluster):
        """The client library commits and reads against OS-process roles."""
        from foundationdb_tpu.cli import open_cluster

        loop, t, db = open_cluster(cluster)
        try:
            async def main():
                tr = db.transaction()
                tr.set(b"deploy/k1", b"v1")
                tr.set(b"\x90spans-shard2", b"v2")  # second storage shard
                await tr.commit()
                tr2 = db.transaction()
                assert await tr2.get(b"deploy/k1") == b"v1"
                assert await tr2.get(b"\x90spans-shard2") == b"v2"
                rows = await tr2.get_range(b"deploy/", b"deploy0")
                assert (b"deploy/k1", b"v1") in rows
                return "ok"

            assert loop.run(main(), timeout=60) == "ok"
        finally:
            t.close()

    def test_conflict_detected_across_processes(self, cluster):
        from foundationdb_tpu.cli import open_cluster
        from foundationdb_tpu.core.errors import NotCommitted

        loop, t, db = open_cluster(cluster)
        try:
            async def main():
                tr1 = db.transaction()
                tr2 = db.transaction()
                await tr1.get(b"conf/k")
                await tr2.get(b"conf/k")
                tr1.set(b"conf/k", b"a")
                tr2.set(b"conf/k", b"b")
                await tr1.commit()
                with pytest.raises(NotCommitted):
                    await tr2.commit()
                return "ok"

            assert loop.run(main(), timeout=60) == "ok"
        finally:
            t.close()

    def test_cli_roundtrip_and_writemode(self, cluster):
        r = run_cli(cluster, "set nope x")
        assert "writemode must be enabled" in r.stdout and r.returncode == 1
        r = run_cli(
            cluster,
            "writemode on; set cli/key cli-val; get cli/key; "
            "getrange cli/ cli0; clear cli/key; get cli/key",
        )
        assert r.returncode == 0, r.stdout + r.stderr
        assert "`cli/key' is `cli-val'" in r.stdout
        assert "not found" in r.stdout  # after the clear

    def test_cli_throttle_tag(self, cluster):
        """fdbcli-style manual tag throttling against the deployed
        ratekeeper role."""
        r = run_cli(cluster, "throttle tag batchjobs 25")
        assert r.returncode == 0 and "Throttled" in r.stdout, r.stdout
        r = run_cli(cluster, "status")
        status = json.loads(r.stdout)
        assert status["roles"]["ratekeeper0"]["tag_rates"] == \
            {"batchjobs": 25.0}
        r = run_cli(cluster, "unthrottle tag batchjobs")
        assert "Unthrottled" in r.stdout
        r = run_cli(cluster, "status")
        assert json.loads(r.stdout)["roles"]["ratekeeper0"]["tag_rates"] == {}

    def test_cli_status(self, cluster):
        r = run_cli(cluster, "status")
        assert r.returncode == 0, r.stdout + r.stderr
        status = json.loads(r.stdout)
        roles = status["roles"]
        for want in ("sequencer0", "proxy0", "proxy1", "tlog0", "tlog1",
                     "storage0", "storage1", "resolver0"):
            assert want in roles, sorted(roles)
            assert "unreachable" not in str(roles[want]), roles[want]

    def test_c_client_against_deployed_cluster(self, cluster):
        """The native C client commits through a proxy process's gateway
        surface (grv_proxy + commit_proxy + read router) — the VERDICT r2
        'C client commits against it' criterion."""
        from foundationdb_tpu.client.net_client import NetClient
        from foundationdb_tpu.core.errors import FdbError
        from foundationdb_tpu.core.mutations import Mutation, MutationType as M
        from foundationdb_tpu.core.types import single_key_range

        spec = json.loads(open(cluster).read())
        host, port = spec["proxy"][0].rsplit(":", 1)
        c = NetClient(host, int(port))
        try:
            rv = c.get_read_version()
            cv = c.commit(
                rv,
                [Mutation(M.SET_VALUE, b"c/deployed", b"yes")],
                write_ranges=[single_key_range(b"c/deployed")],
            )
            assert cv > rv
            rv2 = c.get_read_version()
            assert c.get(b"c/deployed", rv2) == b"yes"
            # Keys on the second shard route through the read router too.
            cv2 = c.commit(
                rv2,
                [Mutation(M.SET_VALUE, b"\xa0far-shard", b"routed")],
                write_ranges=[single_key_range(b"\xa0far-shard")],
            )
            rv3 = c.get_read_version()
            assert rv3 >= cv2
            assert c.get(b"\xa0far-shard", rv3) == b"routed"
            # Conflict check needs a snapshot older than an interfering
            # write but inside the ~5s MVCC window — take it fresh here
            # (the earlier `rv` can be past the window by now: the version
            # clock runs on wall time).
            rv4 = c.get_read_version()
            c.commit(
                rv4,
                [Mutation(M.SET_VALUE, b"c/deployed", b"interferer")],
                write_ranges=[single_key_range(b"c/deployed")],
            )
            with pytest.raises(FdbError) as ei:
                c.commit(
                    rv4,
                    [Mutation(M.SET_VALUE, b"c/deployed", b"no")],
                    read_ranges=[single_key_range(b"c/deployed")],
                    write_ranges=[single_key_range(b"c/deployed")],
                )
            assert ei.value.code == 1020

            # Range read through the C wire client (read-router fanout,
            # cross-shard, limit + reverse).
            rv5 = c.get_read_version()
            c.commit(rv5, [
                Mutation(M.SET_VALUE, b"cr/%02d" % i, b"v%02d" % i)
                for i in range(5)
            ], write_ranges=[single_key_range(b"cr/%02d" % i)
                             for i in range(5)])
            rv6 = c.get_read_version()
            rows = c.get_range(b"cr/", b"cr0", rv6)
            assert rows == [(b"cr/%02d" % i, b"v%02d" % i)
                            for i in range(5)]
            assert c.get_range(b"cr/", b"cr0", rv6, limit=2) == rows[:2]
            assert c.get_range(b"cr/", b"cr0", rv6, reverse=True)[0] == rows[-1]
        finally:
            c.close()


class TestBackupTool:
    def test_snapshot_describe_restore(self, cluster, tmp_path):
        """fdbbackup-analogue cycle against the deployed cluster: write →
        snapshot → wipe → restore → data back."""
        out = run_cli(cluster, "writemode on; set bt/1 v1; set bt/2 v2")
        assert out.returncode == 0, out.stderr
        bk = str(tmp_path / "b.fdbk")

        def tool(*args):
            return subprocess.run(
                [sys.executable, "-m", "foundationdb_tpu.backup_tool", *args],
                cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                capture_output=True, text=True, timeout=120,
            )

        r = tool("snapshot", "--cluster", cluster, "--out", bk,
                 "--begin", "bt/", "--end", "bt0", "--chunk", "1")
        assert r.returncode == 0 and "snapshot complete" in r.stdout, r.stderr
        assert "rows=2" in tool("describe", "--in", bk).stdout

        assert run_cli(cluster, "writemode on; clearrange bt/ bt0").returncode == 0
        desc = tool("describe", "--in", bk).stdout
        rv = int(desc.split("restorable_version=")[1].split()[0])
        # Point-in-time flag (fdbrestore --version analogue).
        r = tool("restore", "--cluster", cluster, "--in", bk,
                 "--version", str(rv))
        assert r.returncode == 0 and f"restored to version {rv}" in r.stdout, \
            r.stdout + r.stderr
        out = run_cli(cluster, "getrange bt/ bt0")
        assert "v1" in out.stdout and "v2" in out.stdout


class TestAdminKill:
    def test_cli_kill_stops_process(self, cluster_factory):
        """fdbcli `kill` analogue: the admin shutdown RPC exits the target
        process cleanly (its supervisor decides on restart)."""
        c = cluster_factory(start=False, proxies=1, ratekeeper=False)
        c.restart_role("sequencer0")  # the only role launched
        out = subprocess.run(
            [sys.executable, "-m", "foundationdb_tpu.cli",
             "--cluster", c.spec_path, "--exec", "kill sequencer0"],
            cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=60,
        )
        assert "shutting down" in out.stdout, out.stdout + out.stderr
        assert c.proc("sequencer0").popen.wait(timeout=15) == 0  # clean exit


class TestDurableDeployedRestart:
    def test_full_bounce_preserves_acked_data(self, cluster_factory):
        """Deployed durable restart: write to a --data-dir cluster, kill
        every process, reboot the same spec+data — acked commits read
        back and new commits land (tlog from_disk + the sequencer's
        begin_epoch chain jump)."""
        c = cluster_factory(tlogs=2, storages=2, data_dirs=True)

        def cli_ok(cmds, tries=30):
            for _ in range(tries):
                r = run_cli(c.spec_path, cmds)
                if r.returncode == 0 and "ERROR" not in r.stdout:
                    return r
                time.sleep(1)
            raise AssertionError(f"cli never succeeded: {r.stdout} {r.stderr}")

        cli_ok("writemode on; set dur/a v1; set dur/b v2")
        # Let tlog fsync/acks settle (acks are pre-reply, but give the
        # pull/flush loops a beat so sqlite holds a prefix too).
        time.sleep(2)
        for p in c.procs:
            c.kill_role(p.name)

        c.start()
        out = cli_ok("getrange dur/ dur0")
        assert "v1" in out.stdout and "v2" in out.stdout, out.stdout
        cli_ok("writemode on; set dur/c v3; get dur/c")
        out = cli_ok("getrange dur/ dur0")
        assert "v3" in out.stdout

    def test_mixed_tlog_state_refuses_boot(self, cluster_factory):
        """One tlog's disk queue lost while others recovered data: the
        sequencer must refuse to start (the fresh-chain fallback would
        false-ack new pushes on the recovered tlogs — silent data loss)
        rather than boot at version 0."""
        c = cluster_factory(proxies=1, tlogs=2, storages=2, ratekeeper=False,
                            data_dirs=True)
        r = run_cli(c.spec_path, "writemode on; set mx/a v1")
        assert r.returncode == 0 and "ERROR" not in r.stdout, r.stdout
        time.sleep(1)
        for p in c.procs:
            c.kill_role(p.name)

        # Blank one tlog's recovered state, reboot tlogs + the sequencer.
        q = os.path.join(c.proc("tlog1").data_dir, "tlog1.q")
        assert os.path.exists(q)
        os.unlink(q)
        c.restart_role("tlog0")
        c.restart_role("tlog1")
        c.restart_role("sequencer0", wait=False)
        seq = c.proc("sequencer0")
        seq.popen.wait(timeout=120)
        out = c.log_tail("sequencer0")
        assert seq.popen.returncode != 0, out
        assert "mixed tlog recovery state" in out, out


class TestDeployedReplication:
    """`replicas: 2` in the spec (reference: DatabaseConfiguration
    replication): proxies tag every team member, each replica serves only
    its team's shards, and reads survive a dead replica via client/router
    team failover — a deployed storage death no longer takes its shard
    offline."""

    def test_reads_survive_replica_kill_and_catchup(self, cluster_factory):
        c = cluster_factory(tlogs=2, storages=2, ratekeeper=False,
                            data_dirs=True, spec_extra={"replicas": 2})
        spec_path = c.spec_path

        r = run_cli(spec_path,
                    "writemode on; set rp/a v1; set rp/b v2; "
                    "getrange rp/ rp0")
        assert "v1" in r.stdout and "v2" in r.stdout, r.stdout
        time.sleep(1.0)  # let replicas pull their tag streams

        # Kill ONE replica: every key still reads (team failover) and
        # writes continue (the dead tag just queues at the tlogs).
        c.kill_role("storage1")
        ok = None
        for _ in range(30):
            ok = run_cli(spec_path,
                         "writemode on; set rp/c v3; getrange rp/ rp0")
            if ok.returncode == 0 and all(
                    v in ok.stdout for v in ("v1", "v2", "v3")):
                break
            time.sleep(1)
        assert ok and all(v in ok.stdout for v in ("v1", "v2", "v3")), (
            ok.stdout if ok else "never succeeded")

        # Restart it: the tlog held its tag stream; it catches up.
        c.restart_role("storage1")
        time.sleep(2.0)

        # Now kill the OTHER replica: only the restarted one serves —
        # proof it caught up on writes made while it was dead.
        c.kill_role("storage0")
        ok = None
        for _ in range(30):
            ok = run_cli(spec_path, "getrange rp/ rp0")
            if ok.returncode == 0 and all(
                    v in ok.stdout for v in ("v1", "v2", "v3")):
                break
            time.sleep(1)
        assert ok and all(v in ok.stdout for v in ("v1", "v2", "v3")), (
            ok.stdout if ok else "never succeeded")
