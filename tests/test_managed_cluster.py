"""Managed deployed cluster: controller-driven recruitment over real TCP.

VERDICT r3 item 6's done-criterion: boot a cluster whose spec names a
controller, kill -9 a chain role (tlog, then sequencer), and observe the
cluster heal with a generation change — acked data intact, commits
resuming — without a full bounce. The restarted process is folded back in
(full tlog replication restored), which is what fdbmonitor's restart-on-exit
produces in production (reference: fdbserver workers re-recruited by
ClusterController.actor.cpp after reboot).
"""

import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_cli(spec_path: str, cmds: str):
    return subprocess.run(
        [sys.executable, "-m", "foundationdb_tpu.cli",
         "--cluster", spec_path, "--exec", cmds],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=60,
    )


@pytest.fixture
def managed(cluster_factory):
    """A controller, 1 sequencer, 1 resolver, 2 tlogs, 2 storages, 2
    proxies, each with a data dir; yields (spec, spec path, cluster)."""
    c = cluster_factory(tlogs=2, storages=2, ratekeeper=False, managed=True,
                        data_dirs=True)
    return c.spec, c.spec_path, c


def controller_status(spec: dict) -> dict:
    from foundationdb_tpu.runtime.net import NetTransport, RealLoop
    from foundationdb_tpu.server import parse_addr

    loop = RealLoop()
    t = NetTransport(loop)
    try:
        ep = t.endpoint(parse_addr(spec["controller"][0]), "controller")
        return loop.run_until(ep.get_status(), timeout=10)
    finally:
        t._listener.close()


def cli_ok(spec_path: str, cmds: str, tries: int = 45):
    last = None
    for _ in range(tries):
        last = run_cli(spec_path, cmds)
        if last.returncode == 0 and "ERROR" not in last.stdout:
            return last
        time.sleep(1)
    raise AssertionError(
        f"cli never succeeded: {last.stdout!r} {last.stderr!r}")


class TestManagedHealing:
    def test_tlog_kill_heals_without_bounce(self, managed):
        spec, spec_path, cluster = managed
        cli_ok(spec_path, "writemode on; set mg/a v1; set mg/b v2")

        # kill -9 one tlog: the controller must form a new generation on
        # the survivors; commits resume; acked data still reads.
        cluster.kill_role("tlog1")
        out = cli_ok(spec_path, "writemode on; set mg/c v3; getrange mg/ mg0")
        assert "v1" in out.stdout and "v2" in out.stdout and "v3" in out.stdout

        # Restart the killed tlog (what fdbmonitor does): the controller
        # folds it back in with another generation change; writes continue.
        cluster.restart_role("tlog1")
        deadline = time.monotonic() + 90
        rejoined = False
        while time.monotonic() < deadline and not rejoined:
            try:
                st = controller_status(spec)
                rejoined = st["generation"].get("tlog") == [0, 1] \
                    and not st["recovering"]
            except Exception:
                pass
            if not rejoined:
                time.sleep(1)
        assert rejoined, "tlog1 never folded back into the generation"
        out = cli_ok(spec_path, "writemode on; set mg/d v4; getrange mg/ mg0")
        assert all(v in out.stdout for v in ("v1", "v2", "v3", "v4"))

    def test_all_tlogs_killed_recovers_from_disk(self, managed):
        """Both tlogs die at once (rack loss): no live chain to lock, so
        the controller must fall back to the durable disk-resume path once
        the restarted workers all report fresh — not spin forever."""
        spec, spec_path, cluster = managed
        cli_ok(spec_path, "writemode on; set rk/a v1; set rk/b v2")
        time.sleep(1)
        for i in (0, 1):
            cluster.kill_role(f"tlog{i}")
        for i in (0, 1):
            cluster.restart_role(f"tlog{i}")
        out = cli_ok(spec_path, "getrange rk/ rk0", tries=90)
        assert "v1" in out.stdout and "v2" in out.stdout, out.stdout
        cli_ok(spec_path, "writemode on; set rk/c v3; get rk/c")

    def test_full_bounce_durable_restart(self, managed):
        """Managed durable restart: kill EVERY process, reboot the same
        spec + data dirs — the controller's bootstrap resumes the tlog
        chains from disk (truncating the unacked suffix) and acked data
        reads back in a new epoch."""
        spec, spec_path, cluster = managed
        cli_ok(spec_path, "writemode on; set fb/a v1; set fb/b v2")
        time.sleep(2)  # let pulls/flushes settle a beat
        for p in cluster.procs:
            cluster.kill_role(p.name)
        cluster.start()
        out = cli_ok(spec_path, "getrange fb/ fb0")
        assert "v1" in out.stdout and "v2" in out.stdout
        cli_ok(spec_path, "writemode on; set fb/c v3; get fb/c")
        st = controller_status(spec)
        assert st["epoch"] >= 2  # durable restart started a new generation

    def test_sequencer_kill_heals_after_restart(self, managed):
        spec, spec_path, cluster = managed
        cli_ok(spec_path, "writemode on; set sq/a v1")

        cluster.kill_role("sequencer0")
        time.sleep(2)  # let the failure be observed
        # There is exactly one sequencer process in the spec; recovery
        # waits for its restart (fdbmonitor's job — emulated here).
        cluster.restart_role("sequencer0")

        out = cli_ok(spec_path, "writemode on; set sq/b v2; getrange sq/ sq0")
        assert "v1" in out.stdout and "v2" in out.stdout

    def test_db_flags_survive_heal(self, managed):
        """Advisor finding: a heal during DR must keep dual-tagging on,
        and a locked database must stay locked through recruitment —
        recruit_proxy with defaults silently dropped both (stream gap /
        stale-client commits after switchover)."""
        spec, spec_path, cluster = managed

        def proxy_rpc(method, *args):
            from foundationdb_tpu.runtime.net import NetTransport, RealLoop
            from foundationdb_tpu.server import parse_addr

            loop = RealLoop()
            t = NetTransport(loop)
            try:
                return [
                    loop.run_until(
                        getattr(t.endpoint(parse_addr(a), "commit_proxy"),
                                method)(*args), timeout=10)
                    for a in spec["proxy"]
                ]
            finally:
                t._listener.close()

        cli_ok(spec_path, "writemode on; set fl/a v1")
        proxy_rpc("set_backup_enabled", True)
        proxy_rpc("set_locked", True)
        time.sleep(3)  # > one heartbeat: the controller sweep caches flags

        epoch0 = controller_status(spec)["epoch"]
        cluster.kill_role("tlog1")
        deadline = time.monotonic() + 90
        healed = False
        while time.monotonic() < deadline and not healed:
            try:
                st = controller_status(spec)
                healed = st["epoch"] > epoch0 and not st["recovering"]
            except Exception:
                pass
            if not healed:
                time.sleep(1)
        assert healed, "cluster never healed after tlog kill"

        # The NEW generation's proxies carry both flags.
        assert all(proxy_rpc("get_backup_enabled"))
        assert all(proxy_rpc("get_locked"))
        st = controller_status(spec)
        assert st["backup_active"] and st["db_locked"]

    def test_operator_cli_commands(self, managed):
        """fdbcli-analogue operator surface over a managed cluster:
        lock/unlock (1038 at the proxies), exclude/include of a chain
        process (generation membership via the controller), configure
        (chain-role counts), coordinators."""
        spec, spec_path, cluster = managed
        cli_ok(spec_path, "writemode on; set op/a v1")

        # lock: non-lock-aware writes fail; unlock: they work again.
        out = run_cli(spec_path, "lock")
        assert "Locked" in out.stdout, out.stdout
        out = run_cli(spec_path, "writemode on; set op/b v2")
        assert "1038" in out.stdout or "locked" in out.stdout.lower()
        out = run_cli(spec_path, "unlock")
        assert "Unlocked" in out.stdout
        cli_ok(spec_path, "writemode on; set op/b v2; get op/b")

        # exclude tlog1: the generation re-forms without it.
        out = cli_ok(spec_path, "exclude tlog1")
        assert "tlog1" in out.stdout
        deadline = time.monotonic() + 90
        ok = False
        while time.monotonic() < deadline and not ok:
            try:
                st = controller_status(spec)
                ok = (st["generation"].get("tlog") == [0]
                      and not st["recovering"]
                      and "tlog1" in st["excluded"])
            except Exception:
                pass
            if not ok:
                time.sleep(1)
        assert ok, "tlog1 never left the generation"
        cli_ok(spec_path, "writemode on; set op/c v3; get op/c")

        # include: it folds back in.
        cli_ok(spec_path, "include tlog1")
        deadline = time.monotonic() + 90
        ok = False
        while time.monotonic() < deadline and not ok:
            try:
                st = controller_status(spec)
                ok = (st["generation"].get("tlog") == [0, 1]
                      and not st["recovering"])
            except Exception:
                pass
            if not ok:
                time.sleep(1)
        assert ok, "tlog1 never rejoined after include"

        # configure proxies=1: next generation uses one commit proxy.
        out = cli_ok(spec_path, "configure proxies=1")
        assert "proxy" in out.stdout
        deadline = time.monotonic() + 90
        ok = False
        while time.monotonic() < deadline and not ok:
            try:
                st = controller_status(spec)
                ok = (st["generation"].get("proxy") == [0]
                      and not st["recovering"])
            except Exception:
                pass
            if not ok:
                time.sleep(1)
        assert ok, "proxy count never reconfigured"
        cli_ok(spec_path, "writemode on; set op/d v4; get op/d")

        # storage exclusion is refused (needs DD drain).
        out = run_cli(spec_path, "exclude storage0")
        assert "ERROR" in out.stdout

        out = run_cli(spec_path, "coordinators")
        assert spec["controller"][0] in out.stdout

    def test_consistencycheck_cli(self, managed):
        """`cli consistencycheck` against a deployed cluster: walks every
        shard team at one snapshot version through each storage's own
        serve path and reports a consistent JSON verdict."""
        import json as _json

        spec, spec_path, cluster = managed
        cli_ok(spec_path, "writemode on; set ck/a v1; set ck/b v2; set ck/c v3")
        out = cli_ok(spec_path, "consistencycheck")
        rep = _json.loads(out.stdout)
        assert rep["status"] == "consistent"
        assert rep["divergences"] == []
        assert rep["shards_checked"] == len(spec["storage"])
        assert rep["rows_compared"] > 0


def admin_rpc(spec: dict, role: str, i: int, method: str, *rpc_args):
    from foundationdb_tpu.runtime.net import NetTransport, RealLoop
    from foundationdb_tpu.server import parse_addr

    loop = RealLoop()
    t = NetTransport(loop)
    try:
        ep = t.endpoint(parse_addr(spec[role][i]), "admin")
        return loop.run_until(getattr(ep, method)(*rpc_args), timeout=10)
    finally:
        t._listener.close()


class TestDeployedChaos:
    """Network-level fault injection over REAL TCP (VERDICT r4 item 8):
    the sim campaign partitions and clogs freely; the deployed path
    customers run must survive the same abuse. Faults are installed via
    the admin service's inject_fault RPC (runtime/net.py set_fault)."""

    def test_partition_controller_tlog_during_heal(self, managed):
        """Kill one tlog AND black-hole the controller's link to the
        surviving tlog: recovery cannot lock the chain until the fault
        expires — it must stall (not corrupt), then complete, with a
        client writing throughout and no acked write lost."""
        spec, spec_path, cluster = managed
        cli_ok(spec_path, "writemode on; set ch/a v1")

        host, port = spec["tlog"][0].rsplit(":", 1)
        out = admin_rpc(spec, "controller", 0, "inject_fault",
                        host, int(port), "drop", 0.05, 8.0)
        assert "drop" in out
        cluster.kill_role("tlog1")

        # Writes keep retrying through the stalled heal and land once the
        # fault expires and recovery completes.
        out = cli_ok(spec_path,
                     "writemode on; set ch/b v2; getrange ch/ ch0",
                     tries=90)
        assert "v1" in out.stdout and "v2" in out.stdout
        st = controller_status(spec)
        assert st["recoveries_completed"] >= 1

    def test_kill_sequencer_mid_recruitment(self, managed):
        """Kill a tlog to start a heal, then kill the sequencer WHILE the
        controller is recruiting: recovery must retry until fdbmonitor
        (the test) brings the sequencer back, and every acked write
        survives the double failure."""
        spec, spec_path, cluster = managed
        cli_ok(spec_path, "writemode on; set sk/a v1; set sk/b v2")

        cluster.kill_role("tlog1")
        time.sleep(1.5)  # sweep notices; recovery begins
        cluster.kill_role("sequencer0")
        time.sleep(2)
        cluster.restart_role("sequencer0")

        out = cli_ok(spec_path,
                     "writemode on; set sk/c v3; getrange sk/ sk0",
                     tries=90)
        assert all(v in out.stdout for v in ("v1", "v2", "v3"))

    def test_clogged_link_commits_still_flow(self, managed):
        """Delay-mode fault: a slow-but-alive proxy→tlog link (the hard
        case — no failure detector trips). Commits must still complete,
        just slower."""
        spec, spec_path, cluster = managed
        cli_ok(spec_path, "writemode on; set cl/a v1")
        host, port = spec["tlog"][0].rsplit(":", 1)
        for p in range(len(spec["proxy"])):
            admin_rpc(spec, "proxy", p, "inject_fault",
                      host, int(port), "delay", 0.2, 6.0)
        out = cli_ok(spec_path,
                     "writemode on; set cl/b v2; getrange cl/ cl0",
                     tries=60)
        assert "v1" in out.stdout and "v2" in out.stdout

    def test_heal_with_replicated_storage(self, cluster_factory):
        """Managed recruitment composes with `replicas: 2`: a tlog kill
        heals with a generation change, and a storage replica death
        afterwards costs availability nothing (team failover) — the
        recruitment path is replication-agnostic and this proves it."""
        cluster = cluster_factory(
            tlogs=2, storages=2, ratekeeper=False, managed=True,
            data_dirs=True, spec_extra={"replicas": 2})
        spec_path = cluster.spec_path
        cli_ok(spec_path, "writemode on; set hr/a v1; set hr/b v2")
        time.sleep(1.0)  # replicas pull their tag streams

        # Replica parity on the deployed plane: consistencycheck walks
        # both members of every 2-replica team via their own serve
        # paths (scanner waits out pull lag rather than flagging it).
        out = cli_ok(spec_path, "consistencycheck")
        assert '"status": "consistent"' in out.stdout, out.stdout
        assert '"replicas_compared": 4' in out.stdout, out.stdout

        # Chain-role heal under replication.
        cluster.kill_role("tlog1")
        out = cli_ok(spec_path,
                     "writemode on; set hr/c v3; getrange hr/ hr0",
                     tries=90)
        assert all(v in out.stdout for v in ("v1", "v2", "v3"))

        # Now a storage replica dies: reads AND writes keep working.
        cluster.kill_role("storage1")
        out = cli_ok(spec_path,
                     "writemode on; set hr/d v4; getrange hr/ hr0",
                     tries=90)
        assert all(v in out.stdout for v in ("v1", "v2", "v3", "v4"))
