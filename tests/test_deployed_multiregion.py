"""Deployed multi-region: region failover over real TCP.

The deployed counterpart of the sim's multi-region battery
(tests/test_multi_region.py; reference: DatabaseConfiguration regions +
satellite TLogs + ClusterController datacenter failover): a spec places
every chain role in one of two regions with >= 1 satellite tlog in the
synchronous push set; SIGKILL-ing the ENTIRE primary region must move
the transaction subsystem to the standby region with zero acked-commit
loss — the satellites are the salvage source — and the healed primary
must be able to take the database back symmetrically.
"""

import os
import subprocess
import sys
import time

import pytest

from foundationdb_tpu.loadgen.deploy import free_ports

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_cli(spec_path: str, cmds: str):
    return subprocess.run(
        [sys.executable, "-m", "foundationdb_tpu.cli",
         "--cluster", spec_path, "--exec", cmds],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=60,
    )


def cli_ok(spec_path: str, cmds: str, tries: int = 60):
    last = None
    for _ in range(tries):
        last = run_cli(spec_path, cmds)
        if last.returncode == 0 and "ERROR" not in last.stdout:
            return last
        time.sleep(1)
    raise AssertionError(
        f"cli never succeeded: {last.stdout!r} {last.stderr!r}")


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


def wait_status(spec: dict, pred, deadline_s: float = 120) -> dict:
    deadline = time.monotonic() + deadline_s
    last = None
    while time.monotonic() < deadline:
        try:
            last = controller_status(spec)
            if pred(last):
                return last
        except Exception:
            pass
        time.sleep(1)
    raise AssertionError(f"status predicate never held; last={last}")


PRI = {"sequencer": [0], "resolver": [0], "tlog": [0, 1], "proxy": [0],
       "storage": [0]}
REM = {"sequencer": [1], "resolver": [1], "tlog": [2, 3], "proxy": [1],
       "storage": [1]}


@pytest.fixture
def multiregion(cluster_factory):
    """Two regions (a sequencer, a resolver, two tlogs, a proxy and a
    storage each), one satellite tlog and a controller, each process with
    a data dir; yields (spec, spec path, cluster)."""
    ports = iter(free_ports(13))

    def addrs(n):
        return [f"127.0.0.1:{next(ports)}" for _ in range(n)]

    c = cluster_factory(managed=True, data_dirs=True, ratekeeper=False,
                        spec_extra={
                            "sequencer": addrs(2), "resolver": addrs(2),
                            "tlog": addrs(4), "storage": addrs(2),
                            "proxy": addrs(2), "satellite_tlog": addrs(1),
                            "regions": {"pri": PRI, "rem": REM}})
    return c.spec, c.spec_path, c


def kill_region(cluster, region: dict) -> None:
    for role, idxs in region.items():
        for i in idxs:
            cluster.kill_role(f"{role}{i}")


class TestRegionFailover:
    def test_primary_region_loss_is_lossless(self, multiregion):
        spec, spec_path, cluster = multiregion
        cli_ok(spec_path, "writemode on; set mr/a v1; set mr/b v2")
        st = controller_status(spec)
        assert st.get("active_region") == "pri"
        assert st["generation"].get("satellite_tlog") == [0]

        # The ENTIRE primary region goes dark — chain roles AND storage.
        kill_region(cluster, PRI)

        st = wait_status(
            spec, lambda s: s.get("active_region") == "rem"
            and not s["recovering"])
        assert st["generation"]["tlog"] == [2, 3]
        # Every acked commit survived (satellite salvage + remote replica)
        # and the database accepts writes in the new region.
        out = cli_ok(spec_path,
                     "writemode on; set mr/c v3; getrange mr/ mr0")
        assert all(v in out.stdout for v in ("v1", "v2", "v3")), out.stdout

    def test_failback_after_heal(self, multiregion):
        spec, spec_path, cluster = multiregion
        cli_ok(spec_path, "writemode on; set fb/a v1")
        kill_region(cluster, PRI)
        wait_status(spec, lambda s: s.get("active_region") == "rem"
                    and not s["recovering"])
        cli_ok(spec_path, "writemode on; set fb/b v2")

        # fdbmonitor restarts the primary region's processes; they rejoin
        # as standby (storage replica catches up from the rem chain).
        for role, idxs in PRI.items():
            for i in idxs:
                cluster.restart_role(f"{role}{i}")
        wait_status(
            spec, lambda s: sorted(s["generation"].get("storage", []))
            == [0, 1] and not s["recovering"])
        cli_ok(spec_path, "writemode on; set fb/c v3")

        # Now the REM region dies: the database must move back to pri —
        # including commits that only ever existed in the rem generation.
        kill_region(cluster, REM)
        wait_status(spec, lambda s: s.get("active_region") == "pri"
                    and not s["recovering"])
        out = cli_ok(spec_path,
                     "writemode on; set fb/d v4; getrange fb/ fb0")
        assert all(v in out.stdout for v in ("v1", "v2", "v3", "v4")), \
            out.stdout


def role_rpc(spec: dict, role: str, i: int, service: str, method: str,
             *rpc_args, timeout: float = 10):
    """One-shot RPC against a deployed process's named service, with full
    transport teardown (t.close() — not just the listener — so the test
    process doesn't accumulate leaked connections across calls)."""
    from foundationdb_tpu.runtime.net import NetTransport, RealLoop
    from foundationdb_tpu.server import parse_addr

    loop = RealLoop()
    t = NetTransport(loop)
    try:
        ep = t.endpoint(parse_addr(spec[role][i]), service)
        return loop.run_until(getattr(ep, method)(*rpc_args),
                              timeout=timeout)
    finally:
        t.close()


def admin_rpc(spec: dict, role: str, i: int, method: str, *rpc_args):
    return role_rpc(spec, role, i, "admin", method, *rpc_args)


def partition_primary(spec: dict, outside: list, dur: float) -> None:
    """Two-sided drop rules between every primary-region process and each
    `outside` (role, index): the pri region stays internally connected —
    alive, but dark to the rest of the cluster."""
    pri_addrs = [(role, i) for role, idxs in PRI.items() for i in idxs]
    for prole, pi in pri_addrs:
        for orole, oi in outside:
            oh, op = spec[orole][oi].rsplit(":", 1)
            admin_rpc(spec, prole, pi, "inject_fault",
                      oh, int(op), "drop", 0.05, dur)
            ph, ppt = spec[prole][pi].rsplit(":", 1)
            admin_rpc(spec, orole, oi, "inject_fault",
                      ph, int(ppt), "drop", 0.05, dur)


class TestRegionPartition:
    def test_partitioned_primary_fails_over_without_loss(self, multiregion):
        """The HARD region-failure mode: the primary region is network-
        partitioned (every process alive, internal links fine) rather
        than dead. The controller must still flip; the old generation
        must be FENCED — its proxies push synchronously to the satellite
        tlogs, which recovery locks, so nothing the partitioned side
        acks after the lock can exist (the reference's epoch fencing via
        tlog locks) — and every write the client ever got an ack for
        must read back afterwards."""
        spec, spec_path, cluster = multiregion
        cli_ok(spec_path, "writemode on; set pp/a v1; set pp/b v2")

        partition_primary(
            spec,
            [("controller", 0), ("satellite_tlog", 0)]
            + [(role, i) for role, idxs in REM.items() for i in idxs],
            dur=60.0)

        # While the partition is live, the zombie generation must mint NO
        # read versions (confirmEpochLive over TCP): proxy0's grv_proxy
        # is up and answering, but its per-batch confirm can't reach the
        # fenced satellite. First prove the zombie IS up (a dead proxy
        # would make any refusal vacuous), then demand the GRV fails —
        # as a wire-delivered FdbError (the refusal) or a timeout (batch
        # parked unconfirmable) — never with a version, and never with a
        # transport error that would mean the probe tested nothing.
        from foundationdb_tpu.core.errors import FdbError

        d = role_rpc(spec, "proxy", 0, "worker", "describe")
        assert d.get("epoch") == 1, d  # alive, still serving epoch 1
        try:
            v = role_rpc(spec, "proxy", 0, "grv_proxy", "get_read_version",
                         "default", None, timeout=5)
            raise AssertionError(f"zombie grv served read version {v}")
        except (FdbError, TimeoutError):
            pass  # refused or unconfirmable — no version minted

        st = wait_status(
            spec, lambda s: s.get("active_region") == "rem"
            and not s["recovering"], deadline_s=90)
        assert st["generation"]["tlog"] == [2, 3]

        # Client writes land in the new region; every prior ack reads.
        out = cli_ok(spec_path,
                     "writemode on; set pp/c v3; getrange pp/ pp0")
        assert all(v in out.stdout for v in ("v1", "v2", "v3")), out.stdout

        # Faults expire; the partitioned region's processes rejoin as
        # standby (its chain roles answer with a retired epoch, its
        # storage folds back into the generation) and acked data is
        # still all there.
        wait_status(
            spec, lambda s: sorted(s["generation"].get("storage", []))
            == [0, 1] and not s["recovering"], deadline_s=120)
        out = cli_ok(spec_path,
                     "writemode on; set pp/d v4; getrange pp/ pp0")
        assert all(v in out.stdout
                   for v in ("v1", "v2", "v3", "v4")), out.stdout


class TestNoFlipWithoutSalvage:
    def test_partition_plus_dead_satellite_stays_put(self, multiregion):
        """Double fault over real TCP: the primary region partitions AND
        the satellite dies. Nothing in the old push set is lockable, so
        the controller must NOT move the database (a flip without
        salvage forks the timeline and loses acked commits) — it has to
        wait. When the partition expires it locks the primary's own
        tlogs and heals IN region; the restarted satellite folds back
        into a later generation; every ack survives."""
        spec, spec_path, cluster = multiregion
        cli_ok(spec_path, "writemode on; set nf/a v1; set nf/b v2")
        st = controller_status(spec)
        assert st.get("active_region") == "pri"

        cluster.kill_role("satellite_tlog0")
        partition_primary(
            spec,
            [("controller", 0)]
            + [(role, i) for role, idxs in REM.items() for i in idxs],
            dur=45.0)

        # Ample time to (wrongly) flip: the active region must not move
        # — there is nothing to salvage from. Transient status timeouts
        # (the controller is mid-retry against black-holed links) just
        # continue the poll; only an OBSERVED flip fails.
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            try:
                st = controller_status(spec)
            except Exception:
                time.sleep(3)
                continue
            assert st.get("active_region") == "pri", st
            time.sleep(3)

        # Partition expires: the controller heals IN region from the
        # primary's own tlogs; the relaunched satellite rejoins.
        cluster.restart_role("satellite_tlog0")
        wait_status(
            spec, lambda s: s.get("active_region") == "pri"
            and not s["recovering"]
            and s["generation"].get("satellite_tlog") == [0]
            and s["epoch"] > 1, deadline_s=120)
        out = cli_ok(spec_path,
                     "writemode on; set nf/c v3; getrange nf/ nf0")
        assert all(v in out.stdout for v in ("v1", "v2", "v3")), out.stdout


class TestRegionSpecValidation:
    def base(self) -> dict:
        return {
            "controller": ["h:1"],
            "sequencer": ["h:2", "h:3"],
            "resolver": ["h:4", "h:5"],
            "tlog": ["h:6", "h:7", "h:8", "h:9"],
            "storage": ["h:10", "h:11"],
            "proxy": ["h:12", "h:13"],
            "satellite_tlog": ["h:14"],
            "regions": {"pri": dict(PRI), "rem": dict(REM)},
        }

    def check(self, spec) -> None:
        from foundationdb_tpu.server import _validate_regions

        _validate_regions(spec)

    def test_valid_spec_passes(self):
        self.check(self.base())

    def test_requires_satellites(self):
        spec = self.base()
        spec["satellite_tlog"] = []
        with pytest.raises(ValueError, match="satellite"):
            self.check(spec)

    def test_requires_controller(self):
        spec = self.base()
        spec["controller"] = []
        with pytest.raises(ValueError, match="managed"):
            self.check(spec)

    def test_indices_must_partition(self):
        spec = self.base()
        spec["regions"]["rem"] = dict(spec["regions"]["rem"], tlog=[2])
        with pytest.raises(ValueError, match="partition"):
            self.check(spec)

    def test_equal_storage_counts(self):
        spec = self.base()
        spec["storage"] = ["h:10", "h:11", "h:15"]
        spec["regions"]["rem"] = dict(
            spec["regions"]["rem"], storage=[1, 2])
        with pytest.raises(ValueError, match="EQUAL storage"):
            self.check(spec)
