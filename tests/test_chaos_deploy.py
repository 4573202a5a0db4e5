"""Deployed-cluster chaos (ISSUE 14): real-process fault injection over
real TCP, acked-durability verification, crash-aware leak checking, and
the real-process torn-tail salvage contract.

The sim campaigns (tests/specs/campaigns/) prove behavior under
deterministic virtual faults; this file proves the SAME invariants when
an OS process actually dies: SIGKILL mid-push, restart from the on-disk
queue, black-holed links through the interposing relay — with the
acked-commit ledger read back exactly afterwards.
"""

import json
import os
import shlex
import signal
import socket
import sys
import time

import pytest

from foundationdb_tpu.loadgen.deploy import SocketCluster

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- satellite: crash-aware leak checking ------------------------------------


class TestCrashedProcessLeakCheck:
    """Regression (ISSUE 14 satellite): the leak check must count
    orphaned children and still-bound ports after a CRASHED (non-
    graceful) process — the old check only ran inside a clean shutdown()
    and could never see what a dead role left behind."""

    def test_crashed_role_port_check_not_vacuous(self, tmp_path):
        cluster = SocketCluster(str(tmp_path), proxies=1, ratekeeper=False)
        cluster.start()
        holder = None
        try:
            cluster.kill_role("storage0")
            rep = cluster.leak_report()
            # The crashed role IS in the checked set (not vacuously
            # skipped), and a clean crash leaves nothing behind.
            assert "storage0" in rep["checked"]
            assert rep["ports_still_bound"] == []

            # Simulate an orphan still holding the crashed role's port:
            # the check must flag it and shutdown must refuse to report
            # a clean teardown.
            addr = cluster.proc("storage0").addr
            holder = socket.create_server(addr)
            rep = cluster.leak_report()
            assert [p["port"] for p in rep["ports_still_bound"]] == [addr[1]]
            with pytest.raises(RuntimeError, match="leaked"):
                cluster.shutdown()
        finally:
            if holder is not None:
                holder.close()
            cluster.kill()

    def test_orphaned_child_of_crashed_role_detected_and_reaped(
            self, tmp_path):
        """A role that forked a child and then crashed: the child lives
        on in the role's process group — invisible to any port check.
        leak_report must flag it; kill() must reap the whole group."""

        class OrphaningCluster(SocketCluster):
            def _argv(self, p):
                argv = super()._argv(p)
                # `exec` keeps the server as the group leader pid the
                # supervisor tracks; `sleep` plays the forked child a
                # real crash leaves behind.
                return ["/bin/sh", "-c",
                        "sleep 300 & exec " + shlex.join(argv)]

        cluster = OrphaningCluster(str(tmp_path), proxies=1,
                                   ratekeeper=False)
        cluster.start()
        try:
            pgid = cluster.proc("proxy0").popen.pid
            cluster.kill_role("proxy0")  # kills the ROLE, not its group
            rep = cluster.leak_report()
            assert "proxy0" in rep["orphan_groups"], rep

            # Restarting the role must NOT lose the dead generation's
            # group: the orphan lives in the OLD pgid, the new process
            # in a fresh one — the leak check chases both (review find).
            cluster.restart_role("proxy0")
            assert cluster.proc("proxy0").alive()
            rep = cluster.leak_report()
            assert "proxy0" in rep["orphan_groups"], rep
            with pytest.raises(RuntimeError, match="leaked"):
                cluster.shutdown()
        finally:
            cluster.kill()
        # The hard teardown killed the orphan group: no RUNNING member
        # remains (on a container without a reaping init the killed
        # child may linger as a zombie — that is a process-table entry,
        # not a leak, and is exactly what _group_has_running ignores).
        from foundationdb_tpu.loadgen.deploy import _group_has_running

        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if not _group_has_running(pgid):
                break
            time.sleep(0.05)
        else:
            raise AssertionError("orphan process group survived kill()")


class TestLateListener:
    """A statically wired cluster has no recovery, so a commit batch that
    fails at boot because a chain peer does not listen yet would leave a
    gap in the version chain for ever: every role `ready`, no commit ever
    acknowledged (the one-in-twenty wedge of six test workers booting
    clusters at once, PR 28). The proxies wait for their links instead."""

    @pytest.mark.parametrize("late", ["tlog1", "resolver0"])
    def test_commits_flow_once_a_late_chain_role_listens(
            self, cluster_factory, late):
        cluster = cluster_factory(start=False, tlogs=2, storages=2)
        for p in cluster.procs:
            if p.name != late:
                cluster.restart_role(p.name, wait=False)
        for p in cluster.procs:
            if p.name != late:
                cluster.wait_ready(p.name)
        # Longer than the proxies' idle-batch interval plus their whole
        # retry ladder: on the parent a batch has failed by now.
        time.sleep(3.0)
        cluster.restart_role(late)

        loop, t, db = cluster.open_client()
        try:
            async def main():
                tr = db.transaction()
                tr.set(b"late/k", b"v")
                await tr.commit()
                return await db.transaction().get(b"late/k")

            assert loop.run(main(), timeout=30) == b"v"
        finally:
            t.close()


class TestBootFailureCleanup:
    """start() (and thus `with SocketCluster(...)`) must not leak the
    already-launched processes or relay listeners when a later role
    fails to boot: __exit__ never runs when __enter__ raises, so start()
    itself owns the mop-up (review finding)."""

    def test_boot_failure_reaps_launched_processes(self, tmp_path):
        cluster = SocketCluster(str(tmp_path), proxies=1, ratekeeper=False)
        launched = []

        def failing_wait(name, timeout_s=None):
            launched.extend(
                p.popen for p in cluster.procs if p.popen is not None)
            raise RuntimeError("injected boot failure")

        cluster.wait_ready = failing_wait
        with pytest.raises(RuntimeError, match="injected boot failure"):
            cluster.start()
        assert launched, "no process was launched before the failure"
        assert cluster.procs == []  # table cleared by the mop-up kill()
        assert all(pp.poll() is not None for pp in launched), (
            "boot failure leaked launched role processes")


# -- satellite: client transport-error mapping --------------------------------


class TestClientReconnectHardening:
    """A deployed client whose proxy connection dies pre-ack must see a
    RETRYABLE error — commit_unknown_result on the commit path (the
    batch may be durable), process-killed elsewhere — never a bare
    non-retryable transport error."""

    def _db(self, loop, addr):
        from foundationdb_tpu.client.transaction import Database, Transaction
        from foundationdb_tpu.runtime.net import NetTransport
        from foundationdb_tpu.runtime.shardmap import KeyShardMap

        t = NetTransport(loop)
        db = Database(
            loop,
            [t.endpoint(addr, "grv_proxy")],
            [t.endpoint(addr, "commit_proxy")],
            KeyShardMap.uniform(1),
            [t.endpoint(addr, "storage")],
        )
        db.transaction_class = Transaction
        return t, db

    def test_dead_proxy_maps_to_retryable(self):
        from foundationdb_tpu.core.errors import (
            CommitUnknownResult,
            ProcessKilled,
        )
        from foundationdb_tpu.runtime.net import RealLoop

        s = socket.create_server(("127.0.0.1", 0))
        dead = s.getsockname()
        s.close()  # nothing listens here: every dial dies pre-ack

        loop = RealLoop()
        t, db = self._db(loop, dead)

        async def main():
            tr = db.transaction()
            try:
                await tr.get_read_version()
                raise AssertionError("dead grv proxy answered")
            except ProcessKilled as e:
                assert e.retryable
            tr2 = db.transaction()
            tr2.set_read_version(100)
            tr2.set(b"k", b"v")
            try:
                await tr2.commit()
                raise AssertionError("dead commit proxy answered")
            except CommitUnknownResult as e:
                # Pre-ack connection death: the commit MAY be durable —
                # unknown-result, retryable, never a bare 1100/1500.
                assert e.retryable
            return "ok"

        try:
            assert loop.run(main(), timeout=60) == "ok"
        finally:
            t.close()


# -- satellite: real-process torn-tail salvage --------------------------------


def _newest_queue(data_dir: str, index: int) -> str:
    import re

    best, best_epoch = os.path.join(data_dir, f"tlog{index}.q"), 1
    for name in os.listdir(data_dir):
        m = re.fullmatch(rf"tlog{index}\.e(\d+)\.q", name)
        if m and int(m.group(1)) >= best_epoch:
            best, best_epoch = os.path.join(data_dir, name), int(m.group(1))
    return best


class TestRealTornTailSalvage:
    """Promotes the sim-only DiskQueue contract (test_durability.py) to a
    real-process test: SIGKILL both tlog processes mid-push under load,
    corrupt their disk-queue tails the way a torn write would, restart
    them from disk — the DiskQueue must truncate the torn record, the
    controller's disk-resume recovery must truncate the unacked suffix,
    and every ACKED key must read back."""

    def test_sigkill_tlogs_mid_push_salvages_acked(self, tmp_path):
        from foundationdb_tpu.core.errors import (
            CommitUnknownResult,
            FdbError,
        )
        from foundationdb_tpu.runtime.diskqueue import _parse_records

        cluster = SocketCluster(str(tmp_path), proxies=1, tlogs=2,
                                ratekeeper=False, managed=True,
                                data_dirs=True)
        cluster.start()
        try:
            loop, t, db = cluster.open_client()
            from foundationdb_tpu.client.transaction import Transaction

            db.transaction_class = Transaction
            acked: list[int] = []

            async def put(i: int) -> None:
                # Unique key + value: a CommitUnknownResult retry is
                # idempotent, so the writer resubmits until it holds a
                # REAL ack for every key it counts.
                deadline = loop.now + 60.0
                while True:
                    tr = db.transaction()
                    try:
                        tr.set(b"tt/%04d" % i, b"v%04d" % i)
                        await tr.commit()
                        acked.append(i)
                        return
                    except CommitUnknownResult:
                        pass  # resubmit: idempotent blind write
                    except FdbError as e:
                        if not e.retryable or loop.now > deadline:
                            raise
                        try:
                            await db.refresh_client_info()
                        except Exception:
                            pass
                    await loop.sleep(0.2)

            inflight: list = []

            async def phase1():
                for i in range(10):
                    await put(i)
                # Launch more commits, then SIGKILL both tlogs while
                # they are IN FLIGHT — the kill lands mid-push/mid-
                # fsync. The tasks stay parked (retrying) until the
                # restart below brings the chain back from disk.
                for i in range(10, 16):
                    inflight.append(
                        loop.spawn(put(i), name=f"tt.put{i}"))
                await loop.sleep(0.05)
                cluster.kill_role("tlog0")
                cluster.kill_role("tlog1")
                return "ok"

            assert loop.run(phase1(), timeout=300) == "ok"

            # Both tlogs are dead. Tear their disk-queue tails the way a
            # crash mid-append would (truncated header + garbage), then
            # restart from disk.
            torn = []
            for idx in (0, 1):
                q = _newest_queue(
                    os.path.join(str(tmp_path), "data", f"tlog{idx}"), idx)
                assert os.path.exists(q), q
                with open(q, "ab") as f:
                    f.write(b"\x40\x00\x00\x00\xde\xad\xbe")
                torn.append(q)
            for idx in (0, 1):
                cluster.restart_role(f"tlog{idx}")

            async def phase2():
                for task in inflight:  # mid-kill commits settle first
                    try:
                        await task
                    except Exception:
                        pass  # an exhausted retry budget is acceptable;
                        # what matters is ACKED entries reading back
                await put(99)  # proves the chain accepts commits again
                tr = db.transaction()
                rows = await tr.get_range(b"tt/", b"tt0", snapshot=True)
                return dict(rows)

            got = loop.run(phase2(), timeout=300)
            for i in acked:
                assert got.get(b"tt/%04d" % i) == b"v%04d" % i, (
                    f"ACKED key tt/{i:04d} lost across SIGKILL+restart")

            # The torn tails were truncated: every byte of the (possibly
            # since-appended) queue files parses as intact records — if
            # the garbage had survived, appends would sit unreachable
            # behind it and the parse would stop short.
            time.sleep(0.5)
            for q in torn:
                # The restarted tlog may have resumed THIS file or begun
                # an e{N} successor; the truncation contract applies to
                # whichever file recovery read.
                data = open(q, "rb").read()
                _records, good_end = _parse_records(data)
                assert good_end == len(data), (
                    f"{q}: {len(data) - good_end} bytes of torn tail "
                    "survived recovery")
            t.close()
        finally:
            cluster.kill()


# -- the deployed chaos battery (mini, fast-battery sized) --------------------


class TestDeployedChaosMini:
    """One seeded chaos cycle against a live open-loop workload: a tlog
    SIGKILL + restart and a relay black-hole partition + heal, gated on
    the exact ledger (zero acked loss, exactly-once), consistency, and
    a matched MTTR entry. The full 4-role-class battery is
    scripts/chaos_run.sh (CHAOS.json)."""

    def test_chaos_cycle_exact_ledger(self, tmp_path):
        from foundationdb_tpu.loadgen.chaos import ChaosEvent, run_chaos

        script = [
            ChaosEvent(1.5, "kill", "tlog0"),
            ChaosEvent(4.0, "restart", "tlog0"),
            ChaosEvent(7.0, "partition", "tlog1", mode="drop"),
            ChaosEvent(10.5, "heal", "tlog1"),
        ]
        ring_path = str(tmp_path / "flight_ring.jsonl")
        rec = run_chaos(seed=11, rate=40.0, workdir=str(tmp_path),
                        script=script, duration_s=13.0, drain_s=15.0,
                        recorder_path=ring_path)
        assert rec["ok"], rec["problems"]
        self._check_flight_ring(rec, ring_path)
        led = rec["ledger"]
        assert led["acked"] > 50
        assert led["acked_lost_count"] == 0
        assert led["exactly_once_ok"]
        assert led["nonretryable_errors"] == []
        assert (led["unknown_committed"] + led["unknown_absent"]
                == led["unknown"])
        assert rec["consistency"]["status"] == "consistent"
        kill = next(f for f in rec["faults"] if f["action"] == "kill")
        assert kill["recovered_epoch"] >= 2
        assert kill["mttr_total_s"] is not None
        assert rec["scrape"]["missing_documented"] == []
        assert rec["scrape"]["audit_problems"] == []

    def _check_flight_ring(self, rec, ring_path):
        """The recorder-armed half of the cycle (ISSUE 15): the REAL
        ring from the run above must carry snapshots + the fault/heal
        stamps, and the doctor must attribute the kill window to a
        recovery — the acceptance criterion on a real-process timeline,
        not a synthetic one (those live in test_flight_recorder.py)."""
        from foundationdb_tpu.obs.doctor import diagnose
        from foundationdb_tpu.obs.recorder import FlightRecorder

        assert rec["recorder"]["recorder_snapshots"] >= 5
        assert rec["recorder"]["slo"]["windows"] >= 4
        records = FlightRecorder.load(ring_path)
        anns = [r for r in records if r.get("kind") == "annotation"]
        assert {a["cls"] for a in anns} >= {"chaos_fault", "chaos_heal"}
        stamps = [(a["action"], a["target"]) for a in anns
                  if a["cls"] in ("chaos_fault", "chaos_heal")]
        assert stamps == [("kill", "tlog0"), ("restart", "tlog0"),
                          ("partition", "tlog1"), ("heal", "tlog1")]
        report = diagnose(records)
        faults = {(f["action"], f["target"]): f for f in report["faults"]}
        assert set(faults) == {("kill", "tlog0"), ("partition", "tlog1")}
        kill_f = faults[("kill", "tlog0")]
        assert kill_f["expected_class"] == "recovery"
        assert kill_f["attributed"], kill_f
        # The chaos ledger's client counters reached the SLO plane.
        snaps = [r for r in records if r.get("kind") == "snapshot"]
        assert "client.commits_acked" in snaps[-1]["metrics"]
        assert "chaos.chaos_faults_injected" in snaps[-1]["metrics"]


class TestChaosCounterNames:
    """Pin the chaos/recovery counter names in the documented-counter
    audit (satellite: the pinned name tests stay exhaustive)."""

    def test_registry_audit_covers_chaos_counters(self):
        from foundationdb_tpu.obs.registry import (
            CHAOS_DOCUMENTED_COUNTERS,
            DOCUMENTED_COUNTERS,
            MetricsRegistry,
        )

        assert "controller.recovery_count" in DOCUMENTED_COUNTERS
        assert all(c.startswith("chaos.chaos_")
                   for c in CHAOS_DOCUMENTED_COUNTERS)
        reg = MetricsRegistry()
        reg.add("controller", "controller0", {
            "recovery_count": 1, "recovery_lock_s": 0.1,
            "recovery_salvage_s": 0.1, "recovery_recruit_s": 0.1,
            "recovery_total_s": 0.3, "recovering": False, "epoch": 2,
        })
        reg.add("chaos", "", {k.split(".", 1)[1]: 0
                              for k in CHAOS_DOCUMENTED_COUNTERS})
        assert reg.audit() == []
        # chaos.* counters are chaos-scope: absent from the core set,
        # demanded via `extra`.
        missing_core = reg.missing_documented()
        assert not any(c.startswith("chaos.") for c in missing_core)
        assert reg.missing_documented(
            extra=CHAOS_DOCUMENTED_COUNTERS) == missing_core
