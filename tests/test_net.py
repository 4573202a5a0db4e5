"""Real-socket transport: wire format, in-process TCP RPC, cross-process
RPC against an unmodified runtime role (TLog), and failure semantics.

This is the deployment-mode pump the flow module promises (reference:
fdbrpc/FlowTransport.actor.cpp + Net2): the same role objects the sim
drives answer RPCs over real TCP, and a lost peer surfaces as
BrokenPromise exactly like a sim kill_process.
"""

import os
import subprocess
import sys
import textwrap

import pytest

from foundationdb_tpu.core.errors import FdbError
from foundationdb_tpu.core.mutations import Mutation, MutationType as M
from foundationdb_tpu.core.types import KeyRange, Verdict
from foundationdb_tpu.runtime import wire
from foundationdb_tpu.runtime.flow import BrokenPromise
from foundationdb_tpu.runtime.net import MAX_FRAME, NetTransport, RealLoop, rpc
from foundationdb_tpu.runtime.tlog import TLog

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestWireFormat:
    def test_scalar_round_trips(self):
        for v in [None, True, False, 0, -1, 2**40, -(2**70), 2**200, 1.5,
                  b"", b"\x00\xff", "héllo", [1, [2, b"x"]], (1, 2),
                  {b"k": [None, False]}, {}]:
            assert wire.loads(wire.dumps(v)) == v

    def test_struct_round_trips(self):
        m = Mutation(M.ADD, b"k", b"\x01")
        assert wire.loads(wire.dumps(m)) == m
        r = KeyRange(b"a", b"b")
        assert wire.loads(wire.dumps(r)) == r
        assert wire.loads(wire.dumps(M.SET_VALUE)) is M.SET_VALUE
        assert wire.loads(wire.dumps(Verdict.CONFLICT)) is Verdict.CONFLICT
        assert wire.loads(wire.dumps([m, r, {1: m}])) == [m, r, {1: m}]

    def test_error_round_trip(self):
        e = wire.loads(wire.dumps(FdbError("boom", code=1020)))
        assert isinstance(e, FdbError) and e.code == 1020 and e.retryable
        assert "boom" in str(e)

    def test_unserializable_raises(self):
        with pytest.raises(TypeError):
            wire.dumps(object())


class Echo:
    @rpc
    async def echo(self, x):
        return x

    @rpc
    def sync_echo(self, x):  # non-async methods also serve
        return x

    @rpc
    async def boom(self):
        raise FdbError("nope", code=1007)

    def not_exported(self):  # unmarked: must be invisible to peers
        return "secret"


class TestInProcessTcp:
    def test_rpc_round_trip_and_errors(self):
        loop = RealLoop()
        server = NetTransport(loop)
        client = NetTransport(loop)
        server.serve("echo", Echo())
        ep = client.endpoint(server.addr, "echo")

        async def main():
            got = await ep.echo({b"k": [Mutation(M.SET_VALUE, b"a", b"b")]})
            assert got == {b"k": [Mutation(M.SET_VALUE, b"a", b"b")]}
            assert await ep.sync_echo(7) == 7
            with pytest.raises(FdbError) as ei:
                await ep.boom()
            assert ei.value.code == 1007
            with pytest.raises(FdbError):
                await ep.no_such_method()
            with pytest.raises(FdbError):
                await client.endpoint(server.addr, "nope").echo(1)
            return "ok"

        try:
            assert loop.run(main(), timeout=30) == "ok"
        finally:
            server.close()
            client.close()

    def test_unexported_method_denied(self):
        """Unmarked methods are invisible to TCP peers (advisor r2: the whole
        object surface must not be dispatchable)."""
        loop = RealLoop()
        server = NetTransport(loop)
        client = NetTransport(loop)
        server.serve("echo", Echo())
        ep = client.endpoint(server.addr, "echo")

        async def main():
            with pytest.raises(FdbError) as ei:
                await ep.not_exported()
            assert "no service" in str(ei.value)
            # Explicit allowlist narrows further: only `echo` is reachable.
            server.serve("narrow", Echo(), methods={"echo"})
            nep = client.endpoint(server.addr, "narrow")
            assert await nep.echo(1) == 1
            with pytest.raises(FdbError):
                await nep.sync_echo(1)
            return "ok"

        try:
            assert loop.run(main(), timeout=30) == "ok"
        finally:
            server.close()
            client.close()

    def test_serve_requires_marked_surface(self):
        loop = RealLoop()
        server = NetTransport(loop)
        try:
            with pytest.raises(ValueError):
                server.serve("bare", object())
        finally:
            server.close()

    def test_error_subclass_crosses_wire(self):
        """T_ERROR decodes to the registered subclass so class-dispatching
        retry logic (WrongShardServer → shard-map refresh) behaves the same
        over TCP as in the sim (advisor r2, medium)."""
        from foundationdb_tpu.core.errors import (
            CommitUnknownResult, NotCommitted, TransactionTooOld,
            WrongShardServer,
        )

        for err in [WrongShardServer("moved"), NotCommitted(),
                    TransactionTooOld("old"), CommitUnknownResult()]:
            back = wire.loads(wire.dumps(err))
            assert type(back) is type(err), (err, back)
            assert back.code == err.code
        # Unknown codes still round-trip as the base class.
        back = wire.loads(wire.dumps(FdbError("custom", code=4321)))
        assert type(back) is FdbError and back.code == 4321

        class Thrower:
            @rpc
            async def moved(self):
                raise WrongShardServer("not mine")

        loop = RealLoop()
        server = NetTransport(loop)
        client = NetTransport(loop)
        server.serve("t", Thrower())
        ep = client.endpoint(server.addr, "t")

        async def main():
            with pytest.raises(WrongShardServer):
                await ep.moved()
            return "ok"

        try:
            assert loop.run(main(), timeout=30) == "ok"
        finally:
            server.close()
            client.close()

    def test_oversized_request_fails_only_itself(self):
        """A frame over MAX_FRAME fails its own future with a non-retryable
        error and leaves the connection (and other in-flight RPCs) alive."""
        loop = RealLoop()
        server = NetTransport(loop)
        client = NetTransport(loop)
        server.serve("echo", Echo())
        ep = client.endpoint(server.addr, "echo")

        async def main():
            big = b"\x00" * (MAX_FRAME + 1)
            with pytest.raises(FdbError) as ei:
                await ep.echo(big)
            assert not ei.value.retryable
            # The connection survived: a normal RPC still works.
            assert await ep.sync_echo(42) == 42
            return "ok"

        try:
            assert loop.run(main(), timeout=30) == "ok"
        finally:
            server.close()
            client.close()

    def test_tlog_role_over_tcp(self):
        """An unmodified runtime TLog serves push/peek/pop over TCP."""
        loop = RealLoop()
        server = NetTransport(loop)
        client = NetTransport(loop)
        server.serve("tlog", TLog(loop))
        ep = client.endpoint(server.addr, "tlog")

        async def main():
            await ep.push(0, 5, {1: [Mutation(M.SET_VALUE, b"k", b"v")]}, 0)
            entries, end, _kc = await ep.peek(1, 1)
            assert entries == [(5, [Mutation(M.SET_VALUE, b"k", b"v")])]
            assert end == 5
            await ep.pop(1, 5)
            entries, _end, _kc = await ep.peek(1, 6)
            assert entries == []
            return "ok"

        try:
            assert loop.run(main(), timeout=30) == "ok"
        finally:
            server.close()
            client.close()


SERVER_SCRIPT = textwrap.dedent("""
    import sys
    from foundationdb_tpu.runtime.net import NetTransport, RealLoop
    from foundationdb_tpu.runtime.tlog import TLog
    loop = RealLoop()
    t = NetTransport(loop)
    t.serve("tlog", TLog(loop))
    print(t.addr[1], flush=True)

    async def forever():
        while True:
            await loop.sleep(3600)

    loop.run(forever(), timeout=120)
""")


class TestCrossProcess:
    def test_tlog_across_processes_and_peer_death(self):
        proc = subprocess.Popen(
            [sys.executable, "-c", SERVER_SCRIPT],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
            cwd=REPO,
        )
        try:
            port = int(proc.stdout.readline())
            loop = RealLoop()
            client = NetTransport(loop)
            ep = client.endpoint(("127.0.0.1", port), "tlog")

            async def main():
                await ep.push(
                    0, 3, {0: [Mutation(M.ADD, b"c", b"\x01" * 8)]}, 0
                )
                entries, end, _ = await ep.peek(0, 1)
                assert end == 3 and entries[0][0] == 3
                # Kill the server with an RPC parked server-side (a push
                # with a chain gap waits for its predecessor forever):
                # the dropped connection must break the pending future.
                fut = ep.push(10, 11, {0: []}, 0)
                await loop.sleep(0.2)  # ensure the request is parked remotely
                proc.kill()
                proc.wait()
                with pytest.raises((BrokenPromise, FdbError)):
                    await fut
                return "ok"

            assert loop.run(main(), timeout=60) == "ok"
            client.close()
        finally:
            proc.kill()
            proc.wait()


PIPELINE_SERVER = textwrap.dedent("""
    from foundationdb_tpu.models.cpu_conflict_set import CPUSkipListConflictSet
    from foundationdb_tpu.runtime.commit_proxy import CommitProxy
    from foundationdb_tpu.runtime.grv_proxy import GrvProxy
    from foundationdb_tpu.runtime.net import NetTransport, RealLoop
    from foundationdb_tpu.runtime.resolver import Resolver
    from foundationdb_tpu.runtime.sequencer import Sequencer
    from foundationdb_tpu.runtime.shardmap import KeyShardMap
    from foundationdb_tpu.runtime.storage import StorageServer
    from foundationdb_tpu.runtime.tlog import TLog

    loop = RealLoop()
    t = NetTransport(loop)
    # Every role-to-role hop rides real TCP (self-endpoints through the
    # listener), proving the sim-shaped call surface end to end.
    t.serve("sequencer", Sequencer(loop))
    t.serve("resolver0", Resolver(loop, CPUSkipListConflictSet()))
    t.serve("tlog0", TLog(loop))
    seq_ep = t.endpoint(t.addr, "sequencer")
    res_ep = t.endpoint(t.addr, "resolver0")
    tlog_ep = t.endpoint(t.addr, "tlog0")
    ss = StorageServer(loop, tag=0, tlog_ep=tlog_ep)
    t.serve("storage0", ss)
    proxy = CommitProxy(loop, seq_ep, [res_ep], KeyShardMap([], tags=[0]),
                        [tlog_ep], KeyShardMap([], tags=[0]))
    grv = GrvProxy(loop, seq_ep)
    t.serve("commit_proxy", proxy)
    t.serve("grv_proxy", grv)
    loop.spawn(proxy.run(), name="proxy.run")
    loop.spawn(grv.run(), name="grv.run")
    loop.spawn(ss.run(), name="ss.run")
    print(t.addr[1], flush=True)

    async def forever():
        while True:
            await loop.sleep(3600)

    loop.run(forever(), timeout=120)
""")


class TestCrossProcessPipeline:
    def test_full_commit_pipeline_over_tcp(self):
        """GRV -> commit -> resolve -> tlog -> storage read, every hop over
        real TCP against a separate server process running unmodified role
        objects — the deployment mode the flow docstring promises."""
        proc = subprocess.Popen(
            [sys.executable, "-c", PIPELINE_SERVER],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
            cwd=REPO,
        )
        try:
            port = int(proc.stdout.readline())
            loop = RealLoop()
            client = NetTransport(loop)
            addr = ("127.0.0.1", port)
            grv = client.endpoint(addr, "grv_proxy")
            proxy = client.endpoint(addr, "commit_proxy")
            storage = client.endpoint(addr, "storage0")

            from foundationdb_tpu.core.types import single_key_range
            from foundationdb_tpu.runtime.commit_proxy import CommitRequest

            async def main():
                rv = await grv.get_read_version()
                res = await proxy.commit(CommitRequest(
                    read_version=rv,
                    mutations=[Mutation(M.SET_VALUE, b"apple", b"1")],
                    write_ranges=[single_key_range(b"apple")],
                ))
                assert res.version > rv
                rv2 = await grv.get_read_version()
                assert rv2 >= res.version
                got = await storage.get(b"apple", rv2)
                assert got == b"1", got
                # Read-write conflict at the stale snapshot crosses the wire
                # with its reference error code.
                with pytest.raises(FdbError) as ei:
                    await proxy.commit(CommitRequest(
                        read_version=rv,
                        mutations=[Mutation(M.SET_VALUE, b"apple", b"2")],
                        read_ranges=[single_key_range(b"apple")],
                        write_ranges=[single_key_range(b"apple")],
                    ))
                assert ei.value.code == 1020  # not_committed
                return "ok"

            assert loop.run(main(), timeout=60) == "ok"
            client.close()
        finally:
            proc.kill()
            proc.wait()


class TestNativeCClient:
    def test_c_client_full_path(self):
        """The native C client (netclient.cpp) drives GRV/commit/read over
        TCP against the cluster transport — the reference's fdb_c network
        client parity path, no Python in the client data plane."""
        from foundationdb_tpu.client.net_client import NetClient
        from foundationdb_tpu.core.types import single_key_range

        proc = subprocess.Popen(
            [sys.executable, "-c", PIPELINE_SERVER],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
            cwd=REPO,
        )
        try:
            port = int(proc.stdout.readline())
            c = NetClient("127.0.0.1", port)
            rv = c.get_read_version()
            assert rv >= 0
            cv = c.commit(
                rv,
                [Mutation(M.SET_VALUE, b"ckey", b"cvalue")],
                write_ranges=[single_key_range(b"ckey")],
            )
            assert cv > rv
            rv2 = c.get_read_version()
            assert rv2 >= cv
            assert c.get(b"ckey", rv2) == b"cvalue"
            assert c.get(b"nokey", rv2) is None
            # Conflict crosses the C ABI with the reference error code.
            with pytest.raises(FdbError) as ei:
                c.commit(
                    rv,
                    [Mutation(M.SET_VALUE, b"ckey", b"other")],
                    read_ranges=[single_key_range(b"ckey")],
                    write_ranges=[single_key_range(b"ckey")],
                )
            assert ei.value.code == 1020
            # Atomic op through the C client.
            cv2 = c.commit(
                rv2,
                [Mutation(M.ADD, b"ctr", (7).to_bytes(8, "little"))],
                write_ranges=[single_key_range(b"ctr")],
            )
            rv3 = c.get_read_version()
            assert int.from_bytes(c.get(b"ctr", rv3), "little") == 7
            c.close()
        finally:
            proc.kill()
            proc.wait()


class TestNativeCClientPipelining:
    def test_pipelined_commits_one_connection(self):
        """Many commits in flight on ONE connection, collected out of
        order (VERDICT r2 weak-7: the blocking one-request-per-connection
        C client could never demonstrate pipeline throughput). Replies
        for other ids stash client-side; every commit must succeed and
        versions must be monotone in send order (the proxy chains
        batches)."""
        from foundationdb_tpu.client.net_client import NetClient
        from foundationdb_tpu.core.types import single_key_range

        proc = subprocess.Popen(
            [sys.executable, "-c", PIPELINE_SERVER],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
            cwd=REPO,
        )
        try:
            port = int(proc.stdout.readline())
            c = NetClient("127.0.0.1", port)
            rv = c.get_read_version()
            n = 12
            reqs = []
            for i in range(n):
                key = b"pl/%03d" % i
                reqs.append(c.commit_send(
                    rv,
                    [Mutation(M.SET_VALUE, key, b"v%03d" % i)],
                    write_ranges=[single_key_range(key)],
                ))
            assert len(set(reqs)) == n  # distinct ids, all in flight
            # Collect in REVERSE order: exercises the reply stash.
            versions = {}
            for rid in reversed(reqs):
                versions[rid] = c.commit_wait(rid)
            ordered = [versions[r] for r in reqs]
            assert all(v > rv for v in ordered)
            assert ordered == sorted(ordered)  # chain order preserved
            # Everything readable afterward.
            rv2 = c.get_read_version()
            for i in range(n):
                assert c.get(b"pl/%03d" % i, rv2) == b"v%03d" % i
            c.close()
        finally:
            proc.kill()
            proc.wait()


class TestWireFuzz:
    def test_server_survives_garbage_frames(self):
        """Malformed/hostile bytes on the wire must never take the server
        down: each bad connection is dropped (or its frame rejected) and
        well-formed clients keep working throughout (reference: fdbrpc
        connection handling tolerates arbitrary peers)."""
        import socket
        import random

        from foundationdb_tpu.runtime.flow import rpc
        from foundationdb_tpu.runtime.net import NetTransport, RealLoop

        class Echo:
            @rpc
            async def ping(self, x):
                return x

        loop = RealLoop()
        server = NetTransport(loop)
        client = NetTransport(loop)
        server.serve("e", Echo())
        ep = client.endpoint(server.addr, "e")
        rng = random.Random(7)

        def hostile(payload: bytes, with_len: bool = True):
            s = socket.create_connection(server.addr, timeout=5)
            try:
                if with_len:
                    s.sendall(len(payload).to_bytes(4, "little") + payload)
                else:
                    s.sendall(payload)
            finally:
                s.close()

        async def main():
            assert await ep.ping(41) == 41
            # 1. random garbage with a plausible length prefix
            for _ in range(10):
                hostile(bytes(rng.randrange(256)
                              for _ in range(rng.randrange(1, 200))))
                assert await ep.ping(1) == 1
            # 2. truncated length header / short frames
            hostile(b"\x01", with_len=False)
            hostile(b"", with_len=True)
            # 3. absurd length prefix (> MAX_FRAME) then nothing
            s = socket.create_connection(server.addr, timeout=5)
            s.sendall((1 << 30).to_bytes(4, "little"))
            s.close()
            # 4. a VALID tuple header followed by nonsense values
            hostile(b"\x09\x05\x00\x00\x00" + b"\xff" * 40)
            assert await ep.ping(2) == 2
            return "ok"

        try:
            assert loop.run(main(), timeout=60) == "ok"
        finally:
            server.close()
            client.close()


class TestTLogRestartSemantics:
    def test_from_disk_preserves_file_and_duplicate_discipline(self, tmp_path):
        """Deployed-restart tlog semantics: from_disk resumes the SAME
        chain file without truncating it; begin_epoch jumps never cause
        false duplicate acks; truncate_to drops the unacked suffix."""
        import os

        from foundationdb_tpu.runtime.flow import Loop
        from foundationdb_tpu.runtime.tlog import TLog

        loop = Loop(seed=1)
        p = str(tmp_path / "t.q")
        t1 = TLog(loop, disk_path=p)

        async def fill():
            await t1.push(0, 10, {0: []})
            await t1.push(10, 20, {0: []})
            await t1.push(20, 30, {0: []})

        loop.run(fill())
        size_before = os.path.getsize(p)

        # Restart from disk: file survives byte-for-byte (no truncate
        # window), chain end recovered.
        t2 = TLog.from_disk(loop, p)
        assert os.path.getsize(p) == size_before
        assert t2._last_appended == 30

        async def scenario():
            # Unacked suffix discipline: drop entries above 20.
            dropped = await t2.truncate_to(20)
            assert dropped == 1 and t2._last_appended == 20
            # Epoch jump, then the new chain pushes.
            start = await t2.begin_epoch(1_000_000)
            assert start == 1_000_000
            # A STALE push from before the jump must fail the gap check,
            # not ack as a duplicate (it was never appended).
            try:
                await t2.push(25, 40, {0: []})
                raise AssertionError("stale push falsely acked")
            except ValueError:
                pass
            # A true retransmit of an appended version still acks.
            assert await t2.push(10, 20, {0: []}) == 20
            # The new chain proceeds.
            assert await t2.push(1_000_000, 1_000_050, {0: []}) == 1_000_050

        loop.run(scenario())

        # Third incarnation: truncation + new pushes are on disk.
        t3 = TLog.from_disk(loop, p)
        assert t3._last_appended == 1_000_050
        versions = [e.version for e in t3._log]
        assert 30 not in versions and 1_000_050 in versions


class TestTcpRelay:
    """Interposing relay (deployed chaos partition injector): bytes
    splice transparently in pass mode, vanish (connections HANG, not
    die) in drop mode, resume intact on heal, and reset in cut mode."""

    def test_pass_drop_heal_cut(self):
        from foundationdb_tpu.runtime.net import TcpRelay

        loop = RealLoop()
        server = NetTransport(loop)
        server.serve("echo", Echo())
        relay = TcpRelay(server.addr)
        client = NetTransport(loop)
        ep = client.endpoint(relay.addr, "echo")

        async def call(x, timeout):
            task = loop.spawn(ep.echo(x), name="relay.call")
            deadline = loop.now + timeout
            while not task.done() and loop.now < deadline:
                await loop.sleep(0.02)
            return task

        async def main():
            # pass: transparent round trip through the relay
            t1 = await call(41, 5.0)
            assert t1.done() and t1.result() == 41
            assert relay.bytes_forwarded > 0

            # drop: the call HANGS (no BrokenPromise — packets vanish)
            relay.set_mode("drop")
            t2 = await call(42, 0.8)
            assert not t2.done(), "drop mode must black-hole, not fail"

            # heal: the SAME in-flight call completes — no byte was lost
            relay.heal()
            deadline = loop.now + 5.0
            while not t2.done() and loop.now < deadline:
                await loop.sleep(0.02)
            assert t2.done() and t2.result() == 42

            # cut: live connections die (pending requests fail fast)
            t3 = await call(43, 5.0)
            assert t3.done() and t3.result() == 43
            relay.set_mode("cut")
            t4 = await call(44, 5.0)
            assert t4.done() and t4.is_error()  # reset/EOF, not a hang
            return "ok"

        try:
            assert loop.run(main(), timeout=60) == "ok"
        finally:
            relay.close()
            server.close()
            client.close()


class _HangService:
    @rpc
    async def hang(self):
        from foundationdb_tpu.runtime.flow import Promise
        await Promise().future  # never answers


class TestAbandonedCall:
    """server.bounded_rpc(transport=...) must ABANDON a timed-out
    request: on a black-holed link the connection stays open (nothing
    ever fails the promise), so without this every probe sweep leaves
    one never-answered entry in conn.pending for the partition's whole
    duration (review finding)."""

    def test_timeout_drops_pending_registration(self):
        from foundationdb_tpu.server import bounded_rpc

        loop = RealLoop()
        server = NetTransport(loop)
        client = NetTransport(loop)
        server.serve("hang", _HangService())
        server.serve("echo", Echo())
        hang_ep = client.endpoint(server.addr, "hang")
        echo_ep = client.endpoint(server.addr, "echo")

        async def main():
            for _ in range(3):
                with pytest.raises(TimeoutError):
                    await bounded_rpc(loop, hang_ep.hang(), 0.05,
                                      transport=client)
            conn = client._conns[tuple(server.addr)]
            assert conn.pending == {}, "timed-out probes accumulated"
            assert client._call_sites == {}
            # The link still works, and a COMPLETED call unregisters
            # its site too (the map cannot grow on the happy path).
            assert await bounded_rpc(loop, echo_ep.echo(7), 5.0,
                                     transport=client) == 7
            assert client._call_sites == {}
            return True

        try:
            assert loop.run(main(), timeout=60)
        finally:
            client.close()
            server.close()


class TestReconnectBackoff:
    """Client reconnect hardening (ISSUE 14 satellite): consecutive
    byte-less dials to a dead peer are suppressed for a bounded jittered
    window (failing fast with the same BrokenPromise a dead connection
    gives), and a peer that comes back is dialled again."""

    def test_dead_peer_dials_suppressed_then_recover(self):
        import socket as _socket

        # A port with nothing behind it (bound-then-closed): dials fail.
        s = _socket.create_server(("127.0.0.1", 0))
        addr = s.getsockname()
        s.close()

        loop = RealLoop()
        client = NetTransport(loop)
        ep = client.endpoint(addr, "echo")

        async def fail_once():
            try:
                await ep.echo(1)
                raise AssertionError("dead peer answered")
            except FdbError as e:
                return str(e)

        async def main():
            msgs = []
            for _ in range(6):
                msgs.append(await fail_once())
                await loop.sleep(0.01)
            return msgs

        try:
            msgs = loop.run(main(), timeout=60)
            # After the first couple of failures the transport suppresses
            # re-dials for a backoff window (message says so).
            assert any("reconnect backoff" in m for m in msgs), msgs
            assert client._dial_backoff[tuple(addr)][0] >= 2

            # Peer comes back: once the (bounded, capped) window expires
            # the next dial goes through and the backoff resets.
            server = NetTransport(loop, host=addr[0], port=addr[1])
            server.serve("echo", Echo())

            async def recovered():
                deadline = loop.now + 3 * NetTransport.DIAL_BACKOFF_CAP
                while True:
                    try:
                        return await ep.echo(99)
                    except FdbError:
                        if loop.now > deadline:
                            raise
                        await loop.sleep(0.05)

            assert loop.run(recovered(), timeout=60) == 99
            assert tuple(addr) not in client._dial_backoff
            server.close()
        finally:
            client.close()


class TestTlsDialRefused:
    """Over TLS the ClientHello is written as the connection object is
    made; on loopback a peer that does not listen yet has refused by then.
    That must fail the call as any dead link does (BrokenPromise, which
    every retry loop catches), not unwind its caller with the selector's
    ValueError for a closed socket (PR 28: `[storage0.run] actor failed:
    ValueError: Invalid file descriptor: -1` at every TLS cluster's boot,
    and a proxy's first push lost without one retry)."""

    def test_a_refused_tls_dial_is_a_broken_promise(self, tmp_path):
        from foundationdb_tpu.loadgen.deploy import free_ports
        from tests.test_tls import make_ca_and_leaf

        loop = RealLoop()
        t = NetTransport(loop, tls=make_ca_and_leaf(str(tmp_path), "main"))
        nobody = ("127.0.0.1", free_ports(1)[0])
        try:
            for _ in range(3):  # the dial backoff's refusals too
                with pytest.raises(BrokenPromise):
                    loop.run_until(t.endpoint(nobody, "tlog").get_version(),
                                   timeout=10)
        finally:
            t.close()
