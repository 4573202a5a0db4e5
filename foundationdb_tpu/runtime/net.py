"""Real-socket RPC transport: the deployment-mode fdbrpc analogue.

The reference runs the SAME role code in simulation (flow/sim2) and
production (flow/Net2.actor.cpp + fdbrpc/FlowTransport.actor.cpp). Here the
split is identical: the sim network (sim/network.py) virtualises RPC under
the deterministic Loop; this module pumps the same Loop against wall-clock
time and real TCP sockets, so unmodified role objects (TLog, StorageServer,
CommitProxy, ...) serve RPCs across processes.

- RealLoop: flow.Loop whose timers fire on the monotonic clock and whose
  idle waits block in selector.select(), waking on socket readiness.
- NetTransport: length-prefixed frames of wire.py-encoded messages. A
  request names (service, method, args); the reply carries the value or an
  FdbError (errors cross the network with their codes, so client retry
  logic behaves identically to the sim). A dropped connection fails every
  pending request with BrokenPromise — exactly what the sim's kill_process
  delivers, so callers cannot tell the difference.

Determinism note: real mode is intentionally non-deterministic (the kernel
schedules packets). Correctness testing stays in the sim; this transport is
the pump the sim's design promised.
"""

from __future__ import annotations

import errno
import heapq
import selectors
import socket
import struct
import threading
import time

_SOFT_ERRNOS = (errno.EAGAIN, errno.EINPROGRESS, errno.ENOTCONN, errno.EALREADY)

from foundationdb_tpu.core.errors import FdbError, TransactionTooLarge
from foundationdb_tpu.obs.span import span_now, span_sink, stage_timer
from foundationdb_tpu.runtime import wire
from foundationdb_tpu.runtime.flow import (
    BrokenPromise, Future, Loop, Promise, rpc,
)

__all__ = ["RealLoop", "NetTransport", "RemoteEndpoint", "TcpRelay", "rpc",
           "rpc_methods", "MAX_FRAME"]

_LEN = struct.Struct("<I")
_REQ, _RSP = 0, 1
MAX_FRAME = 64 << 20


def rpc_methods(obj: object) -> frozenset[str]:
    """The @rpc-marked method names of an object's class."""
    cls = type(obj)
    return frozenset(
        name
        for name in dir(cls)
        if not name.startswith("_")
        and getattr(getattr(cls, name, None), "_rpc_exported", False)
    )


class RealLoop(Loop):
    """flow.Loop over wall-clock time + socket readiness.

    The rng is ENTROPY-seeded by default: determinism across processes is
    a sim property (SimLoop), and a real deployment needs the opposite —
    with a fixed seed every fresh client draws the SAME randomized
    round-robin start, so e.g. every CLI process parity-locks its commits
    onto the same (possibly zombie) proxy forever (deployed multi-region
    partition find)."""

    MAX_IDLE_WAIT = 0.05  # bound each select() so new work is noticed
    WALL_TIME = True  # `now` is monotonic; tracers add epoch WallTime stamps

    def __init__(self, seed: "int | None" = None):
        super().__init__(seed=seed, start_time=time.monotonic())
        self.selector = selectors.DefaultSelector()
        # What this process IS, for the per-process stages of obs/span.py
        # (`loop_busy:<role>`): server.py names it (`--role`); a loop it
        # did not start takes the first service its transport serves
        # other than `admin` (NetTransport.serve); one that serves
        # nothing is a client.
        self.role: "str | None" = None
        self._obs_acc = [0.0, 0.0]  # busy, idle seconds of the open slice

    def resync(self) -> None:
        """Snap `now` to the current monotonic clock. The pump refreshes
        `_now` as it iterates, but code that blocks OUTSIDE the loop
        (e.g. a wall-clock synchronization sleep before loop.run) leaves
        it stale — anything anchoring timestamps to `loop.now` before
        the first pump iteration would then measure phantom lateness
        equal to the blocked interval (loadgen start-at find)."""
        self._now = time.monotonic()

    @property
    def wall_now(self) -> float:
        """Epoch seconds: operator-minted expiries (authz tokens) compare
        against THIS, never against the monotonic `now` (whose epoch is
        host boot — a token minted with time.time() would otherwise stay
        valid for decades)."""
        return time.time()

    def register(self, sock: socket.socket, events: int, callback) -> None:
        try:
            self.selector.register(sock, events, callback)
        except KeyError:
            self.selector.modify(sock, events, callback)

    def unregister(self, sock: socket.socket) -> None:
        try:
            self.selector.unregister(sock)
        except (KeyError, ValueError):
            pass

    def run_until(self, fut: Future, timeout: float = 1e9):
        deadline = time.monotonic() + timeout
        # Busy-share accounting (obs/span.py `loop_busy:<role>`), armed
        # only while a sink is attached: `mark` is where the busy stretch
        # under way began on the span clock. Time outside run_until is
        # neither busy nor idle.
        mark = (time.perf_counter()
                if getattr(self, "span_sink", None) is not None else None)
        while True:
            self._drain_ready()
            if fut.done():
                if mark is not None:
                    self._obs_turn(mark, time.perf_counter(), None)
                return fut.result()
            now = time.monotonic()
            if now > deadline:
                raise TimeoutError(f"run_until exceeded {timeout}s")
            wait = self.MAX_IDLE_WAIT
            if self._timers:
                wait = min(wait, max(0.0, self._timers[0][0] - now))
            if getattr(self, "span_sink", None) is None:
                mark = None
                events = self._idle(wait)
            else:
                # Everything in the turn but the wait is BUSY: ready
                # tasks above, socket callbacks and timers below. Under a
                # profiler the wait is `fdb:loop_select` on its timeline.
                t0 = time.perf_counter()
                with stage_timer(None, "loop_select"):
                    events = self._idle(wait)
                t1 = time.perf_counter()
                self._obs_turn(mark or t0, t0, t1)
                mark = t1
            for key, _mask in events:
                key.data(key.fileobj)
            self._now = time.monotonic()
            while self._timers and self._timers[0][0] <= self._now:
                _t, _seq, p = heapq.heappop(self._timers)
                p.send(None)

    def _idle(self, wait: float) -> "list | tuple":
        """The one place the loop waits: for socket readiness, or with no
        socket registered for the next timer."""
        if self.selector.get_map():
            return self.selector.select(wait)
        if wait > 0:
            time.sleep(wait)
        return ()

    #: One `loop_busy` / `loop_idle` sample a slice of this many seconds.
    OBS_SLICE_S = 0.1

    def _obs_turn(self, busy_from: float, idle_from: float,
                  idle_to: "float | None") -> None:
        """Account one pump turn, busy [busy_from, idle_from) then idle
        [idle_from, idle_to), and flush both sums as one histogram sample
        each once a slice is over (or at the pump's end: idle_to None).
        Not sampled 1-in-N: the SUMS of the two stages are the process's
        busy share, their p95 how full its worst slices are."""
        acc = self._obs_acc
        acc[0] += idle_from - busy_from
        if idle_to is not None:
            acc[1] += idle_to - idle_from
            if acc[0] + acc[1] < self.OBS_SLICE_S:
                return
        sink = span_sink(self)
        if sink is not None:
            role = self.role or "client"
            sink.record_stage("loop_busy:" + role, acc[0])
            sink.record_stage("loop_idle:" + role, acc[1])
        acc[0] = acc[1] = 0.0


class _Conn:
    """One TCP connection (either side): frame reassembly + buffered writes.

    Small frames COALESCE per flush: send_frame appends to the write
    buffer and raises EVENT_WRITE interest instead of hitting the socket
    per frame — every frame queued in one scheduler burst (a GRV batch's
    replies, a pipelined client's requests) drains in ONE send() on the
    next selector round. With TCP_NODELAY set (it is, on both accepted
    and connecting sockets) each send() is one segment, so without
    coalescing a burst of length-prefixed small RPC frames becomes a
    segment per frame; with Nagle instead it becomes a 40ms
    delayed-ACK stall per round trip. Buffers past COALESCE_BYTES flush
    eagerly so a bulk stream never accumulates unbounded.

    With a TLS-configured transport (reference: flow/TLSConfig.actor.cpp —
    mutual TLS between every pair of processes), the framing rides an
    ``ssl.SSLObject`` over memory BIOs: raw socket bytes feed the incoming
    BIO, decrypted application bytes feed the frame reassembly, and
    outgoing handshake/application bytes drain from the outgoing BIO into
    the ordinary nonblocking write buffer. Frames queued before the
    handshake completes are buffered and sent on completion."""

    COALESCE_BYTES = 64 << 10  # past this, flush eagerly (bounded buffer)

    def __init__(self, transport: "NetTransport", sock: socket.socket,
                 server_side: bool = True):
        self.t = transport
        self.sock = sock
        sock.setblocking(False)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.rbuf = bytearray()
        self.wbuf = bytearray()
        self.frames_queued = 0  # coalescing ratio = frames_queued/flushes
        self.flushes = 0
        self.got_bytes = False  # ever received data (dial-health signal)
        self.outbound_addr: "tuple | None" = None  # set by _connect
        self.pending: dict[int, Promise] = {}  # requests sent on this conn
        self.closed = False
        self.tls = None
        ctx = transport.tls_context(server_side)
        if ctx is not None:
            import ssl as _ssl

            self._in_bio = _ssl.MemoryBIO()
            self._out_bio = _ssl.MemoryBIO()
            self.tls = ctx.wrap_bio(
                self._in_bio, self._out_bio, server_side=server_side
            )
            self._hs_done = False
            self._pre_hs: list[bytes] = []  # frames queued pre-handshake
            self._step_tls()
        # _events(), not EVENT_READ: _step_tls may already have queued the
        # ClientHello in wbuf (send hit EAGAIN on an in-flight connect) —
        # registering read-only here would drop write interest and the
        # handshake would deadlock. Or its send found the dial refused
        # (a loopback peer that does not listen yet) and closed us.
        if not self.closed:
            self.t.loop.register(sock, self._events(), self._on_ready)

    # -- IO -------------------------------------------------------------

    def _events(self) -> int:
        return selectors.EVENT_READ | (
            selectors.EVENT_WRITE if self.wbuf else 0
        )

    def _on_ready(self, _sock) -> None:
        try:
            data = self.sock.recv(1 << 20)
        except (BlockingIOError, InterruptedError):
            data = None
        except OSError as e:
            if e.errno in _SOFT_ERRNOS:  # outbound connect still in flight
                data = None
            else:
                self.close()
                return
        if data is not None:
            if not data:
                self.close()
                return
            if not self.got_bytes:
                self.got_bytes = True
                if self.outbound_addr is not None:
                    # The peer is demonstrably alive: reset its dial
                    # backoff NOW (not at conn close) so a recovered
                    # process doesn't keep paying a stale suppression.
                    self.t._dial_backoff.pop(self.outbound_addr, None)
            if self.tls is not None:
                self._in_bio.write(bytes(data))
                if not self._step_tls():
                    return  # closed on TLS failure
            else:
                self.rbuf += data
            self._drain_frames()
        if self.wbuf:
            self._flush()

    # -- TLS pump --------------------------------------------------------

    def _step_tls(self) -> bool:
        """Advance handshake + decrypt available bytes. False → closed."""
        import ssl as _ssl

        if not self._hs_done:
            try:
                self.tls.do_handshake()
                self._hs_done = True
                for payload in self._pre_hs:
                    self.tls.write(payload)
                self._pre_hs = []
            except _ssl.SSLWantReadError:
                pass
            except _ssl.SSLError:
                self._drain_out_bio()
                self.close()  # alert bytes (if any) flushed best-effort
                return False
        if self._hs_done:
            while True:
                try:
                    chunk = self.tls.read(1 << 16)
                except _ssl.SSLWantReadError:
                    break
                except _ssl.SSLError:
                    self.close()
                    return False
                if not chunk:
                    self.close()  # clean TLS EOF
                    return False
                self.rbuf += chunk
        self._drain_out_bio()
        return True

    def _drain_out_bio(self) -> None:
        pending = self._out_bio.read()
        if pending:
            self.wbuf += pending
            self._flush()

    def send_frame(self, payload: bytes) -> None:
        if self.closed:
            raise BrokenPromise("connection closed")
        if len(payload) > MAX_FRAME:
            # The receiver drops the whole connection on an oversized frame
            # (failing every pending request); fail just this one instead,
            # before any bytes hit the socket. Non-retryable.
            raise TransactionTooLarge(
                f"frame of {len(payload)} bytes exceeds {MAX_FRAME}"
            )
        framed = _LEN.pack(len(payload)) + payload
        self.frames_queued += 1
        if self.tls is not None:
            if not self._hs_done:
                self._pre_hs.append(framed)
                return
            self.tls.write(framed)
            self._drain_out_bio()
            return
        self.wbuf += framed
        if len(self.wbuf) >= self.COALESCE_BYTES:
            self._flush()
        elif len(self.wbuf) == len(framed):
            # Buffer was empty: raise write interest ONCE per burst and
            # let the next selector round drain everything queued in the
            # burst in one send(). Later frames skip the selector call —
            # interest is already up (_flush re-registers after drains).
            self.t.loop.register(self.sock, self._events(), self._on_ready)

    def _flush(self) -> None:
        try:
            n = self.sock.send(self.wbuf)
            del self.wbuf[:n]
            if n:
                self.flushes += 1
        except (BlockingIOError, InterruptedError):
            pass
        except OSError as e:
            if e.errno not in _SOFT_ERRNOS:
                self.close()
                return
        self.t.loop.register(self.sock, self._events(), self._on_ready)

    def _drain_frames(self) -> None:
        while len(self.rbuf) >= 4:
            n = _LEN.unpack_from(self.rbuf)[0]
            if n > MAX_FRAME:
                self.close()
                return
            if len(self.rbuf) < 4 + n:
                return
            frame = bytes(self.rbuf[4 : 4 + n])
            del self.rbuf[: 4 + n]
            try:
                self.t._on_frame(self, frame)
            except Exception:  # noqa: BLE001 — a bad frame (corruption,
                # struct-registry version skew) must drop THIS peer, never
                # unwind the selector loop and kill every service with it.
                self.close()
                return

    def close(self) -> None:
        if self.closed:
            return
        self.closed = True
        self.t.loop.unregister(self.sock)
        try:
            self.sock.close()
        except OSError:
            pass
        self.t._on_conn_closed(self)
        pending, self.pending = self.pending, {}
        for p in pending.values():
            p.fail(BrokenPromise("connection lost"))


class RemoteEndpoint:
    """Client stub: ep.method(*args) -> Future (same call shape as the sim
    network's endpoints, so role code is transport-agnostic)."""

    def __init__(self, transport: "NetTransport", addr: tuple, service: str):
        self._t = transport
        self._addr = addr
        self._service = service

    def __getattr__(self, method: str):
        if method.startswith("_"):
            raise AttributeError(method)

        def call(*args, **kwargs) -> Future:
            return self._t._call(self._addr, self._service, method, args,
                                 kwargs)

        call.__name__ = method
        return call

    def __repr__(self) -> str:
        return f"RemoteEndpoint({self._addr!r}, {self._service!r})"


class NetTransport:
    """Serve local role objects + call remote ones over TCP.

    `tls`: optional dict ``{"cert": path, "key": path, "ca": path}`` —
    enables MUTUAL TLS on every connection, both directions (reference:
    flow/TLSConfig.actor.cpp; FDB processes verify each other's chains).
    Peers without the right client certificate cannot complete a
    handshake, so the @rpc surface is unreachable to them. Note: the C
    netclient (native/netclient.cpp) speaks plaintext — point it at a
    non-TLS cluster (the reference's fdb_c grows TLS via network options;
    ours does not yet)."""

    def __init__(self, loop: RealLoop, host: str = "127.0.0.1", port: int = 0,
                 tls: dict | None = None):
        self.loop = loop
        self._services: dict[str, tuple[object, frozenset[str]]] = {}
        self._conns: dict[tuple, _Conn] = {}  # outbound, by remote addr
        self._all_conns: set[_Conn] = set()
        self._next_id = 0
        # Operator-triggered fault rules for deployed chaos testing
        # (the TCP analogue of sim/network.py's partition/clog): peer
        # addr -> {"mode": "drop"|"delay", "delay_s", "until"}. Applied
        # to OUTBOUND calls from this process; installed via the admin
        # service's inject_fault RPC (server.py).
        self._fault_rules: dict[tuple, dict] = {}
        # Reconnect backoff per remote addr: after consecutive dials
        # that died without EVER delivering a byte (dead/partitioned
        # peer), further dials are suppressed for a bounded jittered
        # window — failing fast with the same BrokenPromise observable
        # a dead connection gives. Without this, every retry loop in
        # every client slot re-dials a dead proxy at full rate (a SYN
        # storm against the process fdbmonitor is about to restart).
        # addr -> [consecutive_failures, suppressed_until (loop.now)].
        self._dial_backoff: dict[tuple, list] = {}
        # In-flight request registrations by id(future) -> (conn, msg_id),
        # pruned when the future completes: lets abandon_call() drop the
        # pending-reply entry of an RPC its caller timed out on.
        self._call_sites: dict[int, tuple] = {}
        self._tls_server_ctx = self._tls_client_ctx = None
        if tls:
            import ssl as _ssl

            srv = _ssl.SSLContext(_ssl.PROTOCOL_TLS_SERVER)
            srv.load_cert_chain(tls["cert"], tls["key"])
            srv.load_verify_locations(tls["ca"])
            srv.verify_mode = _ssl.CERT_REQUIRED  # mutual TLS
            cli = _ssl.SSLContext(_ssl.PROTOCOL_TLS_CLIENT)
            cli.load_cert_chain(tls["cert"], tls["key"])
            cli.load_verify_locations(tls["ca"])
            # Peers are verified by CA chain, not hostname (processes move
            # between addresses; the reference verifies subject criteria).
            cli.check_hostname = False
            cli.verify_mode = _ssl.CERT_REQUIRED
            self._tls_server_ctx, self._tls_client_ctx = srv, cli
        self._listener = socket.create_server((host, port))
        self._listener.setblocking(False)
        self.addr = self._listener.getsockname()
        loop.register(self._listener, selectors.EVENT_READ, self._accept)

    def tls_context(self, server_side: bool):
        return self._tls_server_ctx if server_side else self._tls_client_ctx

    # -- server side ------------------------------------------------------

    def serve(self, name: str, obj: object,
              methods: "frozenset[str] | set[str] | None" = None) -> None:
        """Expose `obj` to TCP peers under `name`.

        Only methods named in `methods` (or, by default, those marked with
        the @rpc decorator on the class) are dispatchable — the rest of the
        object surface stays private to the process.
        """
        allow = frozenset(methods) if methods is not None else rpc_methods(obj)
        if not allow:
            raise ValueError(
                f"serve({name!r}): no @rpc-marked methods on "
                f"{type(obj).__name__} and no explicit allowlist given"
            )
        self._services[name] = (obj, allow)
        if name != "admin" and self.loop.role is None:
            self.loop.role = name  # RealLoop.role: a loop nobody named

    def unserve(self, name: str) -> None:
        """Withdraw a service: later calls fail with "no service" (1500) —
        how a stood-down role (a retired generation's proxy/tlog on a
        rejoined region) tells clients to look elsewhere; their retry
        loops demote the endpoint and rotate on."""
        self._services.pop(name, None)

    def _accept(self, _sock) -> None:
        try:
            sock, _peer = self._listener.accept()
        except (BlockingIOError, OSError):
            return
        self._all_conns.add(_Conn(self, sock, server_side=True))

    # -- client side ------------------------------------------------------

    def endpoint(self, addr: tuple, service: str) -> RemoteEndpoint:
        return RemoteEndpoint(self, tuple(addr), service)

    #: reconnect backoff: suppression starts at the 2nd consecutive
    #: byte-less dial failure, doubles, and is jittered + capped.
    DIAL_BACKOFF_BASE = 0.05
    DIAL_BACKOFF_CAP = 2.0

    def _connect(self, addr: tuple) -> _Conn:
        conn = self._conns.get(addr)
        if conn is not None and not conn.closed:
            return conn
        rule = self._dial_backoff.get(addr)
        if rule is not None and self.loop.now < rule[1]:
            raise BrokenPromise(
                f"connect to {addr} suppressed for "
                f"{rule[1] - self.loop.now:.2f}s (reconnect backoff after "
                f"{rule[0]} failed dials)")
        sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        sock.setblocking(False)
        try:
            sock.connect(addr)
        except BlockingIOError:
            pass  # completes asynchronously; sends queue in wbuf meanwhile
        except OSError:
            sock.close()  # synchronous failure: don't leak the fd
            self._note_dial_failed(addr)
            raise
        conn = _Conn(self, sock, server_side=False)
        if conn.closed:  # refused before the TLS hello could leave
            self._note_dial_failed(addr)
            raise BrokenPromise(f"connect to {addr} refused")
        conn.outbound_addr = addr
        self._conns[addr] = conn
        self._all_conns.add(conn)
        return conn

    def _note_dial_failed(self, addr: tuple) -> None:
        fails = self._dial_backoff.get(addr, [0, 0.0])[0] + 1
        delay = 0.0
        if fails >= 2:
            # Jitter BEFORE the cap: the cap is the contract's bound.
            delay = min(self.DIAL_BACKOFF_CAP,
                        self.DIAL_BACKOFF_BASE * (1 << min(fails - 2, 16))
                        * (0.5 + self.loop.rng.random()))
        self._dial_backoff[addr] = [fails, self.loop.now + delay]

    FAULT_DETECT_DELAY = 1.0  # dropped call → BrokenPromise after this

    def set_fault(self, addr: tuple, mode: str, delay_s: float = 0.05,
                  duration_s: float = 5.0) -> None:
        """Install a fault rule against `addr`: "drop" black-holes calls
        (they fail BrokenPromise after FAULT_DETECT_DELAY — the same
        observable as a network partition) and "delay" defers each send
        by `delay_s` (a clogged-but-alive link). Auto-expires after
        `duration_s` — a wedged test cannot leave a cluster permanently
        partitioned."""
        if mode not in ("drop", "delay"):
            raise ValueError(f"unknown fault mode {mode!r}")
        self._fault_rules[tuple(addr)] = {
            "mode": mode, "delay_s": float(delay_s),
            "until": self.loop.now + float(duration_s),
        }

    def clear_faults(self) -> None:
        self._fault_rules.clear()

    def _call(self, addr: tuple, service: str, method: str, args: tuple,
              kwargs: dict | None = None) -> Future:
        addr = tuple(addr)
        rule = self._fault_rules.get(addr)
        if rule is not None:
            if self.loop.now >= rule["until"]:
                self._fault_rules.pop(addr, None)
            elif rule["mode"] == "drop":
                p = Promise()

                async def blackhole():
                    await self.loop.sleep(self.FAULT_DETECT_DELAY)
                    p.fail(BrokenPromise(
                        f"{service}.{method} to {addr} dropped (fault rule)"))

                self.loop.spawn(blackhole(), name="fault.drop")
                return p.future
            else:  # delay
                p = Promise()
                delay = rule["delay_s"]

                async def deferred():
                    await self.loop.sleep(delay)
                    self._send_call(p, addr, service, method, args, kwargs)

                self.loop.spawn(deferred(), name="fault.delay")
                return p.future
        p = Promise()
        self._send_call(p, addr, service, method, args, kwargs)
        return p.future

    def _send_call(self, p: Promise, addr: tuple, service: str, method: str,
                   args: tuple, kwargs: dict | None = None) -> None:
        try:
            self._next_id += 1
            msg_id = self._next_id
            # Serialize BEFORE registering: a TypeError here must not leave
            # a dead pending entry that only a disconnect would release.
            # Kwargs ride as a trailing element; peers without them (the C
            # client) send the 5-element form, which _dispatch also accepts.
            msg = (_REQ, msg_id, service, method, list(args))
            if span_sink(self.loop) is not None:
                # Stage rpc_inbound (obs/span.py): while THIS process
                # traces, the frame carries its send stamp on the span
                # clock as one more trailing element; a receiver that
                # does not know it ignores it (_on_frame reads rest[:4]).
                msg += (kwargs or None, span_now(self.loop))
            elif kwargs:
                msg += (kwargs,)
            frame = wire.dumps(msg)
            conn = self._connect(addr)
            conn.pending[msg_id] = p
            key = id(p.future)
            self._call_sites[key] = (conn, msg_id)
            p.future.add_done_callback(
                lambda _f: self._call_sites.pop(key, None))
            try:
                conn.send_frame(frame)
            except FdbError:
                conn.pending.pop(msg_id, None)  # oversized frame: fail only us
                raise
        except OSError as e:
            p.fail(BrokenPromise(f"connect to {addr} failed: {e}"))
        except TypeError as e:  # unserializable argument — not retryable
            p.fail(FdbError(f"unserializable RPC argument: {e}", code=1500))
        except FdbError as e:  # incl. BrokenPromise, oversized-frame
            p.fail(e)

    def abandon_call(self, fut) -> bool:
        """Forget an in-flight request whose caller has given up on the
        reply (server.bounded_rpc timeout over a black-holed link, where
        the connection stays open so nothing ever fails the promise):
        drops the conn's pending-reply registration, so an hour-long
        partition probed at 1 Hz cannot accumulate one pending promise
        per sweep. A reply that still arrives after heal is dropped by
        _on_frame ('a request we gave up on')."""
        site = self._call_sites.pop(id(fut), None)
        if site is None:
            return False
        conn, msg_id = site
        conn.pending.pop(msg_id, None)
        return True

    # -- dispatch ---------------------------------------------------------

    def _on_frame(self, conn: _Conn, frame: bytes) -> None:
        sink = span_sink(self.loop)
        if sink is None:
            kind, msg_id, *rest = wire.loads(frame)
        else:
            # Stage rpc_decode (obs/span.py): decoding one frame, serial
            # with everything else this process does. Requests only: a
            # reply's decode belongs to the caller's stages.
            with stage_timer(None, "rpc_decode") as timed:
                kind, msg_id, *rest = wire.loads(frame)
            if kind == _REQ:
                sink.stage_tick("rpc_decode", timed.seconds)
                if len(rest) > 4 and rest[4] is not None:
                    # Stage rpc_inbound: the sender's stamp to here, decode
                    # included — its flush, the wire, and the socket
                    # buffer while this thread was busy with something
                    # else. One host, one clock; clamped against skew.
                    sink.stage_tick(
                        "rpc_inbound:" + rest[0] + "." + rest[1],
                        max(0.0, span_now(self.loop) - rest[4]))
        if kind == _REQ:
            service, method, args = rest[:3]
            kwargs = rest[3] if len(rest) > 3 else None
            self._dispatch(conn, msg_id, service, method, args, kwargs)
        else:
            ok, value = rest
            p = conn.pending.pop(msg_id, None)
            if p is None:
                return  # reply for a request we gave up on
            if ok:
                p.send(value)
            else:
                p.fail(value if isinstance(value, FdbError) else FdbError(str(value)))

    def _dispatch(self, conn: _Conn, msg_id: int, service: str, method: str,
                  args: list, kwargs: dict | None = None) -> None:
        def reply(ok: bool, value) -> None:
            if conn.closed:
                return
            try:
                conn.send_frame(wire.dumps((_RSP, msg_id, ok, value)))
            except (TypeError, FdbError) as e:  # FdbError incl. BrokenPromise
                if ok:  # unserializable/oversized result: report, don't vanish
                    try:
                        conn.send_frame(wire.dumps(
                            (_RSP, msg_id, False, FdbError(str(e), code=1500))
                        ))
                    except FdbError:
                        pass

        entry = self._services.get(service)
        if entry is None:
            reply(False, FdbError(f"no service {service}.{method}", code=1500))
            return
        obj, allow = entry
        if method not in allow:
            reply(False, FdbError(f"no service {service}.{method}", code=1500))
            return
        try:
            fn = getattr(obj, method)
            res = fn(*args, **(kwargs or {}))
        except AttributeError:
            reply(False, FdbError(f"no method {service}.{method}", code=1500))
            return
        except FdbError as e:
            reply(False, e)
            return
        except Exception as e:  # noqa: BLE001 — faults must cross the wire
            reply(False, FdbError(f"{type(e).__name__}: {e}", code=1500))
            return
        if hasattr(res, "__await__") or isinstance(res, Future):
            task = self.loop.spawn(res, name=f"rpc.{service}.{method}")

            def on_done(f: Future) -> None:
                if f.is_error():
                    e = f.exception()
                    reply(False, e if isinstance(e, FdbError)
                          else FdbError(f"{type(e).__name__}: {e}", code=1500))
                else:
                    reply(True, f.result())

            task.add_done_callback(on_done)
        else:
            reply(True, res)

    # -- lifecycle --------------------------------------------------------

    def _on_conn_closed(self, conn: _Conn) -> None:
        self._all_conns.discard(conn)
        for addr, c in list(self._conns.items()):
            if c is conn:
                del self._conns[addr]
        if conn.outbound_addr is not None:
            if conn.got_bytes:
                # The peer was genuinely up: a later death is news, not
                # a dead-dial streak — reset the backoff ladder.
                self._dial_backoff.pop(conn.outbound_addr, None)
            else:
                self._note_dial_failed(conn.outbound_addr)

    def close(self) -> None:
        self.loop.unregister(self._listener)
        try:
            self._listener.close()
        except OSError:
            pass
        for conn in list(self._all_conns):
            conn.close()


class TcpRelay:
    """Interposing TCP relay: the deployed chaos harness's partition
    injector (the socket-level twin of sim/network.py's partition/clog).

    The relay sits BETWEEN a role process and everyone who dials it: the
    cluster spec advertises the relay's listen address while the role
    binds a private port (server.py --bind), so every connection to the
    role — clients, peers, the controller's heartbeats — crosses the
    relay. Unlike the admin inject_fault rule (installed INSIDE the
    victim, outbound-only, gone when the process dies), the relay lives
    in the harness process and cuts BOTH directions of a link no matter
    what state the role is in (running, SIGSTOPped, dead).

    Modes:
    - ``pass``      splice bytes both ways (transparent)
    - ``drop``      black hole: connections stay OPEN but no byte moves —
                    peers' RPCs hang exactly like a packets-vanish
                    partition (nothing is read, so no data is lost and a
                    later heal resumes the frame stream intact)
    - ``cut``       connection death: every live splice is closed and new
                    connections are accepted-then-closed (peers observe
                    resets/EOF — the crashed-link observable)
    - ``delay``     forward each chunk after ``delay_s`` (a clogged link)

    Thread-based on purpose: the harness's event loop is busy driving
    the workload, and a relay must keep cutting links even while that
    loop is blocked in a long client call."""

    BUF = 1 << 16
    POLL_S = 0.05  # mode-change latency while parked in drop mode

    def __init__(self, target: tuple, host: str = "127.0.0.1",
                 port: int = 0, mode: str = "pass", delay_s: float = 0.05):
        self.target = (target[0], int(target[1]))
        self._mode = mode
        self.delay_s = float(delay_s)
        self._listener = socket.create_server((host, port))
        self._listener.settimeout(self.POLL_S)
        self.addr = self._listener.getsockname()
        self._pairs: set[tuple] = set()  # (client_sock, upstream_sock)
        self._lock = threading.Lock()
        self._closed = False
        self.conns_accepted = 0
        self.bytes_forwarded = 0
        self._accepter = threading.Thread(
            target=self._accept_loop, name=f"relay-accept:{self.addr[1]}",
            daemon=True)
        self._accepter.start()

    # -- control (harness-facing; thread-safe) ---------------------------

    @property
    def mode(self) -> str:
        return self._mode

    def set_mode(self, mode: str, delay_s: "float | None" = None) -> None:
        if mode not in ("pass", "drop", "cut", "delay"):
            raise ValueError(f"unknown relay mode {mode!r}")
        if delay_s is not None:
            self.delay_s = float(delay_s)
        self._mode = mode
        if mode == "cut":
            self._close_pairs()

    def heal(self) -> None:
        self.set_mode("pass")

    def close(self) -> None:
        self._closed = True
        try:
            self._listener.close()
        except OSError:
            pass
        self._close_pairs()

    def _close_pairs(self) -> None:
        with self._lock:
            pairs, self._pairs = set(self._pairs), set()
        for a, b in pairs:
            for s in (a, b):
                try:
                    s.close()
                except OSError:
                    pass

    # -- data plane ------------------------------------------------------

    def _accept_loop(self) -> None:
        while not self._closed:
            try:
                client, _peer = self._listener.accept()
            except socket.timeout:
                continue
            except OSError:
                return  # listener closed
            self.conns_accepted += 1
            if self._mode == "cut":
                try:
                    client.close()
                except OSError:
                    pass
                continue
            try:
                upstream = socket.create_connection(self.target, timeout=5.0)
            except OSError:
                try:
                    client.close()
                except OSError:
                    pass
                continue
            pair = (client, upstream)
            with self._lock:
                self._pairs.add(pair)
            for src, dst in ((client, upstream), (upstream, client)):
                threading.Thread(
                    target=self._splice, args=(pair, src, dst),
                    name=f"relay-splice:{self.addr[1]}", daemon=True,
                ).start()

    def _send_all(self, dst: socket.socket, data: bytes) -> bool:
        """sendall that tolerates the POLL_S socket timeout both splice
        threads leave on the pair (a slow receiver must backpressure,
        not kill the link) AND honors a drop installed mid-chunk: the
        unsent remainder stalls until heal, or a partition's first
        moment could leak up to a chunk of bytes through a thread
        parked here. False → connection is gone."""
        off = 0
        while off < len(data):
            if self._closed or self._mode == "cut":
                return False
            if self._mode == "drop":
                time.sleep(self.POLL_S)
                continue
            try:
                off += dst.send(data[off:])
            except socket.timeout:
                continue
            except OSError:
                return False
        return True

    def _splice(self, pair, src: socket.socket, dst: socket.socket) -> None:
        src.settimeout(self.POLL_S)
        try:
            while not self._closed:
                mode = self._mode
                if mode == "drop":
                    # Park WITHOUT reading: the sender's bytes stay queued
                    # (kernel buffers, then the sender blocks) so a heal
                    # resumes the stream with nothing lost — a relay that
                    # read-and-discarded would desync the frame stream
                    # the moment the partition healed.
                    time.sleep(self.POLL_S)
                    continue
                try:
                    data = src.recv(self.BUF)
                except socket.timeout:
                    continue
                except OSError:
                    break
                if not data:
                    break
                # Re-check AFTER the recv: a drop installed while this
                # thread was parked in recv() must stall bytes in hand
                # (forwarded only on heal — held, never lost), or the
                # first ~POLL_S of every partition would leak.
                while self._mode == "drop" and not self._closed:
                    time.sleep(self.POLL_S)
                if self._closed or self._mode == "cut":
                    break
                if self._mode == "delay":
                    time.sleep(self.delay_s)
                if not self._send_all(dst, data):
                    break
                self.bytes_forwarded += len(data)
        finally:
            with self._lock:
                self._pairs.discard(pair)
            for s in pair:
                try:
                    s.close()
                except OSError:
                    pass
