"""SocketCluster: spawn and SUPERVISE a real multi-process cluster over TCP.

One helper shared by the open-loop bench, the chaos harness
(loadgen/chaos.py), the fast-battery smoke tests and scripts. Builds a
cluster spec (N proxy processes — the horizontal scale-out axis — plus
sequencer/resolver/tlog/storage/ratekeeper, optionally a controller for
managed recruitment), boots one OS process per role instance
(`python -m foundationdb_tpu.server`), waits for every readiness line,
and tears down gracefully (admin shutdown RPC, SIGKILL only as a last
resort) with an explicit leak check: every process reaped, every
listening port released, no orphaned children.

Beyond boot/teardown, this is the chaos harness's ROLE-LEVEL SUPERVISOR
(the fdbmonitor analogue the nemesis catalog maps onto):

- per-role persistent data dirs (``data_dirs=True``): each process gets
  ``--data-dir <workdir>/data/<role><i>`` so a SIGKILLed role restarts
  from its on-disk state (tlog disk queue, storage sqlite) through the
  existing ``from_disk``/``begin_epoch``/``tlog_adopt`` handshake;
- ``kill_role`` (SIGKILL — real process death, no goodbye),
  ``pause_role``/``resume_role`` (SIGSTOP/SIGCONT — an alive-but-frozen
  process, the failure detector's hardest case), ``restart_role``
  (reboot the same role+index+data-dir, what fdbmonitor does);
- an interposing TCP relay per instance of ``relay_roles``
  (runtime/net.TcpRelay): the spec advertises the relay's port while the
  role binds a private one (server.py --bind), so ``partition_role`` can
  black-hole/cut/delay EVERY connection to the role — both directions,
  regardless of the victim's state — and ``heal_role`` undoes it.

Process stdout/stderr go to per-process log files in the work dir (never a
pipe: a chatty supervisor under overload would fill a 64 KiB pipe buffer
and deadlock the role behind its own logging). Every process starts in its
OWN session/process group, so the leak check can see (and the teardown can
reap) children a crashed role left behind — a port check alone is
vacuously green for a crashed process whose forked child kept running.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time

from dataclasses import dataclass, field

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _group_has_running(pgid: int) -> bool:
    """Does process group `pgid` contain any non-zombie member? (/proc
    scan; if /proc is unavailable, the killpg(0) answer the caller
    already has stands — i.e. report alive.)"""
    try:
        pids = [p for p in os.listdir("/proc") if p.isdigit()]
    except OSError:
        return True
    for pid in pids:
        try:
            with open(f"/proc/{pid}/stat", "rb") as f:
                stat = f.read()
        except OSError:
            continue
        # pid (comm) state ppid pgrp ... — comm may embed spaces/parens;
        # fields are unambiguous after the LAST ')'.
        fields = stat.rsplit(b")", 1)[-1].split()
        if len(fields) >= 3 and fields[0] != b"Z" \
                and int(fields[2]) == pgid:
            return True
    return False


_PORT_LO = 10000  # above the well-known services, below any ephemeral range
_PORT_STRETCH = 64  # consecutive pids start their walks this far apart
_claimed: list[socket.socket] = []  # held until this process exits
_walked = 0


def free_ports(n: int) -> list[int]:
    """`n` localhost ports that nobody else is given before the caller's
    roles bind them, or between a role's death and its restart. They lie
    BELOW the kernel's ephemeral range, so neither a bind to port 0 nor an
    outgoing connection can be handed one. Each is claimed for the rest of
    this process's life under a name in the abstract unix namespace
    (atomic across processes, released by the kernel when the process
    ends), so no other caller of this function gets it either. And each
    is probed by bind, so a port that a foreign program holds is passed
    over. Every process starts its walk at a stretch of its own (by pid);
    the claim, not the stretch, is what keeps two walks apart."""
    global _walked
    with open("/proc/sys/net/ipv4/ip_local_port_range") as f:
        span = int(f.read().split()[0]) - _PORT_LO
    ports: list[int] = []
    while len(ports) < n:
        if _walked >= span:
            raise RuntimeError(
                f"no free port left in {_PORT_LO}..{_PORT_LO + span - 1}")
        port = _PORT_LO + (os.getpid() * _PORT_STRETCH + _walked) % span
        _walked += 1
        claim = socket.socket(socket.AF_UNIX)
        try:
            claim.bind(f"\0foundationdb_tpu.port.{port}")
            with socket.socket() as probe:
                probe.bind(("127.0.0.1", port))
        except OSError:
            claim.close()
            continue
        _claimed.append(claim)
        ports.append(port)
    return ports


def build_spec(proxies: int = 2, tlogs: int = 1, storages: int = 1,
               resolvers: int = 1, ratekeeper: bool = True,
               engine: str = "cpu", extra: "dict | None" = None,
               managed: bool = False,
               resolver_splits: "list[bytes] | None" = None) -> dict:
    """A cluster spec dict with fresh localhost ports (server.py shape).
    ``resolver_splits``: the resolvers - 1 keys at which the resolvers'
    ranges part (server.resolver_shard_map; hex in the spec); None leaves
    the split to KeyShardMap.uniform, which goes by first byte.
    ``managed=True`` adds a controller process — chain-role failures then
    heal with a generation change instead of needing a full bounce."""
    n = (1 + resolvers + tlogs + storages + proxies
         + (1 if ratekeeper else 0) + (1 if managed else 0))
    ports = iter(free_ports(n))
    spec = {
        "sequencer": [f"127.0.0.1:{next(ports)}"],
        "resolver": [f"127.0.0.1:{next(ports)}" for _ in range(resolvers)],
        "tlog": [f"127.0.0.1:{next(ports)}" for _ in range(tlogs)],
        "storage": [f"127.0.0.1:{next(ports)}" for _ in range(storages)],
        "proxy": [f"127.0.0.1:{next(ports)}" for _ in range(proxies)],
        "ratekeeper": ([f"127.0.0.1:{next(ports)}"] if ratekeeper else []),
        "engine": engine,
    }
    if managed:
        spec["controller"] = [f"127.0.0.1:{next(ports)}"]
    if resolver_splits is not None:
        spec["resolver_splits"] = [k.hex() for k in resolver_splits]
    if extra:
        spec.update(extra)
    return spec


@dataclass
class _Proc:
    """One supervised role process."""

    name: str  # e.g. "tlog0"
    role: str
    index: int
    addr: tuple  # advertised (spec) address — the relay's, when relayed
    bind: "tuple | None"  # private bind address behind a relay, else None
    log_path: str
    data_dir: "str | None"
    popen: "subprocess.Popen | None" = None
    log_offset: int = 0  # readiness scan starts here (restart support)
    restarts: int = 0
    paused: bool = False
    # Process-group ids of RETIRED generations of this role (a restart
    # replaces popen; the killed generation's orphaned children live in
    # the OLD group — leak checks and teardown must keep chasing it).
    dead_pgids: list = field(default_factory=list)

    def alive(self) -> bool:
        return self.popen is not None and self.popen.poll() is None


class SocketCluster:
    """Context manager around one deployed cluster's OS processes."""

    # `ready` means compiled, and a resolver on the chip compiles cold when
    # the persistent cache is: 58 s from launch to `ready` on a TPU v5e
    # (PR 21's chip run; ~50 s of it compiles). Both hold three of those.
    BOOT_DEADLINE_S = 300.0
    READY_DEADLINE_S = 180.0  # per-process restart readiness

    def __init__(self, workdir: str, proxies: int = 2, tlogs: int = 1,
                 storages: int = 1, resolvers: int = 1,
                 ratekeeper: bool = True, engine: str = "cpu",
                 spec_extra: "dict | None" = None,
                 env: "dict | None" = None,
                 managed: bool = False,
                 data_dirs: bool = False,
                 relay_roles: tuple = (),
                 resolver_splits: "list[bytes] | None" = None):
        os.makedirs(workdir, exist_ok=True)
        self.workdir = workdir
        self.managed = managed
        self.data_dirs = data_dirs
        self.spec = build_spec(proxies, tlogs, storages, resolvers,
                               ratekeeper, engine, spec_extra, managed,
                               resolver_splits=resolver_splits)
        # A relayed role binds a private port behind its relay's.
        self._bind_ports = iter(free_ports(sum(
            len(self.spec.get(r) or []) for r in relay_roles)))
        self.spec_path = os.path.join(workdir, "cluster.json")
        with open(self.spec_path, "w") as f:
            json.dump(self.spec, f)
        self._env = dict(os.environ, **(env or {}))
        self.procs: list[_Proc] = []
        self.relays: dict[str, "object"] = {}  # name -> TcpRelay
        self._relay_roles = tuple(relay_roles)
        self._build_proc_table()

    def _build_proc_table(self) -> None:
        from foundationdb_tpu.server import ROLES, parse_addr
        from foundationdb_tpu.runtime.net import TcpRelay

        for role in ROLES:
            for i, addr_s in enumerate(self.spec.get(role) or []):
                name = f"{role}{i}"
                addr = parse_addr(addr_s)
                bind = None
                if role in self._relay_roles:
                    # The spec's (advertised) port belongs to the RELAY;
                    # the role binds a private port the relay forwards to.
                    bind = ("127.0.0.1", next(self._bind_ports))
                    self.relays[name] = TcpRelay(bind, host=addr[0],
                                                 port=addr[1])
                data_dir = None
                if self.data_dirs:
                    data_dir = os.path.join(self.workdir, "data", name)
                    os.makedirs(data_dir, exist_ok=True)
                self.procs.append(_Proc(
                    name=name, role=role, index=i, addr=addr, bind=bind,
                    log_path=os.path.join(self.workdir, f"{name}.log"),
                    data_dir=data_dir,
                ))

    def proc(self, name: str) -> _Proc:
        for p in self.procs:
            if p.name == name:
                return p
        raise KeyError(f"no role process {name!r} in this cluster")

    def _argv(self, p: _Proc) -> list[str]:
        argv = [sys.executable, "-m", "foundationdb_tpu.server",
                "--cluster", self.spec_path, "--role", p.role,
                "--index", str(p.index)]
        if p.data_dir:
            argv += ["--data-dir", p.data_dir]
        if p.bind:
            argv += ["--bind", f"{p.bind[0]}:{p.bind[1]}"]
        return argv

    def _env_for(self, p: _Proc) -> dict:
        """One environment per role. A chip belongs to one process, so
        only a resolver whose spec says engine "tpu" may reach one: it
        inherits the caller's environment as it stands (a caller that
        wants that resolver on the CPU backend says JAX_PLATFORMS=cpu
        itself, and the role refuses to boot on a CPU it was not told
        about). A LONE such resolver therefore sees every chip of the
        host, which is what a spec with `resolver_mesh` counts on: its
        one process shards its history over them (server.resolver_mesh).
        With several such resolvers on this host each is bound to
        the chip of its own index — libtpu's TPU_VISIBLE_CHIPS, with the
        process bounds that make one chip a whole topology — and one
        whose chip does not exist fails its boot, which fails start().
        Every other role, and nothing else, is pinned to the CPU."""
        if p.role != "resolver" or self.spec.get("engine") != "tpu":
            return dict(self._env, JAX_PLATFORMS="cpu")
        if len(self.spec["resolver"]) == 1:
            return self._env
        return dict(self._env, TPU_VISIBLE_CHIPS=str(p.index),
                    TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
                    TPU_PROCESS_BOUNDS="1,1,1")

    # -- lifecycle --------------------------------------------------------

    def _launch(self, p: _Proc) -> None:
        if p.popen is not None:
            # The replaced generation's process group may still hold
            # orphaned children — keep its pgid on the chase list.
            p.dead_pgids.append(p.popen.pid)
        # Append mode: restarts keep one log per role instance, and the
        # readiness scan (log_offset) never re-reads an old generation's
        # "ready" line as the new process's.
        p.log_offset = (os.path.getsize(p.log_path)
                        if os.path.exists(p.log_path) else 0)
        log_f = open(p.log_path, "ab")
        p.popen = subprocess.Popen(
            self._argv(p), cwd=REPO, env=self._env_for(p),
            stdout=log_f, stderr=subprocess.STDOUT,
            # Own session = own process group: the leak check can see a
            # crashed role's surviving children, teardown can reap them.
            start_new_session=True,
        )
        log_f.close()  # the child holds the fd
        p.paused = False

    def role_ready(self, name: str) -> bool:
        """Has this process printed its readiness line since (re)launch?"""
        p = self.proc(name)
        if not p.alive():
            return False
        try:
            with open(p.log_path, "rb") as f:
                f.seek(p.log_offset)
                # A line of its own: libtpu and JAX log to the same file.
                return any(line.startswith(b"ready ")
                           for line in f.read().splitlines())
        except OSError:
            return False

    def log_tail(self, name: str, n_bytes: int = 2000) -> str:
        """The end of one role's log (all its generations share the file)."""
        try:
            with open(self.proc(name).log_path, "rb") as f:
                f.seek(max(0, os.fstat(f.fileno()).st_size - n_bytes))
                return f.read().decode(errors="replace")
        except OSError as e:
            return f"<no log: {e}>"

    def wait_ready(self, name: str,
                   timeout_s: "float | None" = None) -> None:
        p = self.proc(name)
        deadline = time.monotonic() + (timeout_s or self.READY_DEADLINE_S)
        while True:
            if self.role_ready(name):
                return
            if p.popen is not None and p.popen.poll() is not None:
                raise RuntimeError(
                    f"{name} exited rc={p.popen.returncode} during boot; "
                    f"{p.log_path} ends:\n{self.log_tail(name)}")
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"timed out waiting for {name} ready; "
                    f"{p.log_path} ends:\n{self.log_tail(name)}")
            time.sleep(0.05)

    def start(self) -> "SocketCluster":
        try:
            for p in self.procs:
                self._launch(p)
            t0 = time.monotonic()
            for p in self.procs:
                remaining = self.BOOT_DEADLINE_S - (time.monotonic() - t0)
                self.wait_ready(p.name, timeout_s=max(1.0, remaining))
        except BaseException:
            # A role that exits or stalls during boot must not leak the
            # already-launched rest of the cluster (or the relays'
            # listener threads): a `with SocketCluster(...)` caller
            # never reaches __exit__ when __enter__ raises.
            self.kill()
            raise
        return self

    # -- chaos supervisor surface (loadgen/chaos.py) ----------------------

    def kill_role(self, name: str, sig: int = signal.SIGKILL) -> float:
        """Real process death: send `sig` (default SIGKILL — no shutdown
        RPC, no flush, exactly what the OOM killer or a kernel panic
        delivers) to the ROLE process only — a real crash does not take
        the role's forked children with it, which is precisely what the
        crashed-process leak check exists to catch (teardown's group
        kill is the mop-up, not the fault model). Returns the wall stamp
        of the kill (chaos MTTR anchors detection latency on it)."""
        p = self.proc(name)
        stamp = time.time()
        if p.alive():
            p.popen.send_signal(sig)
            if p.paused and sig != signal.SIGKILL:
                # A SIGSTOPped process queues SIGTERM and never acts on
                # it: without the SIGCONT the wait below blocks forever
                # (SIGKILL needs no help — the kernel reaps stopped
                # processes on it directly).
                p.popen.send_signal(signal.SIGCONT)
            if sig in (signal.SIGKILL, signal.SIGTERM):
                p.popen.wait()
                p.paused = False
        return stamp

    def pause_role(self, name: str) -> float:
        """SIGSTOP: the process stays alive but answers nothing — the
        failure detector's hardest case (no connection death, RPCs just
        hang; the controller's probe timeout is what notices)."""
        p = self.proc(name)
        if p.alive():
            p.popen.send_signal(signal.SIGSTOP)
            p.paused = True
        return time.time()

    def resume_role(self, name: str) -> None:
        p = self.proc(name)
        if p.alive() and p.paused:
            p.popen.send_signal(signal.SIGCONT)
        p.paused = False

    def restart_role(self, name: str, wait: bool = True,
                     timeout_s: "float | None" = None) -> None:
        """Reboot a (dead) role from its on-disk state — fdbmonitor's
        restart-on-exit. The new process recovers its disk queue
        (TLog.from_disk) and the controller folds it into the next
        generation via the begin_epoch/tlog_adopt handshake."""
        p = self.proc(name)
        if p.alive():
            self.kill_role(name)
        p.restarts += 1
        self._launch(p)
        if wait:
            self.wait_ready(name, timeout_s)

    def partition_role(self, name: str, mode: str = "drop",
                       delay_s: float = 0.05) -> float:
        """Socket-level partition of one role via its interposing relay:
        `drop` black-holes (connections hang), `cut` resets them,
        `delay` clogs. Requires the role in `relay_roles`."""
        relay = self.relays.get(name)
        if relay is None:
            raise KeyError(
                f"{name} has no relay — boot the cluster with "
                f"relay_roles=({self.proc(name).role!r},)")
        relay.set_mode(mode, delay_s=delay_s)
        return time.time()

    def heal_role(self, name: str) -> None:
        relay = self.relays.get(name)
        if relay is not None:
            relay.heal()

    def heal_all(self) -> None:
        for relay in self.relays.values():
            relay.heal()

    # -- leak checking ----------------------------------------------------

    def _port_open(self, addr: tuple) -> bool:
        s = socket.socket()
        s.settimeout(0.2)
        try:
            s.connect(addr)
            return True
        except OSError:
            return False
        finally:
            s.close()

    @staticmethod
    def _pgid_running(pgid: int) -> bool:
        """Any RUNNING process left in process group `pgid`? Catches
        orphaned children of a CRASHED role (e.g. a background prober
        the role forked) that a port check alone can never see. The
        killpg(0) probe comes FIRST — on hosts without /proc the
        fallback in _group_has_running assumes the group exists.
        Zombies don't count: in a container without a reaping init, a
        killed orphan lingers as a defunct table entry forever — it
        holds no ports, no CPU, and cannot be killed again, so flagging
        it would make every teardown red with nothing actionable."""
        try:
            os.killpg(pgid, 0)
        except ProcessLookupError:
            return False
        except PermissionError:
            return True  # exists but not ours — still alive
        return _group_has_running(pgid)

    def _group_alive(self, p: _Proc) -> bool:
        return p.popen is not None and self._pgid_running(p.popen.pid)

    def leak_report(self, dead_only: bool = True) -> dict:
        """What a crashed or stopped cluster left behind: for every role
        process that is DEAD (or all, with dead_only=False), is its REAL
        port still accepting (an orphan holds it — for relayed roles the
        private bind port is checked, never the harness-owned relay,
        which would be vacuously 'bound'), and does its process group
        still have live members? The old check only ran inside a clean
        shutdown() and only connect-probed spec addresses, so a role
        that died before stop() — or died leaving children — passed
        vacuously (ISSUE 14 satellite)."""
        ports, orphans, checked = [], [], []
        for p in self.procs:
            # Retired generations' groups are chased regardless of the
            # CURRENT process's liveness: a killed-then-restarted role
            # is alive, its dead predecessor's orphans are not less
            # leaked for it. Groups observed fully dead are PRUNED — an
            # exited group can never regain members, and keeping the
            # pgid risks a later pid-wraparound collision (an unrelated
            # group misreported, or worse, group-killed at teardown).
            p.dead_pgids = [g for g in p.dead_pgids
                            if self._pgid_running(g)]
            if p.dead_pgids:
                orphans.append(p.name)
            if dead_only and p.alive():
                continue
            checked.append(p.name)
            real = p.bind or p.addr
            if self._port_open(real):
                ports.append({"name": p.name, "port": real[1]})
            if not p.alive() and self._group_alive(p) \
                    and p.name not in orphans:
                orphans.append(p.name)
        return {"checked": checked, "ports_still_bound": ports,
                "orphan_groups": orphans}

    # -- teardown ---------------------------------------------------------

    def shutdown(self, timeout_s: float = 15.0) -> dict:
        """Graceful stop: admin shutdown RPC to every live process, reap,
        then verify nothing leaked — all processes (and their process
        groups) exited, all REAL ports released, crashed roles included.
        Returns {"exit_codes": {...}, "killed": [...]}."""
        from foundationdb_tpu.runtime.net import NetTransport, RealLoop

        killed: list[str] = []
        live = [p for p in self.procs if p.alive()]
        if live:
            self.heal_all()  # partitioned roles must still hear shutdown
            for p in live:
                if p.paused:
                    self.resume_role(p.name)  # a stopped process can't exit
            loop = RealLoop()
            t = NetTransport(loop)
            for p in live:
                try:
                    loop.run_until(
                        t.endpoint(p.bind or p.addr, "admin").shutdown(),
                        timeout=5.0)
                except Exception:
                    pass  # dead/wedged: the SIGKILL pass below reaps it
            t.close()
        deadline = time.monotonic() + timeout_s
        for p in self.procs:
            if p.popen is None:
                continue
            try:
                p.popen.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                killed.append(p.name)
                try:
                    os.killpg(p.popen.pid, signal.SIGKILL)
                except ProcessLookupError:
                    p.popen.kill()
                p.popen.wait()
        codes = {p.name: p.popen.returncode for p in self.procs
                 if p.popen is not None}
        report = self.leak_report(dead_only=False)
        leaks = report["ports_still_bound"] + report["orphan_groups"]
        if leaks:
            # Keep the proc table: clearing it here would leave the
            # caller's mop-up kill() with nothing to reap — the exact
            # vacuous-teardown hole this check exists to close.
            raise RuntimeError(f"cluster leaked after shutdown: {report}")
        self._close_relays()
        self.procs = []
        return {"exit_codes": codes, "killed": killed}

    def kill(self) -> dict:
        """Hard teardown (exception path): SIGKILL every process GROUP —
        orphaned children of crashed AND restarted-over roles included —
        and reap. Returns the leak_report of what even that left."""
        for p in self.procs:
            if p.popen is None:
                continue
            if p.paused:
                self.resume_role(p.name)
            # Dead-generation groups are re-probed before the kill so a
            # recycled pgid (pid wraparound) can't take out an
            # unrelated process group.
            chase = [g for g in p.dead_pgids if self._pgid_running(g)]
            for pgid in [p.popen.pid] + chase:
                try:
                    os.killpg(pgid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
            p.dead_pgids = []
            if p.popen.poll() is None:
                p.popen.kill()
        for p in self.procs:
            if p.popen is not None:
                p.popen.wait()
        report = self.leak_report(dead_only=False)
        self._close_relays()
        self.procs = []
        return report

    def _close_relays(self) -> None:
        for relay in self.relays.values():
            relay.close()
        self.relays = {}

    def __enter__(self) -> "SocketCluster":
        return self.start()

    def __exit__(self, exc_type, _exc, _tb) -> None:
        if exc_type is None:
            try:
                self.shutdown()
            except RuntimeError:
                # Leak detected (crashed role / orphan group): mop up —
                # shutdown kept the proc table for exactly this — then
                # still surface the leak to the caller.
                self.kill()
                raise
        else:
            self.kill()

    # -- client surfaces --------------------------------------------------

    def open_client(self):
        """(loop, transport, db) against this cluster — the Python client
        stack over real sockets (cli.open_cluster)."""
        from foundationdb_tpu.cli import open_cluster

        return open_cluster(self.spec_path)

    def ratekeeper_ep(self, t):
        """Ratekeeper endpoint on transport `t` (None when not deployed)."""
        from foundationdb_tpu.server import parse_addr

        rk = self.spec.get("ratekeeper") or []
        return t.endpoint(parse_addr(rk[0]), "ratekeeper") if rk else None

    def controller_ep(self, t):
        """Controller endpoint on transport `t` (None when unmanaged)."""
        from foundationdb_tpu.server import parse_addr

        cc = self.spec.get("controller") or []
        return t.endpoint(parse_addr(cc[0]), "controller") if cc else None

    def admin_ep(self, t, name: str):
        """Admin endpoint of one role process (inject_fault/clear_faults/
        obs_snapshot), via its REAL address — reachable even when the
        role's relay is partitioned."""
        p = self.proc(name)
        return t.endpoint(p.bind or p.addr, "admin")
