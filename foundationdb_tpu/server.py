"""Deployable server: launch cluster roles as OS processes over real TCP.

The reference's `fdbserver` binary (fdbserver/fdbserver.actor.cpp) runs any
role (or several) in one process, wired together by the cluster file. This
is that entry point for the TPU framework: the SAME role objects the sim
drives (SURVEY §2) served over runtime/net.py's transport.

    python -m foundationdb_tpu.server --cluster cluster.json --role storage --index 0

Cluster spec (the cluster-file analogue) is a JSON file every process and
client reads:

    {
      "sequencer": ["127.0.0.1:4500"],
      "resolver":  ["127.0.0.1:4510"],
      "tlog":      ["127.0.0.1:4540", "127.0.0.1:4541"],
      "storage":   ["127.0.0.1:4550", "127.0.0.1:4551"],
      "proxy":     ["127.0.0.1:4520", "127.0.0.1:4521"],
      "ratekeeper": [],
      "engine": "cpu"
    }

Wiring is static from the spec (v1: no recruitment over TCP — the sim owns
failure/recovery testing; this is the deployment data plane):

- `proxy` is the stateless class: each proxy process hosts a CommitProxy
  AND a GrvProxy (reference: stateless fdbserver class), plus a ReadRouter
  that forwards get/get_range/watch to the owning storage shard so
  single-connection clients (the native C client) need only one address.
- storage[i] has tag i and pulls from tlog[i % n_tlogs]; commit proxies
  push every batch to every tlog (replicated logs, as the sim does).
- shard maps are derived deterministically from the spec, so every process
  and client agrees without a metadata service: `storage_shard_map` is
  KeyShardMap.uniform over the storage count, `resolver_shard_map` the
  spec's own `resolver_splits` (N-1 sorted keys, hex) where it states
  them and KeyShardMap.uniform over the resolver count where it does not.
  `uniform` splits by FIRST BYTE (0x40, 0x80, 0xC0 for four): even for
  keys spread over the byte range, and one resolver's work for a key set
  that shares a prefix — such a deployment states its splits. Or it
  lists ONE resolver and says `"resolver_mesh": N` (engine "tpu"): that
  process shards its history over N chips of its host and moves the
  splits itself (`resolver_mesh`, `make_conflict_set`); the proxies see
  one resolver and clip nothing.

Service names are unindexed ("sequencer", "tlog", ...): the address
already identifies the instance. The ReadRouter is also served under the
alias "storage0" for the C client's default service naming.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from foundationdb_tpu.runtime.flow import ActorCancelled, BrokenPromise, rpc
from foundationdb_tpu.runtime.net import NetTransport, RealLoop
from foundationdb_tpu.core.errors import FutureVersion
from foundationdb_tpu.runtime.shardmap import MAX_KEY, KeyShardMap, ring_teams

ROLES = ("sequencer", "resolver", "tlog", "storage", "proxy", "ratekeeper",
         "controller", "satellite_tlog")


def load_spec(path: str) -> dict:
    with open(path) as f:
        spec = json.load(f)
    for role in ("sequencer", "resolver", "tlog", "storage", "proxy"):
        if not spec.get(role):
            raise ValueError(f"cluster spec missing role {role!r}")
    _validate_regions(spec)
    resolver_shard_map(spec)  # bad resolver_splits fail every boot
    resolver_mesh(spec)  # and so does a mesh the deployment cannot have
    # Resolve key-material paths against the cluster file's directory at
    # LOAD time (the one choke point every entry point — server, cli,
    # dr_tool, tests — goes through), so consumers never depend on cwd.
    base = os.path.dirname(os.path.abspath(path))
    for k in ("authz_public_key", "authz_system_token"):
        if spec.get(k):
            p = spec[k]
            spec[k] = p if os.path.isabs(p) else os.path.join(base, p)
    return spec


REGION_CHAIN_ROLES = ("sequencer", "tlog", "resolver", "proxy")


def _validate_regions(spec: dict) -> None:
    """Multi-region deployed config (reference: DatabaseConfiguration
    `regions` + satellite TLog policy). Spec shape:

        "regions": {"pri": {role: [indices...]}, "rem": {...}},
        "satellite_tlog": ["host:port", ...]   # >= 1 required

    Chain-role indices must partition the role's address list between the
    two regions (a process serves exactly one region); storage indices
    must partition with EQUAL counts — shard j's team is (pri_storage[j],
    rem_storage[j]), the cross-region pairing the sim uses. Managed mode
    only (a controller drives region failover; static wiring can't)."""
    regions = spec.get("regions")
    if not regions:
        return
    if set(regions) != {"pri", "rem"}:
        raise ValueError(
            f"regions must be exactly {{'pri','rem'}}, got {sorted(regions)}")
    if not spec.get("controller"):
        raise ValueError("multi-region requires managed mode (a controller)")
    if not spec.get("satellite_tlog"):
        raise ValueError(
            "multi-region requires >= 1 satellite_tlog (the synchronous "
            "off-region stream copy that makes region failover lossless)")
    for role in REGION_CHAIN_ROLES + ("storage",):
        pri = list(regions["pri"].get(role, []))
        rem = list(regions["rem"].get(role, []))
        all_idx = sorted(pri + rem)
        if all_idx != list(range(len(spec[role]))):
            raise ValueError(
                f"regions must partition {role} indices 0.."
                f"{len(spec[role]) - 1}; got pri={pri} rem={rem}")
        if not pri or not rem:
            raise ValueError(f"each region needs >= 1 {role}")
        if role == "storage" and len(pri) != len(rem):
            raise ValueError(
                "regions need EQUAL storage counts (shard j's team is "
                f"(pri[j], rem[j])); got {len(pri)} vs {len(rem)}")


def _make_tenant_mirror(loop, t, spec: dict, storage_map, spawn):
    """TenantMapMirror for a deployed process when authz is on: storage
    endpoints from the spec, refreshed with the spec's system token.
    `spawn(name, make_coro)` is the caller's task-spawning convention
    (Worker._spawn ties the mirror's life to the generation;
    _supervise for boot-time roles)."""
    if not spec.get("authz_public_key"):
        return None
    from foundationdb_tpu.runtime.authz import TenantMapMirror

    tok = _system_token(spec)
    if tok is None:
        # Fail LOUD at boot, not silently at every refresh: without the
        # system token the mirror's own reads are denied at storage,
        # its view never forms, and every tenant-bound token fails
        # closed with zero diagnostics (review finding).
        print("WARNING: authz_public_key set without authz_system_token "
              "— the tenant-map mirror cannot read the map; tenant-bound "
              "tokens will be denied until the spec adds one.",
              file=sys.stderr, flush=True)
    eps = [t.endpoint(parse_addr(a), "storage") for a in spec["storage"]]
    mirror = TenantMapMirror(loop, eps, storage_map, token=tok)
    spawn("tenant_mirror.run", mirror.run)
    return mirror


def storage_shard_map(spec: dict) -> "KeyShardMap":
    """THE deployed storage map (reference: DatabaseConfiguration
    replication — `replicas` in the spec, default 1): shard i is owned
    by the k-member team {i, i+1, ...} so proxies tag every replica and
    clients/routers fail over between team members. One definition used
    by every deployed consumer (server roles, worker recruitment, cli,
    dr_tool) — maps diverging across processes would corrupt routing."""
    regions = spec.get("regions")
    if regions:
        # Cross-region teams: shard j lives on (pri storage j, rem
        # storage j) — the sim's multi-region pairing (sim/cluster.py
        # teams = [(i, n+i)]), generalized to arbitrary index layouts.
        pri = list(regions["pri"]["storage"])
        rem = list(regions["rem"]["storage"])
        return KeyShardMap.uniform(
            len(pri), teams=[(p, r) for p, r in zip(pri, rem)])
    n = len(spec["storage"])
    return KeyShardMap.uniform(
        n, teams=ring_teams(n, int(spec.get("replicas", 1))))


def resolver_shard_map(spec: dict,
                       n_live: "int | None" = None) -> "KeyShardMap":
    """THE deployed resolver map: resolver i checks the conflict ranges
    that fall in shard i. One definition for every deployed consumer (the
    static wiring, a managed worker's recruited proxy), as with
    storage_shard_map: proxies that split differently would send a read
    and the write it conflicts with to different resolvers.

    Where the spec states `resolver_splits` — N-1 strictly ascending
    keys, hex in the JSON, for its N resolvers — those are the bounds
    (reference: the resolver key ranges the master keeps, and moves with
    resolutionBalancing until the load is even; here the spec states
    where they lie). Where it states none, KeyShardMap.uniform: by first
    byte, which is even only for keys spread over the byte range. Fixed
    for the life of a generation either way: a resolver's history resets
    with the generation, so only then can a bound move without parting a
    read from the writes it must be checked against.

    ``n_live``: the resolvers of the generation being formed (managed
    mode recruits the live ones). With fewer than the spec's N the
    stated ranges are merged with their neighbours, evenly."""
    n = len(spec["resolver"])
    n_live = n if n_live is None else n_live
    splits = spec.get("resolver_splits")
    if splits is None:
        return KeyShardMap.uniform(n_live)
    try:
        keys = [bytes.fromhex(s) for s in splits]
    except (TypeError, ValueError):
        raise ValueError(
            f"resolver_splits must be hex strings, got {splits!r}") from None
    if len(keys) != n - 1:
        raise ValueError(
            f"resolver_splits has {len(keys)} keys; {n} resolvers need "
            f"{n - 1}")
    if any(not a < b for a, b in zip([b""] + keys, keys + [MAX_KEY])):
        raise ValueError(
            "resolver_splits must be strictly ascending keys inside "
            f"the keyspace, got {splits!r}")
    if n_live != n:
        keys = [keys[(j * n) // n_live - 1] for j in range(1, n_live)]
    return KeyShardMap(keys, tags=list(range(len(keys) + 1)))


def resolver_mesh(spec: dict) -> "int | None":
    """How many chips the spec's ONE resolver spans: `resolver_mesh`, N >= 2
    — upstream's `configure resolvers=N` served as one resolver process
    whose history is sharded by key range over N chips of its host, one
    shard a chip (parallel/sharded_resolver.py: every shard judges its own
    keys, the conflict bits are summed on the device before anything is
    painted, the splits follow the live history). None where the spec
    does not say: one engine on one chip, as ever.

    Refused here, at every role's boot (load_spec), as a bad
    `resolver_splits` is: the key with an engine other than "tpu" (only
    that engine has a mesh), with several resolver addresses (the proxies
    would clip every batch by `resolver_shard_map` and each process would
    shard its clip again: the mesh IS the four resolvers), or beside
    `resolver_splits` (the mesh moves its own). That the process sees N
    chips only the resolver can know: its own boot refuses
    (make_conflict_set)."""
    n = spec.get("resolver_mesh")
    if n is None:
        return None
    if not isinstance(n, int) or isinstance(n, bool) or n < 2:
        raise ValueError(
            f"resolver_mesh must be a whole number of chips, 2 or more, "
            f"got {n!r}")
    if spec.get("engine", "cpu") != "tpu":
        raise ValueError(
            f"resolver_mesh={n} needs engine 'tpu' (the only engine with "
            f"a mesh), got engine {spec.get('engine', 'cpu')!r}")
    if len(spec["resolver"]) != 1:
        raise ValueError(
            f"resolver_mesh={n} is ONE resolver over {n} chips; the spec "
            f"lists {len(spec['resolver'])} resolver addresses")
    if spec.get("resolver_splits") is not None:
        raise ValueError(
            f"resolver_mesh={n} moves its own splits (auto_reshard); "
            "resolver_splits beside it would state what nothing reads")
    return n


def _system_token(spec: dict) -> str | None:
    """Operator-minted system-scope authz token for in-process system
    actors (TimeKeeper) — spec key `authz_system_token`, a path to the
    token file (resolved by load_spec). With authz enabled, system
    (``\\xff``) writes require it."""
    path = spec.get("authz_system_token")
    if not path:
        return None
    with open(path) as f:
        return f.read().strip()


def parse_addr(s: str) -> tuple[str, int]:
    host, port = s.rsplit(":", 1)
    return host, int(port)


def _resolver_knobs(spec: dict) -> dict:
    """Optional deployed-resolver scheduler knobs from the cluster spec
    (the TCP twins of the sim campaign table's resolverBudget /
    resolverDispatchCost): `resolver_budget_s` arms the dispatch-queue
    scheduler (sched/resolver_queue.py) so batches park behind the
    engine and the ratekeeper's resolver_queue signal is exercisable on
    a real deployment; `resolver_dispatch_cost_s` models per-batch
    engine time. Both default off (immediate dispatch)."""
    out: dict = {}
    if spec.get("resolver_budget_s"):
        out["budget_s"] = float(spec["resolver_budget_s"])
    if spec.get("resolver_dispatch_cost_s"):
        out["dispatch_cost_s"] = float(spec["resolver_dispatch_cost_s"])
    return out


def _make_admission_filter():
    """Recent-writes filter for a deployed resolver when the admission
    subsystem is armed (FDB_TPU_ADMISSION=1; admission/__init__.py)."""
    from foundationdb_tpu.admission import (
        RecentWritesFilter,
        admission_env_default,
    )

    return RecentWritesFilter() if admission_env_default() else None


def _make_admission_policy():
    """AdmissionPolicy for a deployed commit proxy (env-armed, like the
    sim recruiter's new_admission_policy)."""
    from foundationdb_tpu.admission import (
        AdmissionPolicy,
        RecentWritesFilter,
        admission_env_default,
    )

    if not admission_env_default():
        return None
    return AdmissionPolicy(filter=RecentWritesFilter(), enabled=True)


def _make_authz(spec: dict):
    """Tenant authz verifier from the spec's `authz_public_key` (a PEM
    path — main() resolves it against the cluster file's directory before
    build_role sees the spec, same convention as tls paths). None = authz
    disabled."""
    path = spec.get("authz_public_key")
    if not path:
        return None
    from foundationdb_tpu.runtime.authz import TokenAuthority

    with open(path, "rb") as f:
        return TokenAuthority(f.read())


def tls_config(spec: dict, spec_path: str) -> dict | None:
    """The spec's optional `tls` section (cert/key/ca paths, resolved
    relative to the cluster file — reference: TLSConfig from the cluster
    file's tls: suffix + command-line knobs)."""
    tls = spec.get("tls")
    if not tls:
        return None
    base = os.path.dirname(os.path.abspath(spec_path))
    return {k: os.path.join(base, v) if not os.path.isabs(v) else v
            for k, v in tls.items()}


#: One exchange carries ONE schedule domain: the commit proxies cap
#: multi-resolver wave batches at the deployed engine's chunk.
#: make_conflict_set builds TPUConflictSet -- or, where the spec's
#: `resolver_mesh` says so, ShardedConflictSet, which inherits it -- with
#: its DEFAULT batch_size; this constant mirrors that default in the
#: proxy process (which must not import the jax engine just to read a
#: number); the resolver's resolve_edges refuses oversized windows
#: loudly if the two ever drift.
DEPLOYED_WAVE_BATCH_LIMIT = 512


def make_conflict_set(engine: str, n_resolvers: int = 1,
                      mesh: "int | None" = None, **sizes):
    """Resolver engine: 'tpu' is the production kernel; 'cpu' (C++ skiplist)
    keeps a cluster deployable on hosts with no accelerator. 'tpu' refuses
    to build on any other platform (utils.require_tpu): JAX's own fallback
    to the CPU is silent, and a resolver that took it would look deployed.

    What 'tpu' builds: TPUConflictSet on the process's first chip, or,
    with ``mesh`` = N (the spec's `resolver_mesh`, see resolver_mesh()),
    ShardedConflictSet(n_shards=N) over the process's first N chips at
    its runtime defaults (auto_reshard and its interval and skew): the
    same class below the device entry points, so the role serves either
    through the same code. Both at the served role's sizes, which are the
    engine's constructor defaults (capacity 1<<16 a shard, batch 512,
    8 + 8 slots, 32 key bytes); a mesh wider than the chips the process
    sees is refused here, naming the key. ``sizes`` are constructor
    arguments for a harness that proves the served construction at
    another size (chip_smoke.py); no deployed path passes any.

    ``n_resolvers`` is the DEPLOYMENT's resolver role count (the spec's
    resolver list), not this process's: wave commit (FDB_TPU_WAVE_COMMIT=1)
    at n_resolvers > 1 is a CAPABILITY check — engines implementing the
    global edge-exchange protocol (resolve_edges/resolve_apply over
    core/wavemesh: tpu, oracle) reorder against the OR-reduced global
    graph the commit proxies assemble, so sharded deployments are legal;
    the cpu skiplist never materializes the conflict graph and must
    refuse recruitment rather than silently un-serialize (the sim
    cluster enforces the same rule)."""
    from foundationdb_tpu.core.types import (
        validate_wave_commit,
        wave_commit_env_default,
    )

    wave = wave_commit_env_default()
    if wave:
        validate_wave_commit(
            n_resolvers, "cpu" if engine == "cpu" else None,
            wave_global_capable=engine in ("tpu", "oracle"),
        )
    if engine == "tpu":
        from foundationdb_tpu.utils import (
            enable_compilation_cache,
            require_tpu,
        )

        enable_compilation_cache()
        found = require_tpu("a resolver with engine 'tpu'")
        if mesh is not None:
            if mesh > found["count"]:
                raise ValueError(
                    f"resolver_mesh={mesh} asks for {mesh} chips and this "
                    f"process sees {found['count']} "
                    f"({found['platform']}, {found['device_kind']})")
            from foundationdb_tpu.parallel.sharded_resolver import (
                ShardedConflictSet,
            )

            return ShardedConflictSet(n_shards=mesh, wave_commit=wave,
                                      **sizes)
        from foundationdb_tpu.models.conflict_set import TPUConflictSet

        return TPUConflictSet(wave_commit=wave, **sizes)
    if mesh is not None or sizes:
        raise ValueError(
            f"resolver_mesh and engine sizes are engine 'tpu's, "
            f"not {engine!r}'s")
    if engine == "cpu":
        from foundationdb_tpu.models.cpu_conflict_set import CPUSkipListConflictSet

        return CPUSkipListConflictSet()
    if engine == "oracle":
        from foundationdb_tpu.sim.oracle import OracleConflictSet

        return OracleConflictSet(wave_commit=wave)
    raise ValueError(f"unknown engine {engine!r}")


def make_engine(spec: dict, name: str):
    """The spec's conflict engine for resolver process `name`. An engine
    with compiled entry points is warmed up before it is handed over
    (TPUConflictSet.warm_up), and what its arrays sit on is printed to the
    role's log; Resolver.get_metrics()["device"] serves the same."""
    engine = spec.get("engine", "cpu")
    cs = make_conflict_set(engine, len(spec["resolver"]),
                           mesh=resolver_mesh(spec))
    if hasattr(cs, "warm_up"):
        warm = cs.warm_up()
        dev = cs.device_info()
        print(f"device {name} engine={engine} platform={dev['platform']} "
              f"device_kind={dev['device_kind']!r} count={dev['count']} "
              f"warm_up_s={json.dumps(warm)}", flush=True)
    return cs


def make_resolver(loop, spec: dict, name: str, init_version: int = 0):
    """The resolver role for the static boot and for each recruited
    generation alike. It is built before the process prints `ready`, or
    against jit caches a managed worker filled before it did, so `ready`
    means compiled: no commit pays a compile, and none blocks the role's
    event loop past the commit proxies' wedge timeout."""
    from foundationdb_tpu.runtime.resolver import Resolver

    return Resolver(loop, make_engine(spec, name),
                    init_version=init_version,
                    admission_filter=_make_admission_filter(),
                    **_resolver_knobs(spec))


class ReadRouter:
    """Client-facing read surface on proxy processes: forwards reads to the
    owning storage shard. Lets one-connection clients (netclient.cpp) drive
    the full path without per-shard connections; richer clients (cli.py,
    client/transaction.py) talk to storage endpoints directly. With
    `replicas` > 1 in the spec, reads fail over across the shard's team
    (a dead replica costs one detection delay, not availability)."""

    FAILED_TTL = 4.0  # how long a failed replica is tried last

    def __init__(self, storage_map: KeyShardMap, storage_eps: list,
                 loop=None):
        self.map = storage_map
        self.eps = storage_eps
        self.loop = loop
        # Failed-replica memory (the router-side twin of the client's
        # Database._order_team): a dead/lagging replica is deprioritized
        # for a TTL so ONE request pays the detection delay, not all.
        self._failed_at: dict[int, float] = {}

    def _order(self, team):
        if self.loop is None:
            return list(team)
        now = self.loop.now
        return sorted(
            team,
            key=lambda t: now - self._failed_at.get(t, -1e9) < self.FAILED_TTL,
        )

    async def _on_team(self, team, call):
        """Run `call(ep)` against the team with failover: connection loss
        AND a lagging replica (FutureVersion — e.g. freshly restarted,
        still catching up on its tag stream) both move to the next
        member; the last error propagates only when EVERY member fails
        (all-lagging surfaces the retryable FutureVersion to the
        client)."""
        last: Exception | None = None
        for tag in self._order(team):
            try:
                return await call(self.eps[tag])
            except (BrokenPromise, FutureVersion) as e:
                if self.loop is not None:
                    self._failed_at[tag] = self.loop.now
                last = e
                continue
        raise last if last else BrokenPromise("empty storage team")

    @rpc
    async def get(self, key: bytes, version: int, token=None):
        return await self._on_team(
            self.map.team_for_key(key),
            lambda ep: ep.get(key, version, token=token))

    @rpc
    async def get_range(self, begin: bytes, end: bytes, version: int,
                        limit: int = 10_000, reverse: bool = False,
                        token=None):
        rows: list = []
        shards = [
            s for s in self.map.shards
            if s.range.begin < end and begin < s.range.end
        ]
        for s in (reversed(shards) if reverse else shards):
            lo = max(begin, s.range.begin)
            hi = min(end, s.range.end)
            got = await self._on_team(
                s.team,
                lambda ep, lo=lo, hi=hi: ep.get_range(
                    lo, hi, version, limit=limit, reverse=reverse,
                    token=token))
            rows.extend(got)
            if len(rows) >= limit:
                return rows[:limit]
        return rows

    @rpc
    async def watch(self, key: bytes, value, token=None):
        return await self._on_team(
            self.map.team_for_key(key),
            lambda ep: ep.watch(key, value, token=token))

    @rpc
    async def wait_for_version(self, version: int) -> None:
        # Team semantics: ONE caught-up member per shard suffices (a dead
        # replica must not wedge the barrier — review finding).
        for s in self.map.shards:
            await self._on_team(
                s.team, lambda ep: ep.wait_for_version(version))


def _supervise(loop: RealLoop, name: str, make_coro):
    """Run a role actor forever, restarting on failure (a peer that is not
    up yet surfaces as BrokenPromise; deployment boots in any order)."""
    loop.spawn(_supervised(loop, name, make_coro), name=f"supervise.{name}")


async def bounded_rpc(loop: RealLoop, fut, timeout_s: float,
                      transport=None):
    """Await an RPC future for at most `timeout_s`; a timeout raises
    TimeoutError. A BLACK-HOLED link (packets vanish, connection stays
    up — the chaos relay's drop mode, a wedged peer, a SIGSTOPped
    process) otherwise hangs the await forever: a dead process at least
    closes its sockets and fails pending calls with BrokenPromise, but a
    black-holed one fails nothing — and a controller sweep or recovery
    lock stuck on one such link would never heal the cluster. Every
    failure-detection and recovery RPC in DeployedController goes
    through this bound so a hung link is indistinguishable from a dead
    one (which is exactly how the caller must treat it). Passing the
    NetTransport lets a timeout also ABANDON the request
    (transport.abandon_call): without that, a long partition probed
    every sweep accumulates one never-answered pending promise per
    probe on the still-open connection."""
    from foundationdb_tpu.runtime.flow import Promise

    p = Promise()

    async def timer():
        await loop.sleep(timeout_s)
        if not p.future.done():
            p.send(None)

    timer_task = loop.spawn(timer(), name="rpc.deadline")

    def on_done(f):
        if not p.future.done():
            p.send(f)
        # Reap the deadline timer NOW: at chaos/sweep call rates,
        # letting every completed call's timer sleep out its full
        # timeout parks thousands of dead coroutines on the loop.
        timer_task.cancel()

    fut.add_done_callback(on_done)
    f = await p.future
    if f is None:
        if transport is not None:
            transport.abandon_call(fut)
        raise TimeoutError(f"rpc exceeded {timeout_s}s (hung link?)")
    return f.result()


class Worker:
    """Per-process recruitment surface for managed clusters (reference: the
    fdbserver worker the ClusterController recruits roles onto —
    fdbserver/worker.actor.cpp). When the spec names a `controller`, chain
    roles (sequencer/resolver/tlog/proxy) do NOT self-wire at boot: each
    process serves only this Worker, and the controller forms generations
    by RPC — which is what lets a deployed cluster heal a killed tlog or
    sequencer with a generation change instead of a full bounce
    (VERDICT r3 item 6)."""

    def __init__(self, loop: RealLoop, t: NetTransport, spec: dict,
                 role: str, index: int, data_dir: str | None):
        self.loop = loop
        self.t = t
        self.spec = spec
        self.role = role
        self.index = index
        self.data_dir = data_dir
        self.epoch = 0
        self._run_tasks: list = []  # current generation's actor tasks
        self.storage = None  # storage role: the long-lived StorageServer

    @rpc
    async def ping(self) -> str:
        return "pong"

    @rpc
    async def describe(self) -> dict:
        d = {"role": self.role, "index": self.index, "epoch": self.epoch}
        # Proxy processes report their database flags so the controller's
        # sweep keeps a live cache — a heal must re-apply backup tagging
        # and the database lock to the next generation (advisor finding:
        # recruiting with defaults silently dropped both: a DR stream gap,
        # and a post-switchover unlock letting stale clients commit).
        cp = getattr(self, "_commit_proxy", None)
        if cp is not None:
            d["backup_enabled"] = cp.backup_enabled
            d["locked"] = cp.locked
        return d

    @rpc
    async def stand_down(self, expect_epoch: int) -> bool:
        """Retire this process's recruited chain role (reference: a
        displaced tlog/proxy halts when it learns a newer generation owns
        the database — worker_removed). The controller's sweep calls this
        on ZOMBIES: processes serving an epoch older than the current
        generation that are not in it — after a region partition heals,
        the dark side's proxies are still alive and ANSWERING commits
        (every one failing at the fenced satellite), and a client that
        keeps rotating onto them burns its whole retry budget (deployed
        multi-region partition find). Standing down turns them into
        "no service" answers, which clients demote and route around.

        `expect_epoch` is the stale epoch the sweep OBSERVED — if a
        recovery recruited this worker in between, the epoch moved and
        this call must be a no-op (the race guard)."""
        if expect_epoch == 0 or self.epoch != expect_epoch:
            return False
        self._cancel_runs()
        if self.role == "proxy":
            self._release_grv_lease()
            self._fail_commit_queue("proxy stood down: generation retired")
            self._fail_grv_queue("proxy stood down: generation retired")
            self.t.unserve("commit_proxy")
            self.t.unserve("grv_proxy")
        elif self.role in ("tlog", "satellite_tlog"):
            self._tlog = None
            self.t.unserve("tlog")
        elif self.role == "sequencer":
            self.t.unserve("sequencer")
        elif self.role == "resolver":
            self.t.unserve("resolver")
        self.epoch = 0  # fresh: recruitable into a future generation
        return True

    def _fail_commit_queue(self, reason: str) -> None:
        """Answer every commit the outgoing proxy holds (CommitProxy.retire):
        the batch loop is cancelled on retire/stand-down, so a parked
        commit would otherwise hang its client forever over a healthy
        connection (the client's on_error resubmits against the new
        generation)."""
        cp = getattr(self, "_commit_proxy", None)
        if cp is None:
            return
        cp.retire(reason)
        self._commit_proxy = None

    def _release_grv_lease(self) -> None:
        """Deliberate retirement returns the outgoing GRV proxy's
        ratekeeper budget share NOW (Ratekeeper.release_lease) so the
        survivors see the whole budget within one get_rates poll, instead
        of the share aging out over the live-poller TTL. Fire-and-forget:
        retirement must never block on a possibly-dead ratekeeper — the
        TTL path stays the crash fallback."""
        g = getattr(self, "_grv_proxy", None)
        if g is None or g.ratekeeper is None:
            return

        async def _release(grv):
            try:
                await grv.release_lease()
            except Exception:
                pass  # unreachable ratekeeper: TTL ageing covers it

        self.loop.spawn(_release(g), name="grv.release_lease")

    def _fail_grv_queue(self, reason: str) -> None:
        """The GRV twin of _fail_commit_queue (same parked-request
        contract for get_read_version promises)."""
        from foundationdb_tpu.core.errors import ProcessKilled

        g = getattr(self, "_grv_proxy", None)
        if g is None:
            return
        for q in (g._queue, g._batch_queue, g._system_queue):
            for p, _tags in q:
                p.fail(ProcessKilled(reason))
            q.clear()
        self._grv_proxy = None

    # -- role recruitment (controller-only callers) -----------------------

    def _cancel_runs(self) -> None:
        for task in self._run_tasks:
            task.cancel()
        self._run_tasks = []

    def _spawn(self, name: str, make_coro) -> None:
        self._run_tasks.append(
            self.loop.spawn(_supervised(self.loop, name, make_coro),
                            name=f"supervise.{name}")
        )

    @rpc
    async def tlog_resume(self) -> int:
        """Durable bootstrap: recover this process's newest disk queue and
        serve it. Returns the recovered end version (get_version semantics:
        last entry + 1, or 0 for a fresh/blank queue). The controller
        compares ends across tlogs, truncates the unacked suffix, and jumps
        the chain (the controller-driven form of the static boot_sequencer
        restart sync)."""
        from foundationdb_tpu.runtime.tlog import TLog

        if self.data_dir is None:
            tlog = TLog(self.loop)
        else:
            tlog = TLog.from_disk(self.loop, self._newest_queue())
        tlog.system_token = _system_token(self.spec)
        self._tlog = tlog
        self.t.serve("tlog", tlog)
        return await tlog.get_version()

    @rpc
    async def tlog_adopt(self, epoch: int, start_version: int) -> int:
        """Finish a resumed tlog's handoff: adopt the generation's chain
        start (a no-op for a fresh epoch-1 chain) and the epoch stamp the
        controller's sweep checks."""
        await self._tlog.begin_epoch(start_version)
        self._tlog.epoch = epoch  # arm the generation fence on the chain
        self.epoch = epoch
        return start_version

    def _newest_queue(self) -> str:
        """The highest-epoch queue file for this tlog index (recoveries
        write tlog{i}.e{N}.q; the static path wrote tlog{i}.q)."""
        import re

        best, best_epoch = os.path.join(
            self.data_dir, f"tlog{self.index}.q"), 1
        for name in os.listdir(self.data_dir):
            m = re.fullmatch(rf"tlog{self.index}\.e(\d+)\.q", name)
            if m and int(m.group(1)) >= best_epoch:
                best, best_epoch = os.path.join(self.data_dir, name), int(m.group(1))
        return best

    @rpc
    async def recruit_tlog(self, epoch: int, start_version: int,
                           seed_entries: list) -> int:
        """Next-generation tlog: fresh chain at start_version, seeded with
        the prior generation's salvaged un-popped suffix."""
        from foundationdb_tpu.runtime.tlog import TLog

        disk = (os.path.join(self.data_dir, f"tlog{self.index}.e{epoch}.q")
                if self.data_dir else None)
        tlog = TLog(self.loop, init_version=start_version,
                    seed=[(v, t) for v, t in seed_entries], disk_path=disk,
                    epoch=epoch)
        tlog.system_token = _system_token(self.spec)
        self._tlog = tlog
        self.t.serve("tlog", tlog)
        self.epoch = epoch
        return start_version

    @rpc
    async def recruit_sequencer(self, epoch: int, recovery_version: int) -> int:
        from foundationdb_tpu.runtime.sequencer import Sequencer

        seq = Sequencer(self.loop, epoch=epoch,
                        recovery_version=recovery_version)
        self.t.serve("sequencer", seq)
        self.epoch = epoch
        return seq.last_handed_out

    @rpc
    async def recruit_resolver(self, epoch: int, start_version: int) -> int:
        self.t.serve(
            "resolver",
            make_resolver(self.loop, self.spec, f"resolver{self.index}",
                          init_version=start_version),
        )
        self.epoch = epoch
        return start_version

    @rpc
    async def recruit_proxy(self, epoch: int, tlog_addrs: list,
                            resolver_addrs: list,
                            backup_enabled: bool = False,
                            locked: bool = False,
                            seq_addr: "list | None" = None) -> int:
        """Rebuild this process's CommitProxy + GrvProxy against the new
        generation's LIVE tlog/resolver sets. Old actor loops are
        cancelled; the service names are re-pointed at the new objects, so
        clients keep their endpoints (in-flight calls to the old objects
        resolve against the new generation's chain guards).
        `backup_enabled`/`locked` carry the database flags across the
        generation change (the sim recruiter propagates the same pair —
        sim/cluster.py)."""
        from foundationdb_tpu.runtime.commit_proxy import CommitProxy
        from foundationdb_tpu.runtime.grv_proxy import GrvProxy

        self._cancel_runs()
        self._release_grv_lease()
        self._fail_commit_queue("proxy retired by recovery")
        self._fail_grv_queue("proxy retired by recovery")
        seq_ep = self.t.endpoint(
            tuple(seq_addr) if seq_addr
            else parse_addr(self.spec["sequencer"][0]),
            "sequencer")
        rk = self.spec.get("ratekeeper") or []
        rk_ep = (self.t.endpoint(parse_addr(rk[0]), "ratekeeper")
                 if rk else None)
        tlog_eps = [self.t.endpoint(tuple(a), "tlog") for a in tlog_addrs]
        resolver_eps = [self.t.endpoint(tuple(a), "resolver")
                        for a in resolver_addrs]
        controller_ep = self.t.endpoint(
            parse_addr(self.spec["controller"][0]), "controller")
        storage_map = storage_shard_map(self.spec)
        from foundationdb_tpu.core.types import wave_commit_env_default

        proxy = CommitProxy(
            self.loop, seq_ep, resolver_eps,
            resolver_shard_map(self.spec, len(resolver_eps)), tlog_eps,
            storage_map,
            controller_ep=controller_ep, epoch=epoch,
            authz=_make_authz(self.spec),
            tenant_mirror=_make_tenant_mirror(
                self.loop, self.t, self.spec, storage_map, self._spawn),
            admission=_make_admission_policy(),
            wave_commit=wave_commit_env_default(),
            wave_batch_limit=DEPLOYED_WAVE_BATCH_LIMIT,
        )
        proxy.backup_enabled = backup_enabled
        proxy.locked = locked
        self._commit_proxy = proxy
        # tlog_addrs already includes the satellites (the controller
        # passes the full push set) — exactly the confirmEpochLive set.
        grv = GrvProxy(self.loop, seq_ep, rk_ep, tlog_eps=tlog_eps,
                       epoch=epoch)
        self._grv_proxy = grv
        self.t.serve("commit_proxy", proxy)
        self.t.serve("grv_proxy", grv)
        self._spawn(f"proxy{self.index}.run", proxy.run)
        self._spawn(f"grv{self.index}.run", grv.run)
        self.epoch = epoch
        return epoch

    @rpc
    async def recruit_storage(self, epoch: int, recovery_version: int,
                              tlog_addrs: list) -> int:
        """Re-point the long-lived StorageServer at the new generation:
        roll back above the recovery version, pull from the new tlogs."""
        tlog_eps = [self.t.endpoint(tuple(a), "tlog") for a in tlog_addrs]
        tag = self.storage.tag
        self.storage.recover_to(
            recovery_version, tlog_eps[tag % len(tlog_eps)], tlog_eps
        )
        self.epoch = epoch
        return epoch


def _supervised(loop: RealLoop, name: str, make_coro):
    """The _supervise coroutine, returned (not spawned) so callers can hold
    and cancel the task — generation changes retire old actor loops."""

    async def runner():
        while True:
            try:
                await make_coro()
                return
            except ActorCancelled:
                raise
            except Exception as e:  # noqa: BLE001 — supervisor boundary
                print(f"[{name}] actor failed: {type(e).__name__}: {e}; "
                      "restarting in 0.5s", file=sys.stderr, flush=True)
                await loop.sleep(0.5)

    return runner()


class DeployedController:
    """Failure detection + generation formation over real TCP.

    The deployed counterpart of the sim's ClusterController + recovery
    state machine (runtime/cluster.py, runtime/recovery.py; reference:
    fdbserver/ClusterController.actor.cpp + masterserver recovery): sweep
    worker heartbeats, and on a chain-role failure lock the surviving
    tlogs, salvage the un-popped suffix, and recruit the next generation
    on every process that answers. Processes come from the static spec
    (there is no spare-worker pool to place roles on — recruitment
    re-forms the generation on the surviving/restarted spec processes,
    which fdbmonitor keeps restarting). Singleton by deployment (one
    `controller` entry in the spec); the coordinator-quorum election the
    sim exercises is not wired over TCP.
    """

    HEARTBEAT_INTERVAL = 1.0
    RETRY_DELAY = 0.5
    BOOT_DEADLINE = 120.0
    #: per-RPC bound on failure-detection probes (sweep, rejoin, zombie,
    #: region-flip, probe_live): a black-holed link answers like a dead one.
    PROBE_TIMEOUT = 2.5
    #: per-RPC bound on recovery-path calls (lock, salvage, recruit —
    #: salvage can carry a real payload; recruits rebuild role state).
    RECOVERY_RPC_TIMEOUT = 15.0

    def __init__(self, loop: RealLoop, t: NetTransport, spec: dict,
                 data_dir: str | None):
        self.loop = loop
        self.t = t
        self.spec = spec
        self.data_dir = data_dir
        self.epoch = 0
        self.recovery_version = 0
        # role -> list of live spec indices in the current generation.
        self.live: dict[str, list[int]] = {}
        self.recoveries_completed = 0
        self._recovering = False
        # Per-recovery MTTR breakdown (the deployed chaos harness's
        # primary observable): one entry per completed recovery with
        # wall-clock detection stamp + per-stage durations
        # (detection -> lock -> salvage -> accepting-commits).
        self.recovery_log: list[dict] = []
        # Database flags cached from proxy describes (sweep + pre-recovery
        # probe) and re-applied at recruit_proxy — the deployed analogue
        # of the sim recruiter reading cluster.backup_active/db_locked.
        self.backup_active = False
        self.db_locked = False
        # Operator maintenance config (fdbcli exclude / configure):
        # excluded chain processes are left out of the next generation
        # (upstream's exclude semantics for stateless/log classes — the
        # process stays up, the cluster stops depending on it); desired
        # counts clamp how many of each chain role the generation uses.
        # Storage is data-bearing and not excludable here (that is data
        # distribution's drain job — sim-only for now). PERSISTED in the
        # controller's data dir (reference keeps exclusions in
        # \xff/conf/excluded for the same reason): a controller restart
        # must not silently recruit a drained-for-decommission process
        # back into the generation (review finding).
        self.excluded: set[tuple[str, int]] = set()
        self.desired_counts: dict[str, int] = {}
        # Multi-region: which region hosts the transaction subsystem.
        # PERSISTED (with the maintenance config): after a failover to
        # "rem", a controller restart must resume rem's chain, not try to
        # resurrect the dead primary's disks.
        self.regions = spec.get("regions")
        self.active_region = "pri" if self.regions else None
        self._region_blackouts = 0  # consecutive all-dead probes of active
        self._load_maintenance()

    def _maintenance_path(self) -> str | None:
        if not self.data_dir:
            return None
        return os.path.join(self.data_dir, "maintenance.json")

    def _load_maintenance(self) -> None:
        path = self._maintenance_path()
        if not path or not os.path.exists(path):
            return
        try:
            with open(path) as f:
                doc = json.load(f)
            self.excluded = {(r, int(i)) for r, i in doc.get("excluded", [])}
            self.desired_counts = {
                r: int(n) for r, n in doc.get("configured", {}).items()
            }
            if self.regions and doc.get("active_region") in self.regions:
                self.active_region = doc["active_region"]
            # Sanitize a persisted config that (e.g. after a spec edit)
            # would empty a chain role: drop its exclusions, loudly.
            for role in ("tlog", "resolver", "proxy"):
                all_idx = list(range(len(self.spec[role])))
                if not [i for i in self._admitted(role, all_idx)
                        if (role, i) not in self.excluded]:
                    dropped = {(r, i) for r, i in self.excluded if r == role}
                    if dropped:
                        self.excluded -= dropped
                        print(f"[controller] WARNING: persisted exclusions "
                              f"{sorted(dropped)} would leave no {role}; "
                              "dropped", file=sys.stderr, flush=True)
        except (OSError, ValueError):
            pass  # unreadable config: start clean rather than refuse boot

    def _save_maintenance(self) -> None:
        path = self._maintenance_path()
        if not path:
            return
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({
                "excluded": sorted([r, i] for r, i in self.excluded),
                "configured": dict(self.desired_counts),
                "active_region": self.active_region,
            }, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    # -- endpoints ---------------------------------------------------------

    def _worker(self, role: str, i: int):
        return self.t.endpoint(parse_addr(self.spec[role][i]), "worker")

    def _tlog(self, i: int):
        return self.t.endpoint(parse_addr(self.spec["tlog"][i]), "tlog")

    def _addrs(self, role: str, live: list[int]) -> list:
        return [list(parse_addr(self.spec[role][i])) for i in live]

    async def _retry(self, make_call, deadline: float):
        while True:
            try:
                # Per-attempt bound: a black-holed worker must fail the
                # attempt (and be retried / recovery re-planned), not
                # absorb the whole recovery into one hung await.
                return await bounded_rpc(self.loop, make_call(),
                                         self.RECOVERY_RPC_TIMEOUT,
                                         transport=self.t)
            except Exception:
                if self.loop.now > deadline:
                    raise
                await self.loop.sleep(self.RETRY_DELAY)

    # -- status (cli/status surface) ---------------------------------------

    @rpc
    async def get_status(self) -> dict:
        d = {
            "epoch": self.epoch,
            "recovery_version": self.recovery_version,
            "recoveries_completed": self.recoveries_completed,
            "recovering": self._recovering,
            "generation": {r: list(v) for r, v in self.live.items()},
            "backup_active": self.backup_active,
            "db_locked": self.db_locked,
            "excluded": sorted(f"{r}{i}" for r, i in self.excluded),
            "configured": dict(self.desired_counts),
        }
        if self.regions:
            d["active_region"] = self.active_region
        return d

    @rpc
    async def get_client_info(self) -> dict:
        """The deployed ClientDBInfo (reference: clients monitor the
        cluster controller's ClientDBInfo and swap proxy connections on
        generation change). Returns the CURRENT generation's proxy
        addresses; clients refresh on commit_unknown/process-killed
        errors and stop routing to retired proxies — without this, a
        deployed client only ever knows the static spec list and can
        keep handing commits to a zombie region's proxy (deployed
        multi-region partition find)."""
        return {
            "epoch": self.epoch,
            "proxy_addrs": self._addrs("proxy", self.live.get("proxy", [])),
        }

    @rpc
    async def get_metrics(self) -> dict:
        """Registry scrape surface (obs/registry.py `controller.*`): the
        documented recovery_* counters — recovery count plus the LAST
        recovery's per-stage MTTR breakdown (seconds). Zeros until the
        first recovery so the documented-counter audit holds on a
        freshly booted cluster too."""
        last = self.recovery_log[-1] if self.recovery_log else {}
        return {
            "recovery_count": self.recoveries_completed,
            "recovery_lock_s": last.get("lock_s", 0.0),
            "recovery_salvage_s": last.get("salvage_s", 0.0),
            "recovery_recruit_s": last.get("recruit_s", 0.0),
            "recovery_total_s": last.get("total_s", 0.0),
            "recovering": self._recovering,
            "epoch": self.epoch,
        }

    @rpc
    async def get_recovery_log(self) -> list:
        """Every completed recovery's MTTR entry (chaos harness: matched
        against fault-injection wall stamps to attribute detection
        latency per fault)."""
        return list(self.recovery_log)

    def _probe(self, role: str, i: int, method: str = "describe"):
        """A failure-detection RPC task, time-bounded (PROBE_TIMEOUT) so
        black-holed links count as failures instead of wedging the
        sweep/recovery forever."""
        fut = getattr(self._worker(role, i), method)()
        return self.loop.spawn(
            bounded_rpc(self.loop, fut, self.PROBE_TIMEOUT,
                        transport=self.t),
            name=f"probe.{role}{i}.{method}")

    @rpc
    async def set_excluded(self, role: str, index: int,
                           excluded: bool) -> dict:
        """fdbcli exclude/include for CHAIN roles: drop the process from
        (or return it to) generation membership with a generation change.
        Storage is refused — draining a data-bearing role is data
        distribution's job (sim-only DataDistributor.exclude)."""
        if role not in ("tlog", "resolver", "proxy"):
            raise ValueError(
                f"role {role!r} is not excludable here: chain roles only "
                "(storage drain requires data distribution)")
        if not 0 <= index < len(self.spec[role]):
            raise ValueError(f"no {role}{index} in the cluster spec")
        if excluded:
            # Refuse (don't record-and-ignore) an exclusion that would
            # leave the role with nothing to recruit — otherwise status
            # reports the process excluded while the generation quietly
            # keeps depending on it (review finding).
            remaining = [
                i for i in range(len(self.spec[role]))
                if i != index and (role, i) not in self.excluded
            ]
            n = self.desired_counts.get(role)
            if not (remaining[:n] if n is not None else remaining):
                raise ValueError(
                    f"cannot exclude {role}{index}: no {role} would "
                    "remain recruitable")
            self.excluded.add((role, index))
        else:
            self.excluded.discard((role, index))
        self._save_maintenance()
        self.loop.spawn(
            self._recover(
                f"operator {'exclude' if excluded else 'include'} "
                f"{role}{index}"),
            name="controller.exclude_recovery")
        return {"excluded": sorted(f"{r}{i}" for r, i in self.excluded)}

    @rpc
    async def configure(self, counts: dict) -> dict:
        """fdbcli configure analogue for chain-role counts: the next
        generation uses the first N spec processes of each role."""
        for role, n in counts.items():
            if role not in ("tlog", "resolver", "proxy"):
                raise ValueError(f"cannot configure count for {role!r}")
            n = int(n)
            if not 1 <= n <= len(self.spec[role]):
                raise ValueError(
                    f"{role} count must be in [1, {len(self.spec[role])}]")
            self.desired_counts[role] = n
        self._save_maintenance()
        self.loop.spawn(self._recover(f"operator configure {counts}"),
                        name="controller.configure_recovery")
        return {"configured": dict(self.desired_counts)}

    @rpc
    async def request_recovery(self, epoch: int, reason: str) -> None:
        """A proxy observed the pipeline wedged (lost tlog pushes) —
        heartbeats can't always see it first (reference: proxies force
        recovery on tlog failure)."""
        if self._recovering or epoch != self.epoch:
            return
        self.loop.spawn(self._recover(f"requested: {reason}"),
                        name="controller.requested_recovery")

    # -- bootstrap ---------------------------------------------------------

    async def bootstrap(self) -> None:
        """First generation of this controller lifetime.

        Three cases, distinguished by what the tlog workers report:
        - some worker holds a RECRUITED tlog (epoch > 0): only the
          controller restarted — the old generation is still live and
          committing. Resuming disk files here would truncate commits
          acked after the end-snapshot (they keep landing while we read);
          instead run the lock-based recovery against the LIVE tlogs,
          exactly like a failure-triggered generation change.
        - all workers fresh, disk queues hold data: durable full-bounce
          restart — resume chains, truncate the unacked suffix, new epoch.
        - all fresh and blank: new cluster at epoch 1.
        """
        live_tlogs, live_sats, max_epoch = [], [], 0
        for i in range(len(self.spec["tlog"])):
            try:
                d = await self._probe("tlog", i)
                if d.get("epoch", 0) > 0:
                    live_tlogs.append(i)
                    max_epoch = max(max_epoch, d["epoch"])
            except Exception:
                continue
        for i in range(len(self.spec.get("satellite_tlog") or [])):
            try:
                d = await self._probe("satellite_tlog", i)
                if d.get("epoch", 0) > 0:
                    live_sats.append(i)
                    max_epoch = max(max_epoch, d["epoch"])
            except Exception:
                continue
        if live_tlogs or live_sats:
            # The recovery's next epoch derives from the OBSERVED live
            # generation — without a data dir it must still exceed it, or
            # the new generation would restart the version chain.
            if self.regions and live_tlogs:
                # A live chain names the active region authoritatively
                # (stronger evidence than the persisted value, which a
                # wiped controller data dir loses).
                for r in ("pri", "rem"):
                    if set(live_tlogs) & set(self.regions[r]["tlog"]):
                        self.active_region = r
                        break
            self.epoch = max_epoch
            self.live = {"tlog": live_tlogs, "satellite_tlog": live_sats}
            await self._recover("controller restart over a live generation")
            return
        await self._bootstrap_resume()

    async def _bootstrap_resume(self) -> float:
        """Resume tlog chains from disk (or start blank). Only safe when no
        recruited tlog is live — callers check first (appends racing the
        end-version snapshot would be truncated as 'unacked'). Returns
        the monotonic stamp at the end of the disk-salvage phase
        (tlog_resume + truncate, just before generation forming) — the
        disk-resume recovery's salvage/recruit MTTR boundary."""
        deadline = self.loop.now + self.BOOT_DEADLINE
        chain = self._chain_tlog_idx()  # active region only: the standby's
        # disks hold retired generations and must not vote on the chain end
        ends = []
        for i in chain:
            ep = self._worker("tlog", i)
            ends.append(await self._retry(ep.tlog_resume, deadline))
        minv, maxv = min(ends), max(ends)
        if minv == 0 and maxv > 0:
            raise RuntimeError(
                f"mixed tlog recovery state (ends={ends}): some disk "
                "queues recovered data, some are empty — refusing to "
                "start. Restore the missing tlog queue or clear the "
                "data dir to accept data loss."
            )
        if minv > 0:
            epoch = (_bump_epoch(self.data_dir, floor=self.epoch)
                     if self.data_dir
                     else self.epoch + 1 if self.epoch else 2)
            for i in chain:
                await self._retry(
                    lambda i=i: self._tlog(i).truncate_to(minv - 1), deadline)
            t_salvaged = self.loop.now
            await self._form_generation(
                epoch, minv, live=self._all_live(), seed_entries=[],
                resume=True,
            )
        else:
            t_salvaged = self.loop.now
            await self._form_generation(
                1, 0, live=self._all_live(), seed_entries=[], resume=True,
            )
        return t_salvaged

    def _region_idx(self, role: str) -> "list[int] | None":
        """Active region's spec indices for a chain role (None when the
        cluster is single-region). Storage is NOT region-filtered: both
        regions' storages are always in the generation (the remote
        replicas pull the stream cross-region — the DCN leg)."""
        if not self.regions or role not in REGION_CHAIN_ROLES:
            return None
        return list(self.regions[self.active_region].get(role, []))

    def _seq_idx(self) -> int:
        """The generation's sequencer spec index (active region's)."""
        r = self._region_idx("sequencer")
        return r[0] if r else 0

    def _standby_region(self) -> "str | None":
        if not self.regions:
            return None
        return "rem" if self.active_region == "pri" else "pri"

    def _admitted(self, role: str, candidates: list[int]) -> list[int]:
        """Maintenance filter for chain roles: drop excluded processes,
        then take the first `desired_counts[role]` of what REMAINS — so
        `exclude tlog0; configure tlogs=1` yields [1], not the excluded
        tlog0 (review finding: counting by raw spec index made exclusion
        and configure impossible to compose). Safety valve: a config
        that would leave a chain role EMPTY (everything excluded) is
        ignored rather than wedging recovery forever.

        Multi-region: chain roles recruit only in the ACTIVE region
        (reference: the transaction subsystem lives in one DC; failover
        moves it wholesale). Satellite tlogs and storage span regions."""
        if role == "storage":
            return candidates  # data-bearing: not excludable without DD
        if role == "satellite_tlog":
            return candidates  # always in the push set when present
        region = self._region_idx(role)
        if region is not None:
            candidates = [i for i in candidates if i in region]
        out = [i for i in candidates if (role, i) not in self.excluded]
        n = self.desired_counts.get(role)
        if n is not None:
            out = out[:n]
        return out or candidates

    def _admit(self, role: str, i: int) -> bool:
        """Is process (role, i) part of the admitted set right now? Used
        by the sweep's rejoin scan — consistent with _admitted by
        construction."""
        return i in self._admitted(role, list(range(len(self.spec[role]))))

    def _all_live(self) -> dict:
        roles = ["tlog", "resolver", "proxy", "storage"]
        if self.spec.get("satellite_tlog"):
            roles.append("satellite_tlog")
        return {r: self._admitted(r, list(range(len(self.spec[r]))))
                for r in roles}

    # -- generation formation ----------------------------------------------

    async def _form_generation(self, epoch: int, recovery_version: int,
                               live: dict, seed_entries: list,
                               resume: bool) -> None:
        from foundationdb_tpu.runtime.sequencer import EPOCH_VERSION_JUMP

        deadline = self.loop.now + self.BOOT_DEADLINE
        start = 0 if epoch == 1 else recovery_version + EPOCH_VERSION_JUMP
        tlog_addrs = self._addrs("tlog", live["tlog"])
        resolver_addrs = self._addrs("resolver", live["resolver"])
        # Satellite tlogs are full replicas of the mutation stream IN the
        # proxies' synchronous push set (every ack includes them — that's
        # what makes region failover lossless), but NOT in the storage
        # pull set (storages pull from the chain; sim/cluster.py keeps
        # the same split).
        sat_live = live.get("satellite_tlog", [])
        sat_addrs = self._addrs("satellite_tlog", sat_live) if sat_live else []
        seq_idx = self._seq_idx()
        seq_addr = list(parse_addr(self.spec["sequencer"][seq_idx]))

        for i in live["resolver"]:
            await self._retry(
                lambda i=i: self._worker("resolver", i)
                .recruit_resolver(epoch, start), deadline)
        if not resume:
            for i in live["tlog"]:
                await self._retry(
                    lambda i=i: self._worker("tlog", i)
                    .recruit_tlog(epoch, start, seed_entries), deadline)
        sat_seed = seed_entries
        if resume and sat_live:
            # Disk-resume bootstrap: the salvage seed is empty (the chain
            # IS the data), but fresh satellites must still hold what
            # lagging storages haven't applied — a region loss right
            # after a full bounce would otherwise have no salvage source.
            # The snapshot is gated (tlog.entries_snapshot): pass the
            # forming epoch + the system token so the tlog can tell this
            # bootstrap call from a mistimed/displaced reader.
            src = live["tlog"][0]
            sat_seed = await self._retry(
                lambda: self._tlog(src).entries_snapshot(
                    epoch=epoch, token=_system_token(self.spec)),
                deadline)
        for i in sat_live:
            await self._retry(
                lambda i=i: self._worker("satellite_tlog", i)
                .recruit_tlog(epoch, start, sat_seed), deadline)
        seq_start = await self._retry(
            lambda: self._worker("sequencer", seq_idx)
            .recruit_sequencer(epoch, recovery_version), deadline)
        assert seq_start == start
        if resume:
            # Resumed tlogs keep their recovered chain; adopt the jumped
            # start (the unacked suffix was truncated in bootstrap; a
            # fresh epoch-1 chain adopts start 0, a no-op) + epoch stamp.
            for i in live["tlog"]:
                await self._retry(
                    lambda i=i: self._worker("tlog", i)
                    .tlog_adopt(epoch, start), deadline)
        for i in live["proxy"]:
            await self._retry(
                lambda i=i: self._worker("proxy", i)
                .recruit_proxy(epoch, tlog_addrs + sat_addrs, resolver_addrs,
                               self.backup_active, self.db_locked,
                               seq_addr=seq_addr),
                deadline)
        for i in live["storage"]:
            await self._retry(
                lambda i=i: self._worker("storage", i)
                .recruit_storage(epoch, recovery_version, tlog_addrs),
                deadline)
        self.epoch = epoch
        self.recovery_version = recovery_version
        self.live = live

    # -- failure detection + recovery ---------------------------------------

    async def run(self) -> None:
        while True:
            await self.loop.sleep(self.HEARTBEAT_INTERVAL)
            if self._recovering:
                continue
            reason = await self._sweep()
            if reason:
                await self._recover(reason)

    async def _sweep(self) -> str | None:
        """Ping every generation process; also notice spec processes that
        are BACK (restarted by fdbmonitor) but not in the generation — a
        rejoin is folded in with a generation change, restoring full tlog
        replication."""
        checks = [("sequencer", self._seq_idx())]
        for role in ("tlog", "resolver", "proxy", "storage",
                     "satellite_tlog"):
            checks.extend((role, i) for i in self.live.get(role, []))
        # All probes in flight at once: one sweep costs ONE RPC timeout
        # even with several dead/black-holed endpoints (mirrors the sim
        # controller's parallel _sweep). Each probe is PROBE_TIMEOUT-
        # bounded: a black-holed link (relay drop / SIGSTOP) delivers no
        # BrokenPromise — without the bound the sweep hangs forever and
        # the cluster never heals.
        tasks = [(role, i, self._probe(role, i)) for role, i in checks]
        verdict = None
        flag_answers = []
        for role, i, t in tasks:
            try:
                d = await t
            except Exception:
                verdict = verdict or f"{role}{i} failed heartbeat"
                continue
            if role == "proxy" and "backup_enabled" in d:
                flag_answers.append(d)
            if d.get("epoch") != self.epoch:
                # fdbmonitor restarted the process between sweeps: it
                # answers pings but serves no recruited role — fold it
                # back in with a generation change (catches restarts
                # faster than a wedged proxy batch would).
                verdict = verdict or f"{role}{i} restarted (epoch {d.get('epoch')})"
        if flag_answers:
            # Any-answered OR: the flags are set on every proxy together
            # (backup._set_proxies / set_database_lock loop over all), so
            # one fresh answer is authoritative; OR guards the window
            # where a setter died mid-loop.
            self.backup_active = any(d["backup_enabled"] for d in flag_answers)
            self.db_locked = any(d.get("locked") for d in flag_answers)
        if verdict:
            return verdict
        missing = [
            (role, i)
            for role in ("tlog", "resolver", "proxy", "storage",
                         "satellite_tlog")
            for i in set(range(len(self.spec.get(role) or []))) - set(
                self.live.get(role, []))
            if self._admit(role, i)  # excluded processes must not rejoin
        ]
        tasks = [(role, i, self._probe(role, i, "ping"))
                 for role, i in missing]
        for role, i, t in tasks:
            try:
                await t
            except Exception:
                continue
            verdict = verdict or f"{role}{i} rejoined"
        if verdict is None:
            # Healthy sweeps only: a failed sweep is about to run a
            # recovery — the next quiet sweep mops zombies up.
            await self._stand_down_zombies()
        return verdict

    async def _stand_down_zombies(self) -> None:
        """Retire chain roles still serving a RETIRED epoch outside the
        generation (reference: displaced roles halt via worker_removed).
        Exists for the region-partition case: the dark region's whole
        chain keeps running — its proxies answer commits that can only
        fail at the fenced satellite — and nothing else ever tells it
        the database moved (region-filtered recruitment never touches
        it until failback). Also mops up an excluded proxy/tlog after
        its generation retires."""
        members = {
            "sequencer": {self._seq_idx()},
            "tlog": set(self.live.get("tlog", [])),
            "resolver": set(self.live.get("resolver", [])),
            "proxy": set(self.live.get("proxy", [])),
            "satellite_tlog": set(self.live.get("satellite_tlog", [])),
        }
        probes = [
            (role, i, self._probe(role, i))
            for role, mem in members.items()
            for i in set(range(len(self.spec.get(role) or []))) - mem
        ]
        for role, i, t in probes:
            try:
                d = await t
            except Exception:
                continue
            stale = d.get("epoch", 0)
            if 0 < stale < self.epoch:
                try:
                    if await bounded_rpc(
                            self.loop,
                            self._worker(role, i).stand_down(stale),
                            self.PROBE_TIMEOUT, transport=self.t):
                        print(f"[controller] stood down zombie {role}{i} "
                              f"(epoch {stale})", file=sys.stderr, flush=True)
                except Exception:
                    continue  # unreachable again: next sweep retries

    async def _recover(self, reason: str) -> None:
        """Lock → salvage → recruit (runtime/recovery.py's state machine,
        driven over TCP against worker RPCs). Each completed recovery
        appends an MTTR entry to `recovery_log`: `detected_wall` (epoch
        seconds at detection — chaos harnesses subtract their fault-
        injection stamp to get detection latency) and the lock/salvage/
        recruit stage durations. Stage rule: time spent in FAILED
        attempts accrues to the stage being retried (a lock that takes
        five tries took that long to lock)."""
        if self._recovering:
            return
        self._recovering = True
        t_detect, w_detect = self.loop.now, self.loop.wall_now
        print(f"[controller] recovery: {reason}", file=sys.stderr, flush=True)
        await self._learn_db_flags()
        lock_failures = 0
        try:
            while True:
                try:
                    # Lock the generation's full push set: chain tlogs AND
                    # satellite tlogs — on a region loss the satellites
                    # are the only lockable members and carry every acked
                    # commit (that is their whole purpose). Lock RPCs are
                    # time-bounded: a black-holed tlog must drop out of
                    # the lockable set, not hang the recovery.
                    locked: list[tuple[int, tuple[str, int]]] = []
                    for role in ("tlog", "satellite_tlog"):
                        for i in self.live.get(role, []):
                            try:
                                locked.append(
                                    (await bounded_rpc(
                                        self.loop,
                                        self._push_tlog(role, i).lock(),
                                        self.PROBE_TIMEOUT,
                                        transport=self.t),
                                     (role, i)))
                            except Exception:
                                continue
                    chain_locked = any(r == "tlog" for _, (r, _i) in locked)
                    if chain_locked:
                        # Debounce is per-incident: a lockable chain means
                        # the region is NOT dark — stale counts from an
                        # earlier blip must not let one future all-dark
                        # probe trigger a cross-region move.
                        self._region_blackouts = 0
                    if not locked:
                        # No generation tlog reachable. If EVERY chain
                        # tlog worker answers but fresh (epoch 0 —
                        # fdbmonitor restarted them all, e.g. rack power
                        # loss), no live chain exists to lock: fall back
                        # to the durable disk-resume path instead of
                        # spinning.
                        lock_failures += 1
                        if lock_failures >= 5 and await self._all_tlogs_fresh():
                            print("[controller] all tlogs restarted fresh — "
                                  "disk-resume recovery", file=sys.stderr,
                                  flush=True)
                            # The failed lock rounds ARE this recovery's
                            # lock stage (stage rule above) — stamping
                            # the boundary here keeps the MTTR breakdown
                            # from dumping them into recruit_s.
                            t_locked = self.loop.now
                            t_salvaged = await self._bootstrap_resume()
                            self.recoveries_completed += 1
                            self._log_recovery(
                                reason + " (disk-resume)", w_detect,
                                t_detect, t_locked, t_salvaged)
                            return
                        await self.loop.sleep(self.RETRY_DELAY)
                        continue
                    if (self.regions and not chain_locked
                            and await self._maybe_flip_region()):
                        lock_failures = 0  # probe the new region's chain
                    t_locked = self.loop.now
                    recovery_version, (src_role, src) = max(locked)
                    seed = await bounded_rpc(
                        self.loop,
                        self._push_tlog(src_role, src).recover_entries(),
                        self.RECOVERY_RPC_TIMEOUT, transport=self.t)
                    t_salvaged = self.loop.now
                    live = await self._probe_live()
                    if (self._seq_idx() not in live["sequencer"]
                            or not live["tlog"]
                            or not live["resolver"] or not live["proxy"]):
                        await self.loop.sleep(self.RETRY_DELAY)
                        continue
                    epoch = (_bump_epoch(self.data_dir, floor=self.epoch)
                             if self.data_dir else self.epoch + 1)
                    await self._form_generation(
                        epoch, recovery_version, live, seed, resume=False)
                    self.recoveries_completed += 1
                    self._log_recovery(reason, w_detect, t_detect,
                                       t_locked, t_salvaged)
                    print(f"[controller] recovered to epoch {epoch} "
                          f"v{recovery_version} live={live} "
                          f"region={self.active_region}",
                          file=sys.stderr, flush=True)
                    return
                except Exception as e:  # noqa: BLE001 — keep retrying
                    print(f"[controller] recovery attempt failed: "
                          f"{type(e).__name__}: {e}", file=sys.stderr,
                          flush=True)
                    await self.loop.sleep(self.RETRY_DELAY)
        finally:
            self._recovering = False

    def _push_tlog(self, role: str, i: int):
        """Endpoint of a push-set member (chain or satellite tlog)."""
        return self.t.endpoint(parse_addr(self.spec[role][i]), "tlog")

    MAX_RECOVERY_LOG = 64  # long soaks must not grow the log unbounded

    def _log_recovery(self, reason: str, w_detect: float, t_detect: float,
                      t_locked: float, t_salvaged: float) -> None:
        """One MTTR entry per completed recovery; stage ends are
        monotonic-clock stamps, recruit ends NOW (the generation just
        formed = accepting commits). Also emitted as a trace event so a
        --trace-dir deployment gets the breakdown in its JSONL."""
        now = self.loop.now
        entry = {
            "epoch": self.epoch,
            "recovery_version": self.recovery_version,
            "reason": reason,
            "detected_wall": round(w_detect, 6),
            "completed_wall": round(self.loop.wall_now, 6),
            "lock_s": round(t_locked - t_detect, 6),
            "salvage_s": round(t_salvaged - t_locked, 6),
            "recruit_s": round(now - t_salvaged, 6),
            "total_s": round(now - t_detect, 6),
        }
        self.recovery_log.append(entry)
        del self.recovery_log[:-self.MAX_RECOVERY_LOG]
        tracer = getattr(self.loop, "tracer", None)
        if tracer is not None:
            tracer.event("DeployedRecoveryComplete",
                         Epoch=entry["epoch"], Reason=reason,
                         LockS=entry["lock_s"],
                         SalvageS=entry["salvage_s"],
                         RecruitS=entry["recruit_s"],
                         TotalS=entry["total_s"])

    async def _maybe_flip_region(self) -> bool:
        """Region failover decision (reference: ClusterController bestDC /
        region preference): flip to the standby when the ACTIVE region's
        chain is completely unreachable — no sequencer, tlog, resolver or
        proxy process answers — while the standby has a full chain up.
        Gated on several consecutive all-dark probes so one slow sweep
        can't move the transaction subsystem across regions; partial
        liveness always heals IN region (the normal generation change).
        Salvage correctness is the caller's concern: it only reaches here
        when no chain tlog was lockable, and the satellites it DID lock
        hold every acked commit."""
        reachable: list = []
        region = self.regions[self.active_region]
        probes = [
            (role, i, self._probe(role, i, "ping"))
            for role in REGION_CHAIN_ROLES
            for i in region.get(role, [])
        ]
        for role, i, t in probes:
            try:
                await t
                reachable.append((role, i))
            except Exception:
                continue
        if reachable:
            self._region_blackouts = 0
            return False
        self._region_blackouts += 1
        if self._region_blackouts < 3:
            return False
        standby = self._standby_region()
        sb = self.regions[standby]
        for role in REGION_CHAIN_ROLES:
            alive = 0
            for i in sb.get(role, []):
                try:
                    await self._probe(role, i, "ping")
                    alive += 1
                    break
                except Exception:
                    continue
            if not alive:
                return False  # standby not viable either — keep waiting
        print(f"[controller] REGION FAILOVER: {self.active_region} dark, "
              f"moving transaction subsystem to {standby}",
              file=sys.stderr, flush=True)
        self.active_region = standby
        self._region_blackouts = 0
        self._save_maintenance()
        return True

    async def _learn_db_flags(self) -> None:
        """Probe every spec proxy for its database flags before recruiting
        the next generation — covers the controller-restart path where no
        sweep has cached them yet. Keeps the cache when nothing answers
        (all proxies dead: the last swept values are the best evidence)."""
        answers = []
        for i in range(len(self.spec["proxy"])):
            try:
                d = await self._probe("proxy", i)
            except Exception:
                continue
            if d.get("epoch", 0) > 0 and "backup_enabled" in d:
                answers.append(d)
        if answers:
            self.backup_active = any(d["backup_enabled"] for d in answers)
            self.db_locked = any(d.get("locked") for d in answers)

    def _chain_tlog_idx(self) -> list[int]:
        """The active region's chain tlog spec indices (all, pre-
        maintenance); every index in single-region clusters."""
        r = self._region_idx("tlog")
        return r if r is not None else list(range(len(self.spec["tlog"])))

    async def _all_tlogs_fresh(self) -> bool:
        """Every (active-region) chain tlog worker answers AND serves no
        recruited tlog."""
        for i in self._chain_tlog_idx():
            try:
                d = await self._probe("tlog", i)
            except Exception:
                return False
            if d.get("epoch", 0) != 0:
                return False
        return True

    async def _probe_live(self) -> dict:
        """Which spec processes answer right now (the recruitable set),
        probed concurrently. Includes `sequencer`: [0] or [] — recovery
        cannot complete without the one sequencer process and waits for
        fdbmonitor to bring it back."""
        roles = ["sequencer", "tlog", "resolver", "proxy", "storage"]
        if self.spec.get("satellite_tlog"):
            roles.append("satellite_tlog")
        tasks = [
            (role, i, self._probe(role, i, "ping"))
            for role in roles
            for i in range(len(self.spec[role]))
        ]
        live: dict[str, list[int]] = {r: [] for r in roles}
        for role, i, t in tasks:
            try:
                await t
                live[role].append(i)
            except Exception:
                continue
        for role in ("tlog", "resolver", "proxy"):
            live[role] = self._admitted(role, live[role])
        return live


def _bump_epoch(data_dir: str, floor: int = 0) -> int:
    """Advance and persist the recovery generation (reference: the recovery
    count in the coordinators' state). First durable restart → epoch 2.
    `floor`: a live generation epoch observed elsewhere — the bump must
    exceed it even if this data dir's counter lags (e.g. it was wiped)."""
    path = os.path.join(data_dir, "epoch")
    try:
        with open(path) as f:
            epoch = int(f.read().strip()) + 1
    except (OSError, ValueError):
        epoch = 2
    epoch = max(epoch, floor + 1)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write(str(epoch))
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return epoch


def build_role(loop: RealLoop, t: NetTransport, spec: dict, role: str,
               index: int, data_dir: str | None) -> None:
    """Construct and serve one role instance on transport `t`.

    Two wiring modes:
    - static (no `controller` in the spec): every role self-wires from the
      spec at boot; restart recovery is the full-bounce boot_sequencer
      sync below. Chain-role failure needs a full bounce.
    - managed (`controller` names a process): chain roles serve only a
      Worker; the DeployedController forms generations over RPC and heals
      chain-role failures with a generation change (reference: fdbserver
      workers + ClusterController recruitment).
    """
    managed = bool(spec.get("controller"))
    seq_addr = parse_addr(spec["sequencer"][0])
    n_storages = len(spec["storage"])
    n_tlogs = len(spec["tlog"])
    resolver_map = resolver_shard_map(spec)
    storage_map = storage_shard_map(spec)

    def eps(role_name: str, service: str | None = None):
        service = service or role_name
        return [t.endpoint(parse_addr(a), service) for a in spec[role_name]]

    if role == "controller":
        cc = DeployedController(loop, t, spec, data_dir)
        t.serve("controller", cc)

        async def boot_controller():
            await cc.bootstrap()
            loop.spawn(cc.run(), name="controller.run")

        return loop.spawn(boot_controller(), name="controller.boot")
    if managed and role in ("sequencer", "resolver", "tlog",
                            "satellite_tlog"):
        if role == "resolver" and spec.get("engine") == "tpu":
            # Every generation's recruit_resolver builds a fresh engine of
            # the same shapes; compiling them once here, before `ready`,
            # keeps the compile out of the recovery's recruit RPC.
            make_engine(spec, f"resolver{index}")
        t.serve("worker", Worker(loop, t, spec, role, index, data_dir))
        return None
    if role == "satellite_tlog":
        raise ValueError("satellite_tlog requires managed mode (controller)")
    if managed and role == "proxy":
        t.serve("worker", Worker(loop, t, spec, role, index, data_dir))
        router = ReadRouter(storage_map, eps("storage"), loop=loop)
        t.serve("read_router", router)
        t.serve("storage0", router)  # C client default service name
        return None
    if role == "sequencer":
        from foundationdb_tpu.runtime.sequencer import Sequencer

        if data_dir is None:
            # Memory-only cluster: fresh chain at version 0, serve now
            # (the restart sync below exists to reconcile durable state).
            t.serve("sequencer", Sequencer(loop))
            return None

        async def boot_sequencer():
            # Deployed durable restart: the static-wiring slice of the
            # sim's recovery. Chain start derives from the MINIMUM
            # recovered tlog end — an ack required every tlog's fsync, so
            # entries above the minimum are an unacked suffix present on
            # only some logs; serving them would apply a transaction on
            # some shards and not others. Those suffixes are truncated,
            # then every chain consumer (tlogs, resolvers) adopts the
            # jumped start.
            ends = []
            deadline = loop.now + 120.0
            for ep in eps("tlog"):
                while True:
                    try:
                        ends.append(await ep.get_version())
                        break
                    except Exception:
                        if loop.now > deadline:
                            raise TimeoutError(
                                "tlogs unreachable during restart sync")
                        await loop.sleep(0.3)  # tlog not up yet
            minv = min(ends) if ends else 0
            maxv = max(ends) if ends else 0
            if minv == 0 and maxv > 0:
                # Mixed state: some tlogs recovered data, at least one came
                # up empty (lost/blank disk queue). Falling through to the
                # fresh-cluster branch would restart the chain at version 0
                # while recovered tlogs still hold higher versions — their
                # duplicate check would false-ack new pushes without
                # appending them (silent data loss). Refuse to start; the
                # operator must either restore the missing queue file or
                # wipe the data dir to accept the loss explicitly.
                raise RuntimeError(
                    f"mixed tlog recovery state (ends={ends}): some disk "
                    "queues recovered data, some are empty — refusing to "
                    "start. Restore the missing tlog queue or clear the "
                    "data dir to accept data loss."
                )
            if minv > 0:
                # get_version reports last_entry+1 for a recovered log;
                # entries strictly above minv-1 are the unacked suffix.
                for ep in eps("tlog"):
                    await ep.truncate_to(minv - 1)
                # Recovery generation persists across bounces (reference:
                # the coordinated state's recovery count) — each durable
                # restart with recovered data starts a new epoch.
                epoch = _bump_epoch(data_dir)
                seq = Sequencer(loop, epoch=epoch, recovery_version=minv)
                for ep in eps("tlog") + eps("resolver"):
                    while True:
                        try:
                            await ep.begin_epoch(seq.last_handed_out)
                            break
                        except Exception:
                            await loop.sleep(0.3)
            else:
                seq = Sequencer(loop)
            t.serve("sequencer", seq)

        return loop.spawn(boot_sequencer(), name="sequencer.boot")
    elif role == "resolver":
        t.serve("resolver", make_resolver(loop, spec, f"resolver{index}"))
    elif role == "tlog":
        from foundationdb_tpu.runtime.tlog import TLog

        if data_dir:
            disk = os.path.join(data_dir, f"tlog{index}.q")
            tlog = TLog.from_disk(loop, disk)
        else:
            tlog = TLog(loop)
        tlog.system_token = _system_token(spec)  # gates entries_snapshot
        t.serve("tlog", tlog)
    elif role == "storage":
        from foundationdb_tpu.runtime.kvstore import make_kvstore
        from foundationdb_tpu.runtime.storage import StorageServer

        tlog_eps = eps("tlog")
        # Engine choice (reference: DatabaseConfiguration storage engine
        # `ssd-2` vs `ssd-redwood-1`): spec key `storage_engine`.
        kv = (make_kvstore(
                  os.path.join(data_dir, f"storage{index}.db"),
                  spec.get("storage_engine", "sqlite"))
              if data_dir else None)
        ss = StorageServer(
            loop, tag=index, tlog_ep=tlog_eps[index % n_tlogs],
            tlog_replicas=tlog_eps, kvstore=kv, authz=_make_authz(spec),
        )
        ss.tenant_mirror = _make_tenant_mirror(
            loop, t, spec, storage_map,
            lambda name, mk: _supervise(loop, name, mk))
        ss.system_token = _system_token(spec)
        smap = storage_map
        if any(len(sh.team) > 1 for sh in smap.shards):
            # Replicated deployment: serve ONLY this replica's team
            # shards (the serve-set guard — a replica outside a shard's
            # team has no tag stream for it and would answer with
            # missing data instead of wrong_shard_server).
            ss.init_served([
                (sh.range.begin, sh.range.end)
                for sh in smap.shards if index in sh.team
            ])
        t.serve("storage", ss)
        _supervise(loop, f"storage{index}.run", ss.run)
        if managed:
            # Long-lived data role: serves reads from boot; the controller
            # re-points its pull loop at each new generation's tlogs.
            w = Worker(loop, t, spec, role, index, data_dir)
            w.storage = ss
            t.serve("worker", w)
    elif role == "proxy":
        from foundationdb_tpu.runtime.commit_proxy import CommitProxy
        from foundationdb_tpu.runtime.grv_proxy import GrvProxy

        seq_ep = t.endpoint(seq_addr, "sequencer")
        rk = spec.get("ratekeeper") or []
        rk_ep = t.endpoint(parse_addr(rk[0]), "ratekeeper") if rk else None
        from foundationdb_tpu.core.types import wave_commit_env_default

        proxy = CommitProxy(
            loop, seq_ep, eps("resolver"), resolver_map,
            eps("tlog"), storage_map,
            authz=_make_authz(spec),
            tenant_mirror=_make_tenant_mirror(
                loop, t, spec, storage_map,
                lambda name, mk: _supervise(loop, name, mk)),
            admission=_make_admission_policy(),
            wave_commit=wave_commit_env_default(),
            wave_batch_limit=DEPLOYED_WAVE_BATCH_LIMIT,
        )
        # Static wiring: epoch 0 = unfenced (no recruitment protocol).
        # GrvProxy skips the per-batch confirm_epoch fan-out at epoch 0 —
        # the fence check is vacuous there and the tlog round trip is
        # pure latency in the common read path; lock detection rides the
        # normal commit/read paths instead (r5 review finding).
        grv = GrvProxy(loop, seq_ep, rk_ep, tlog_eps=eps("tlog"))
        router = ReadRouter(storage_map, eps("storage"), loop=loop)
        t.serve("commit_proxy", proxy)
        t.serve("grv_proxy", grv)
        t.serve("read_router", router)
        t.serve("storage0", router)  # C client default service name

        async def run_once_linked():
            # Static wiring has no recovery. A batch whose resolve or push
            # fails because a peer does not listen YET (roles boot in any
            # order, and the retry ladder is a third of a second) leaves a
            # gap in the version chain that nothing ever fills: that peer
            # waits for the lost version, every later batch waits behind
            # it, and no commit is acknowledged again. So this proxy asks
            # for no commit version until every chain peer has answered it
            # once; clients' commits queue meanwhile.
            for call in ([ep.get_metrics for ep in eps("resolver")]
                         + [ep.get_version for ep in eps("tlog")]):
                while True:
                    try:
                        await call()
                        break
                    except BrokenPromise:
                        await loop.sleep(0.1)
            await proxy.run()

        _supervise(loop, f"proxy{index}.run", run_once_linked)
        _supervise(loop, f"grv{index}.run", grv.run)
    elif role == "ratekeeper":
        from foundationdb_tpu.runtime.ratekeeper import Ratekeeper

        rk = Ratekeeper(loop, eps("storage"), eps("tlog"),
                        proxy_eps=eps("proxy", "commit_proxy"),
                        resolver_eps=eps("resolver"))
        t.serve("ratekeeper", rk)
        _supervise(loop, "ratekeeper.run", rk.run)
        # TimeKeeper rides in the FIRST ratekeeper process only (the
        # deployed wiring has no cluster controller; the reference hosts
        # exactly one, in the CC — duplicates would double idle commits
        # and overwrite each other's same-second samples).
        if index != 0:
            return
        from foundationdb_tpu.client.ryw import RYWTransaction
        from foundationdb_tpu.client.transaction import Database
        from foundationdb_tpu.runtime.timekeeper import TimeKeeper

        tk_db = Database(
            loop,
            eps("proxy", "grv_proxy"),
            eps("proxy", "commit_proxy"),
            storage_shard_map(spec),
            eps("storage"),
        )
        tk_db.transaction_class = RYWTransaction
        tk = TimeKeeper(loop, tk_db, token=_system_token(spec))
        _supervise(loop, "timekeeper.run", tk.run)
    else:
        raise ValueError(f"unknown role {role!r}")


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(
        prog="python -m foundationdb_tpu.server",
        description="Serve cluster roles over TCP (fdbserver analogue).",
    )
    ap.add_argument("--cluster", required=True, help="cluster spec JSON path")
    ap.add_argument("--role", required=True, choices=ROLES)
    ap.add_argument("--index", type=int, default=0,
                    help="which address of the role's list is mine")
    ap.add_argument("--data-dir", default=None,
                    help="durable state directory (tlog disk queue, "
                         "storage sqlite); default: memory only")
    ap.add_argument("--bind", default=None,
                    help="host:port to BIND instead of the spec's address "
                         "for this role — used when an interposing relay "
                         "(chaos partition injector) owns the advertised "
                         "address and forwards here")
    ap.add_argument("--trace-dir", default=None,
                    help="write rolling JSONL trace files here "
                         "(reference: fdbserver --logdir)")
    ap.add_argument("--trace-max-files", type=int, default=16,
                    help="retention cap on this process's rolled "
                         "trace.*.jsonl files (oldest deleted beyond it; "
                         "0 = unlimited)")
    args = ap.parse_args(argv)

    spec = load_spec(args.cluster)  # resolves authz_public_key to absolute
    addrs = spec.get(args.role) or []
    if not 0 <= args.index < len(addrs):
        raise SystemExit(
            f"--index {args.index} out of range for role {args.role} "
            f"({len(addrs)} addresses in spec)"
        )
    host, port = parse_addr(args.bind if args.bind
                            else addrs[args.index])
    if args.data_dir:
        os.makedirs(args.data_dir, exist_ok=True)

    loop = RealLoop()
    loop.role = args.role  # names this process's loop_busy / loop_idle
    from foundationdb_tpu.runtime.trace import Tracer

    tracer = Tracer(loop, trace_dir=args.trace_dir,
                    process=f"{args.role}{args.index}",
                    max_files=args.trace_max_files or None)
    # Commit-path tracing (obs subsystem, FDB_TPU_OBS=1): one span sink
    # per process; this process's stage histograms are scraped via the
    # admin obs_snapshot RPC (cli `latency` / metrics tooling).
    from foundationdb_tpu.obs.span import SpanSink, obs_env_default

    span_sink_obj = (SpanSink(loop) if obs_env_default() else None)
    t = NetTransport(loop, host=host, port=port,
                     tls=tls_config(spec, args.cluster))
    boot = build_role(loop, t, spec, args.role, args.index, args.data_dir)
    if boot is not None:
        # The role defers serving behind a boot task (sequencer restart
        # sync): the readiness line must not print until it serves, or
        # supervisors/tests proceed against a process that cannot answer.
        loop.run_until(boot, timeout=300)

    from foundationdb_tpu.runtime.flow import Promise

    class _Admin:
        """Process-control surface (reference: fdbcli `kill` asks a
        worker to exit; fdbmonitor restarts it)."""

        def __init__(self):
            self.stopped = Promise()

        @rpc
        async def shutdown(self) -> str:
            tracer.event("ProcessShutdownRequested", Role=args.role,
                         Index=args.index)
            # Resolve AFTER replying: the @rpc reply is written when this
            # coroutine returns; a zero-delay timer runs strictly later
            # on the loop, so the exit can't race the reply flush.
            loop.spawn(self._finish(), name="admin.shutdown")
            return "shutting down"

        @rpc
        async def inject_fault(self, host: str, port: int, mode: str,
                               delay_s: float = 0.05,
                               duration_s: float = 5.0) -> str:
            """Operator-triggered network fault from THIS process toward
            (host, port): "drop" black-holes its outbound calls (a
            one-sided partition), "delay" defers them (clog). The chaos
            harness for deployed clusters — the TCP analogue of the sim
            campaign's partition/clog injection. Auto-expires."""
            tracer.event("FaultInjected", Role=args.role, Index=args.index,
                         Peer=f"{host}:{port}", Mode=mode,
                         Duration=duration_s)
            t.set_fault((host, int(port)), mode, delay_s, duration_s)
            return f"fault {mode} -> {host}:{port} for {duration_s}s"

        @rpc
        async def clear_faults(self) -> str:
            t.clear_faults()
            return "faults cleared"

        @rpc
        async def obs_snapshot(self) -> dict:
            """This process's span-sink dump (mergeable histograms) +
            breakdown — the deployed scrape surface for commit-path
            stage attribution (obs subsystem; None when FDB_TPU_OBS is
            off)."""
            if span_sink_obj is None:
                return {"enabled": False}
            return {"enabled": True,
                    "breakdown": span_sink_obj.breakdown(),
                    "dump": span_sink_obj.dump()}

        async def _finish(self):
            await loop.sleep(0)
            self.stopped.send(None)

    admin = _Admin()
    t.serve("admin", admin)
    # Flight recorder (obs subsystem, FDB_TPU_RECORDER=<ring path>): the
    # controller process doubles as the cluster's always-on recorder —
    # periodic deployed scrapes with explicit scrape_gap records, derived
    # annotations, and SLO tracking onto a bounded on-disk ring
    # (obs/recorder.py; `cli doctor` / --doctor read it back). Controller
    # only: it is the one role whose lifetime spans recoveries of the
    # others, and a recorder that dies with its subject records nothing.
    recorder = None
    if args.role == "controller" and os.environ.get("FDB_TPU_RECORDER"):
        from foundationdb_tpu.obs.recorder import FlightRecorder
        from foundationdb_tpu.obs.registry import scrape_deployed_async

        recorder = FlightRecorder(
            loop, lambda: scrape_deployed_async(loop, t, spec),
            os.environ["FDB_TPU_RECORDER"],
            interval_s=float(
                os.environ.get("FDB_TPU_RECORDER_INTERVAL") or 5.0),
        )
        loop.spawn(recorder.run(), name="controller.flight_recorder")
    tracer.event("ProgramStart", Role=args.role, Index=args.index,
                 Address=f"{t.addr[0]}:{t.addr[1]}")
    print(f"ready {args.role}{args.index} on {t.addr[0]}:{t.addr[1]}",
          flush=True)

    async def until_shutdown():
        await admin.stopped.future
        await loop.sleep(0.05)  # one select() round: reply bytes on the wire

    try:
        loop.run(until_shutdown(), timeout=float("inf"))
    except KeyboardInterrupt:
        pass
    finally:
        if recorder is not None:
            recorder.close()  # ring file stays — it IS the artifact
        tracer.close()
        t.close()


if __name__ == "__main__":
    main()
