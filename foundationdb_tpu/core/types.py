"""Core value types: verdicts, key ranges, per-transaction conflict info.

Mirrors the reference's fdbserver/ConflictSet.h (ConflictBatch::TransactionCommitted /
TransactionConflict / TransactionTooOld) and fdbclient/FDBTypes.h (KeyRangeRef),
re-expressed as plain Python dataclasses; the device-side representation lives
in foundationdb_tpu.models.conflict_set as packed int32 tensors.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field

from foundationdb_tpu.core.errors import InvertedRange

# Limits matching the reference's fdbclient defaults (FDBTypes.h / Knobs).
MAX_KEY_SIZE = 10_000
MAX_VALUE_SIZE = 100_000
MAX_TRANSACTION_SIZE = 10_000_000

# THE canonical tenant-map location (reference: SystemData's tenant map
# prefix). One definition — client/tenant.py (management + resolution),
# runtime/authz.py (the read carve-out, a security boundary) and the
# commit proxies' live-map refresh all import it from here.
TENANT_MAP_PREFIX = b"\xff/tenant/map/"


class Verdict(enum.IntEnum):
    """Resolver verdict for one transaction in a batch.

    Values are the on-device int8 encoding; order matters (0 is the common
    fast-path so a padded/masked txn slot defaults to COMMITTED and is
    filtered host-side).
    """

    COMMITTED = 0
    CONFLICT = 1
    TOO_OLD = 2


# Wave-commit schedule levels (the reorder-don't-abort resolve mode —
# models/conflict_kernel.py phase 2b, sim/oracle.py): a committed txn's
# level is its commit wave (>= 0; serialization order = (level, batch
# index)), LEVEL_NONE marks non-commits for non-cycle reasons (history
# conflict, TOO_OLD, masked slot), LEVEL_CYCLE marks a true-dependency-
# cycle abort — the repair subsystem's residue. One definition here so the
# jax kernel, the pure-python oracle, and the runtime Resolver/commit
# proxy all agree without the runtime importing device code.
WAVE_LEVEL_NONE = -1
WAVE_LEVEL_CYCLE = -2


def env_choice(name: str, default: str, allowed: tuple[str, ...]) -> str:
    """Validated FDB_TPU_* env flag: an unknown value raises with the
    accepted list instead of silently falling through to the default (a
    typo'd value would otherwise run the default while claiming the
    variant). One definition here — importable WITHOUT
    device code — serves the kernel's import-once flags, the sim/server
    wave default, and the compile-cache knob alike."""
    import os

    value = os.environ.get(name, default)
    if value not in allowed:
        raise ValueError(
            f"{name}={value!r} is not a valid setting; accepted values: "
            f"{', '.join(allowed)}"
        )
    return value


def wave_commit_env_default() -> bool:
    """FDB_TPU_WAVE_COMMIT env default — the oracle engine, sim cluster,
    and deployed server must honor the same A/B env contract as the
    device kernel."""
    return env_choice("FDB_TPU_WAVE_COMMIT", "0", ("0", "1")) == "1"


def validate_wave_commit(n_resolvers: int = 1,
                         skiplist_engine: str | None = None,
                         wave_global_capable: bool = True) -> None:
    """Refuse deployments a wave-commit resolver cannot serve (call only
    when wave commit is ON). One definition of the rules — the sim
    cluster, its engine factory, and the deployed server must enforce
    identical refusals or a config drift silently un-serializes.

    - The C++ skiplist engines never materialize the conflict graph and
      implement no wave schedule; ``skiplist_engine`` is the caller's
      name for the engine ("cpu"/"cpp"), None when the engine supports
      wave commit.
    - Role-level multi-resolver deployments clip ranges per key shard,
      so a shard alone cannot serializably reorder — the deployment is
      legal exactly when every resolver's engine implements the GLOBAL
      wave protocol (resolve_edges/resolve_apply: per-shard clipped
      predecessor bitsets are OR-reduced into the global graph at the
      commit proxy and every shard levels that graph identically — see
      core/wavemesh.py). ``wave_global_capable`` is the caller's
      capability verdict for its engine; engines without the protocol
      keep the old single-resolver-only rule."""
    if skiplist_engine is not None:
        raise ValueError(
            f"wave commit is not implemented by the {skiplist_engine} "
            "skiplist engine"
        )
    if n_resolvers > 1 and not wave_global_capable:
        raise ValueError(
            "wave commit with multiple resolvers requires engines that "
            "implement the global edge-exchange protocol (resolve_edges/"
            "resolve_apply): per-shard resolvers each see only their "
            "clipped conflict edges, and a clipped-graph wave schedule "
            "is not serializable"
        )


@dataclass(frozen=True)
class KeyRange:
    """Half-open byte-string key range [begin, end)."""

    begin: bytes
    end: bytes

    def __post_init__(self):
        if self.end < self.begin:
            raise InvertedRange(f"inverted range {self.begin!r} > {self.end!r}")

    @property
    def empty(self) -> bool:
        return self.begin == self.end

    def contains(self, key: bytes) -> bool:
        return self.begin <= key < self.end

    def overlaps(self, other: "KeyRange") -> bool:
        return self.begin < other.end and other.begin < self.end


def single_key_range(key: bytes) -> KeyRange:
    """The conflict range for a point read/write: [key, keyAfter(key))."""
    return KeyRange(key, key + b"\x00")


def strinc(key: bytes) -> bytes:
    """First key not prefixed by `key` (reference: flow strinc()).

    Strips trailing 0xff bytes then increments the last byte; an all-0xff or
    empty key has no upper bound and raises.
    """
    stripped = key.rstrip(b"\xff")
    if not stripped:
        raise ValueError(f"strinc has no result for {key!r}")
    return stripped[:-1] + bytes([stripped[-1] + 1])


@dataclass
class TxnConflictInfo:
    """One transaction's resolver-visible payload.

    Mirrors CommitTransactionRef's read_conflict_ranges / write_conflict_ranges
    / read_snapshot_version (reference: fdbclient/CommitTransaction.h).
    """

    read_version: int
    read_ranges: list[KeyRange] = field(default_factory=list)
    write_ranges: list[KeyRange] = field(default_factory=list)
    # report_conflicting_keys: when True the resolver also returns which read
    # ranges lost (reference: report_conflicting_keys option).
    report_conflicting_keys: bool = False
