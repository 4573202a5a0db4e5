"""TPUReadSet: resident key-universe mirror + one probe per dispatch.

The read-plane analogue of ``TPUConflictSet``'s resident dictionary
(models/conflict_set.py): the versioned map's sorted key
universe is packed ONCE into ``[n, W]`` int32 rows (core/keypack.py) and
stays resident — in HBM on the device arm, as the u64-column host mirror
otherwise — across dispatches. A dispatch packs only its queries and runs
one two-sided search (``ops/lex.searchsorted_words_2sided_fp`` jitted on
device; the same column-cascade in numpy on host) that answers every point
lookup and range boundary of the batch at once. Values then gather
host-side from the per-key version chains, which keeps every arm
byte-identical to the scalar ``VersionedMap.at`` oracle:

- point hit: the equal-packed-row run from the two-sided search is
  confirmed by exact bytes (packed rows truncate at ``max_key_bytes``),
  then the chain resolves at the read version exactly as ``at()`` does;
- range: the conservative packed bounds are tightened by an advance-only
  byte compare at the run edges (truncation rounds down, so packed bounds
  can only be LOW), then keys in [lo, hi) resolve per chain.

The mirror invalidates on key-universe changes only (``struct_seq`` on the
map — inserts, purges, rollback/GC removals); value updates mutate the
referenced chains in place and cost the mirror nothing. That is the same
economics as the resident conflict dictionary: rebuilds are the cold path,
steady-state reads ride the resident tensors.
"""

from __future__ import annotations

import bisect

import numpy as np

from foundationdb_tpu.core.keypack import INT32_MAX, KeyCodec, row_sort_keys


def reads_device_default() -> bool:
    """FDB_TPU_READS_DEVICE: probe on the jax device (default 0 = host)."""
    from foundationdb_tpu.core.types import env_choice

    return env_choice("FDB_TPU_READS_DEVICE", "0", ("0", "1")) == "1"


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


class TPUReadSet:
    """Batched point/range reads over a versioned map.

    `vmap` duck-types ``runtime.storage.VersionedMap``: sorted ``_keys``,
    ``_chains`` (key → ascending ``(version, value)`` chain), and a
    ``struct_seq`` counter bumped whenever the KEY SET changes."""

    MIN_QUERY_SLOTS = 8  # device query pad floor (bounds compile count)

    def __init__(self, vmap, codec: KeyCodec | None = None,
                 device: bool | None = None):
        self.vmap = vmap
        self.codec = codec or KeyCodec()
        self.device = reads_device_default() if device is None else bool(device)
        self._seq = None  # mirror generation (vmap.struct_seq at build)
        self._keys: list[bytes] = []
        self._chains: list[list[tuple[int, bytes | None]]] = []
        self._void = row_sort_keys(
            np.zeros((0, self.codec.width), np.int32))
        self._dev_rows = None
        self._probe = None  # jitted two-sided search (device arm)
        self.stats = {
            "rebuilds": 0, "uploads": 0, "probes": 0,
            "point_reads": 0, "range_reads": 0, "pack_s": 0.0,
        }

    # -- mirror maintenance ---------------------------------------------------

    def _sync(self) -> None:
        seq = getattr(self.vmap, "struct_seq", 0)
        if self._seq == seq:
            return
        self._keys = list(self.vmap._keys)
        self._chains = [self.vmap._chains[k] for k in self._keys]
        rows = (self.codec.pack(self._keys, mode="begin") if self._keys
                else np.zeros((0, self.codec.width), np.int32))
        # memcmp-order void view: one native np.searchsorted call answers
        # a whole dispatch on the host arm (C-speed, no per-column pass).
        self._void = row_sort_keys(rows)
        self._seq = seq
        self.stats["rebuilds"] += 1
        if self.device:
            import jax.numpy as jnp

            cap = max(1, _next_pow2(len(self._keys)))
            padded = np.full((cap, self.codec.width), INT32_MAX, np.int32)
            padded[: len(self._keys)] = rows
            self._dev_rows = jnp.asarray(padded)
            self.stats["uploads"] += 1
            if self._probe is None:
                import jax

                from foundationdb_tpu.ops.lex import searchsorted_words_2sided_fp

                self._probe = jax.jit(searchsorted_words_2sided_fp)

    def _search2(self, q_rows: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(left, right) bounds of each query row in the resident mirror —
        the one vectorized search a dispatch pays."""
        self.stats["probes"] += 1
        if self.device and self._dev_rows is not None:
            k = q_rows.shape[0]
            slots = max(self.MIN_QUERY_SLOTS, _next_pow2(k))
            qpad = np.full((slots, q_rows.shape[1]), INT32_MAX, np.int32)
            qpad[:k] = q_rows
            lo, hi = self._probe(self._dev_rows, qpad)
            n = len(self._keys)
            return (np.minimum(np.asarray(lo)[:k], n),
                    np.minimum(np.asarray(hi)[:k], n))
        qv = row_sort_keys(np.ascontiguousarray(q_rows))
        return (np.searchsorted(self._void, qv, side="left"),
                np.searchsorted(self._void, qv, side="right"))

    # -- value resolution (host gather; identical to VersionedMap.at) --------

    def _value_at(self, idx: int, version: int) -> bytes | None:
        chain = self._chains[idx]
        last_v, last_val = chain[-1]
        if last_v <= version:
            return last_val
        i = bisect.bisect_right(chain, version, key=lambda e: e[0]) - 1
        return None if i < 0 else chain[i][1]

    # -- batched reads --------------------------------------------------------

    def get_points(self, keys: list[bytes], versions) -> list[bytes | None]:
        """One batched lookup: values of `keys` at `versions` (an int, or a
        per-key sequence — the coalescer merges requests at different read
        versions into one probe; the search is version-independent)."""
        self._sync()
        out: list[bytes | None] = [None] * len(keys)
        self.stats["point_reads"] += len(keys)
        if not keys or not self._keys:
            return out
        if isinstance(versions, int):
            versions = [versions] * len(keys)
        from time import perf_counter

        t0 = perf_counter()
        q = self.codec.pack(keys, mode="begin")
        self.stats["pack_s"] += perf_counter() - t0
        lo, hi = self._search2(q)
        for j, key in enumerate(keys):
            for i in range(int(lo[j]), int(hi[j])):
                if self._keys[i] == key:
                    out[j] = self._value_at(i, versions[j])
                    break
        return out

    def get_ranges(self, reqs) -> list[list[tuple[bytes, bytes]]]:
        """Batched range reads. `reqs` is a list of
        ``(begin, end, limit, reverse, version)``; all boundary probes ride
        one search."""
        self._sync()
        self.stats["range_reads"] += len(reqs)
        if not reqs or not self._keys:
            return [[] for _ in reqs]
        from time import perf_counter

        t0 = perf_counter()
        bounds = [r[0] for r in reqs] + [r[1] for r in reqs]
        q = self.codec.pack(bounds, mode="begin")
        self.stats["pack_s"] += perf_counter() - t0
        lo, _hi = self._search2(q)
        n, m = len(self._keys), len(reqs)
        out = []
        for j, (begin, end, limit, reverse, version) in enumerate(reqs):
            a, b = int(lo[j]), int(lo[m + j])
            # Truncated packed bounds are conservative-LOW: advance by
            # exact bytes (bounded by the shared-prefix collision run).
            while a < n and self._keys[a] < begin:
                a += 1
            while b < n and self._keys[b] < end:
                b += 1
            idxs = range(b - 1, a - 1, -1) if reverse else range(a, b)
            rows: list[tuple[bytes, bytes]] = []
            for i in idxs:
                v = self._value_at(i, version)
                if v is not None:
                    rows.append((self._keys[i], v))
                    if len(rows) >= limit:
                        break
            out.append(rows)
        return out

    # -- the sequential oracle ------------------------------------------------

    def oracle_get(self, key: bytes, version: int) -> bytes | None:
        """Scalar reference read (VersionedMap.at semantics, no mirror):
        the parity baseline every batched arm must match byte-for-byte."""
        chain = self.vmap._chains.get(key)
        if not chain:
            return None
        i = bisect.bisect_right(chain, version, key=lambda e: e[0]) - 1
        return None if i < 0 else chain[i][1]

    def oracle_range(self, begin: bytes, end: bytes, limit: int,
                     reverse: bool, version: int) -> list[tuple[bytes, bytes]]:
        keys = self.vmap._keys
        a = bisect.bisect_left(keys, begin)
        b = bisect.bisect_left(keys, end)
        idxs = range(b - 1, a - 1, -1) if reverse else range(a, b)
        rows: list[tuple[bytes, bytes]] = []
        for i in idxs:
            v = self.oracle_get(keys[i], version)
            if v is not None:
                rows.append((keys[i], v))
                if len(rows) >= limit:
                    break
        return rows
