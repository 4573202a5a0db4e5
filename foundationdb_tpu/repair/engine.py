"""Client-side transaction repair: partial re-execution instead of restart.

The naive retry loop (``Database.run`` / ``Transaction.on_error``) treats a
conflict (error 1020) like any other retryable failure: exponential
backoff, a fresh GRV round trip, then a full re-read and re-derivation of
every mutation. Under Zipf-style hot-key contention (the north-star
workload runs at 0.6-0.7 conflict rate) that throws away almost all of the
losing attempt's work, even though the resolver already computed *which*
read ranges lost. This module keeps the rest:

- ``RepairableTransaction`` records every storage fetch (point reads and
  fully-scanned range spans) in a per-attempt read cache.
- On NotCommitted carrying a conflicting-keys report and the failed
  batch's commit version ``fail_cv`` (both attached by the commit proxy),
  ``run_repairable`` invalidates only the cached reads overlapping the
  loser ranges, pins the next attempt's read version to ``fail_cv - 1``,
  and replays the transaction body: unconflicted reads are served from
  the cache (zero storage traffic), conflicted ones re-fetch, mutations
  are re-derived, and the resubmit needs NO fresh GRV.

Serializability argument (checked against sim/oracle.py by
tests/test_repair.py and the bench harness):

1. The failed attempt submitted its FULL read-conflict set at read
   version ``rv0``; the resolver evaluated every range and reported the
   losers — so every unreported range had no overlapping write in
   ``(rv0, fail_cv - 1]`` (prior batches commit strictly below fail_cv).
   Cached values of unreported ranges therefore equal snapshot
   ``fail_cv - 1`` exactly.
2. Reported ranges are re-read at ``fail_cv - 1``, so the replayed body
   observes exactly the snapshot at ``fail_cv - 1``.
3. The resubmit again carries the full read-conflict set, now at read
   version ``fail_cv - 1``; the resolver re-validates every range over
   ``(fail_cv - 1, cv2]``. That window INCLUDES ``fail_cv`` — so writes
   by same-batch winners (which land exactly at fail_cv and are not in
   any loser report) are caught and simply trigger another repair round
   at the newer version. Soundness never depends on report completeness
   beyond history conflicts, which every engine provides (the oracle and
   the TPU kernel report exactly; engines without reporting degrade to
   the conservative all-ranges superset in runtime/resolver.py).

Step 1 is per-ROUND: only cache entries the latest failed attempt's
read-conflict set covered carry its validation forward. An entry a replay
round skipped (divergent control flow) drops out — ``begin_repair``
deletes it rather than serving a value no round's window re-validates.

Hot-range backoff: the proxy piggybacks its decayed conflict-odds sketch
scores for the loser ranges (see repair/hotrange.py); when the odds say
immediate retry is futile the engine sleeps a jittered, score-scaled
backoff first — contention-aware, unlike on_error's blind doubling.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable

from foundationdb_tpu.client.ryw import RYWTransaction
from foundationdb_tpu.core.errors import FdbError, NotCommitted


@dataclass
class RepairConfig:
    """Knobs for the repair loop (documented in README.md)."""

    # Consecutive repair rounds per transaction before falling back to a
    # full restart (the attempt-bound convergence guarantee).
    max_repair_attempts: int = 4
    # Decayed loss score at/above which immediate retry is considered
    # futile and a jittered backoff is applied first.
    hot_score_threshold: float = 6.0
    # Backoff = min(cap, base * score, the failed commit's own round
    # trip) * jitter(0.5..1.5).
    hot_backoff_base: float = 0.002
    hot_backoff_cap: float = 0.25
    # Optional re-execution hook: ``await hook(tr, conflicting)`` runs
    # after the cache invalidation and may return False to decline the
    # repair (→ full restart). None = the default replay (the loop
    # re-runs the transaction body against the recorded read cache).
    reexecute: Callable | None = None


@dataclass
class RepairStats:
    """Counters the goodput harness and tests assert on."""

    commits: int = 0
    repaired_commits: int = 0  # commits that needed ≥1 repair round
    repair_rounds: int = 0
    full_restarts: int = 0
    declined: int = 0  # NotCommitted that could not be repaired
    hot_backoffs: int = 0
    cache_hits: int = 0  # replayed reads served without storage traffic
    ranges_invalidated: int = 0

    extra: dict = field(default_factory=dict)


class RepairableTransaction(RYWTransaction):
    """RYW transaction with a recorded read cache for repair replay.

    The cache sits BELOW the RYW overlay (the ``_fetch_key`` /
    ``_fetch_range`` seams of client/transaction.py), so replayed reads
    still pay their read-conflict ranges and still see the attempt's own
    uncommitted writes — only the storage round trip is skipped.
    """

    def __init__(self, db):
        super().__init__(db)
        # The repair engine needs loser reports on every conflict.
        self.report_conflicting_keys = True
        self.repair_stats: RepairStats | None = None

    def _reset(self) -> None:
        super()._reset()
        self._read_cache: dict[bytes, bytes | None] = {}
        self._span_cache: list[tuple[bytes, bytes, dict[bytes, bytes]]] = []
        self._replaying = False

    # -- recorded fetch seams -------------------------------------------------

    async def _fetch_key(self, key: bytes, version: int) -> bytes | None:
        if key in self._read_cache:
            if self._replaying and self.repair_stats is not None:
                self.repair_stats.cache_hits += 1
            return self._read_cache[key]
        for b, e, rows in self._span_cache:
            if b <= key < e:
                if self._replaying and self.repair_stats is not None:
                    self.repair_stats.cache_hits += 1
                return rows.get(key)
        value = await super()._fetch_key(key, version)
        self._read_cache[key] = value
        return value

    async def _fetch_range(
        self, begin: bytes, end: bytes, version: int, limit: int,
        reverse: bool,
    ) -> list[tuple[bytes, bytes]]:
        for b, e, rows in self._span_cache:
            if b <= begin and end <= e:
                if self._replaying and self.repair_stats is not None:
                    self.repair_stats.cache_hits += 1
                out = sorted(
                    (k, v) for k, v in rows.items() if begin <= k < end
                )
                if reverse:
                    out.reverse()
                return out[:limit]
        rows = await super()._fetch_range(begin, end, version, limit, reverse)
        if len(rows) < limit:
            # Exhausted scan: the whole span's membership is known, so it
            # can serve any sub-range (a truncated scan only knows a
            # prefix and is not cached).
            self._span_cache.append((begin, end, dict(rows)))
        return rows

    # -- repair transitions ---------------------------------------------------

    def begin_repair(self, read_version: int,
                     conflicting: list[tuple[bytes, bytes]]) -> None:
        """Start a repair round: drop cached reads overlapping the loser
        ranges, keep the rest of the VALIDATED reads, pin the snapshot to
        `read_version` (= fail_cv - 1, see the module docstring), and
        reset the attempt state for the replay.

        Only cache entries covered by the failed attempt's submitted
        read-conflict set survive: the soundness argument ("unreported ⇒
        unwritten through fail_cv − 1") holds exactly for ranges the
        resolver just validated. An entry a replay round did NOT read
        (divergent control flow) drops out of that set — keeping it would
        let a later round serve a value no round's conflict window covers
        (review find: stale read admitted through branchy bodies).

        The conflicting-keys stash survives so
        ``\\xff\\xff/transaction/conflicting_keys/`` stays readable
        mid-repair (reference: the special key space serves the LAST
        failed attempt's report until the next commit attempt)."""
        read_cache, span_cache = self._read_cache, self._span_cache
        validated = [r for r in self.read_ranges if not r.empty]
        stash = self._conflicting_ranges
        before = len(read_cache) + sum(len(r) for _b, _e, r in span_cache)
        self._reset()
        self._conflicting_ranges = stash

        def dead_key(k: bytes) -> bool:
            return any(b <= k < e for b, e in conflicting)

        def covered_key(k: bytes) -> bool:
            return any(r.begin <= k < r.end for r in validated)

        self._read_cache = {
            k: v for k, v in read_cache.items()
            if covered_key(k) and not dead_key(k)
        }
        self._span_cache = [
            (b0, e0, rows) for b0, e0, rows in span_cache
            if any(r.begin <= b0 and e0 <= r.end for r in validated)
            and not any(b0 < e and b < e0 for b, e in conflicting)
        ]
        if self.repair_stats is not None:
            kept = (len(self._read_cache)
                    + sum(len(r) for _b, _e, r in self._span_cache))
            self.repair_stats.ranges_invalidated += max(0, before - kept)
        self._replaying = True
        self.set_read_version(read_version)


async def run_repairable(db, fn, max_retries: int = 50,
                         config: RepairConfig | None = None,
                         stats: RepairStats | None = None):
    """Run ``await fn(tr)`` + commit with conflict REPAIR instead of the
    full-restart retry loop; falls back to ``on_error`` (reset + backoff
    + fresh GRV) whenever a conflict cannot be repaired or any other
    retryable error fires. Drop-in alternative to ``Database.run``."""
    config = config or RepairConfig()
    stats = stats if stats is not None else RepairStats()
    tr = RepairableTransaction(db)
    tr.repair_stats = stats
    repair_round = 0
    for _ in range(max_retries):
        asked = None
        try:
            result = await fn(tr)
            asked = db.loop.now
            await tr.commit()
            stats.commits += 1
            if repair_round:
                stats.repaired_commits += 1
            return result
        except NotCommitted as e:
            repaired = False
            if repair_round < config.max_repair_attempts:
                repaired = await _try_repair(
                    tr, e, config, stats,
                    turn=None if asked is None else db.loop.now - asked)
            if repaired:
                repair_round += 1
                stats.repair_rounds += 1
                continue
            stats.declined += repair_round < config.max_repair_attempts
            repair_round = 0
            stats.full_restarts += 1
            await tr.on_error(e)
        except FdbError as e:
            # Anything else retryable (FutureVersion mid-replay, killed
            # proxy, ...): the repair declines — full restart drops the
            # cache and takes the canonical recovery path.
            repair_round = 0
            stats.full_restarts += 1
            await tr.on_error(e)  # raises if not retryable
    raise FdbError("retry limit reached", code=1021)


async def _try_repair(tr: RepairableTransaction, e: NotCommitted,
                      config: RepairConfig, stats: RepairStats,
                      turn: "float | None" = None) -> bool:
    """Attempt to enter a repair round for this conflict; False = decline.
    `turn`: what the failed commit took, one turn of the commit pipeline
    as this client sees it."""
    ranges = e.conflicting_ranges
    fail_cv = e.fail_version
    if not ranges or fail_cv is None or fail_cv <= 0:
        return False  # nothing to repair against (old peer / no report)
    conflicting = [(bytes(b), bytes(end)) for b, end in ranges]
    # Contention-aware backoff: when the proxy's sketch says these ranges
    # are losing constantly, an immediate resubmit is near-certain to
    # lose again — sleep a jittered, score-scaled delay first. Never
    # much longer than the failed commit itself took: by then the batch
    # the contenders rode has been decided, and more sleep only idles
    # (it hid behind the reads' wait for the next push until PR 39;
    # without the bound the loop then LOSES to a full restart).
    odds = max((s for _b, _e2, s in (e.hot_ranges or [])), default=0.0)
    if odds >= config.hot_score_threshold:
        stats.hot_backoffs += 1
        delay = min(config.hot_backoff_cap, config.hot_backoff_base * odds)
        if turn is not None:
            delay = min(delay, turn)
        await tr.db.loop.sleep(delay * (0.5 + tr.db.loop.rng.random()))
    tr.begin_repair(fail_cv - 1, conflicting)
    if config.reexecute is not None:
        ok = await config.reexecute(tr, conflicting)
        if not ok:
            return False  # custom hook declined: caller full-restarts
    return True
