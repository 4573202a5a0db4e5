"""Transaction log: the durability point of the commit path.

Reference: fdbserver/TLogServer.actor.cpp — commit proxies push each batch's
mutations tagged by destination storage server; the push is acknowledged
only after fsync; storage servers pull their tag with peek/pop and the log
trims below the popped version. Pushes carry (prev_version, version) and
are applied in chain order, like the resolver. Recovery locks the log,
freezing its end version.
"""

from __future__ import annotations

import bisect

from dataclasses import dataclass

from foundationdb_tpu.core.mutations import Mutation
from foundationdb_tpu.obs.span import span_now, span_sink
from foundationdb_tpu.runtime.flow import Loop, Promise, rpc


@dataclass(frozen=True)
class TLogEntry:
    version: int
    # tag -> mutations bound for that storage server
    tagged: dict[int, list[Mutation]]

    @property
    def nbytes(self) -> int:
        return sum(
            len(m.param1) + len(m.param2) + 8
            for muts in self.tagged.values()
            for m in muts
        )


class TLogLocked(Exception):
    """Pushed after recovery locked this log (reference: tlog_stopped)."""


class TLog:
    FSYNC_SECONDS = 0.0005  # simulated durable-write latency per push
    # In-memory budget for the un-popped suffix (reference: TLog
    # SPILLING — SpilledData moves committed-but-unpopped data out of
    # memory). A dead replica that never pops its tag then pins DISK,
    # not RAM: entries beyond the budget drop out of the in-memory list
    # and are served back from the disk queue (which already holds every
    # pushed entry durably). Memory-only tlogs (no disk_path) cannot
    # spill and keep the unbounded-but-honest old behavior.
    SPILL_BYTES = 64 << 20
    SPILL_CACHE_TTL = 10.0  # release the spill-read cache when cold

    def __init__(
        self,
        loop: Loop,
        init_version: int = 0,
        seed: list[tuple[int, dict[int, list[Mutation]]]] | None = None,
        retired_tags: set[int] | None = None,
        disk_path: str | None = None,
        disk_preserved: bool = False,
        epoch: int = 0,
    ):
        """`seed`: prior-generation entries salvaged by recovery (versions
        all < init_version); storage servers finish pulling them from this
        log as if the old generation had never died. `retired_tags`: tags
        that will never pull again (stopped backups) — excluded from the
        trim floor even if seed entries or late pushes still carry them.
        `disk_path`: append-only disk queue — pushes are written + fsync'd
        before the ack, so acknowledged commits survive a full-cluster
        restart (runtime/diskqueue.py; reference: the tlog's DiskQueue)."""
        self.loop = loop
        self.disk = None
        if disk_path is not None:
            from foundationdb_tpu.runtime.diskqueue import DiskQueue

            self.disk = DiskQueue(disk_path, preserve=disk_preserved)
            if seed and not disk_preserved:
                # salvaged entries must be durable in OUR file too (when
                # preserved, the seed IS the file's recovered content)
                for v, t in seed:
                    self.disk.append((v, t))
                self.disk.fsync()
        self._log: list[TLogEntry] = [TLogEntry(v, t) for v, t in (seed or [])]
        assert all(e.version < init_version for e in self._log)
        # Running queue size (ratekeeper polls every 100 ms; recounting the
        # whole log there would be O(queue) exactly when the queue is huge).
        # _queue_bytes counts the WHOLE un-popped suffix (incl. spilled —
        # the ratekeeper must see spilled backlog); _mem_bytes only what
        # is resident (the spill criterion).
        self._queue_bytes = sum(e.nbytes for e in self._log)
        self._mem_bytes = self._queue_bytes
        # Spilled region bookkeeping: (version, nbytes) per spilled entry
        # — tiny — so trims can account bytes and salvage knows exactly
        # which disk records are live without trusting file contents
        # below the floor.
        self._spilled_meta: list[tuple[int, int]] = []
        self._spilled_through = 0  # entries <= this live on disk only
        # Parsed spill-region cache, INCREMENTALLY maintained (review
        # findings: rebuilding it from a full-file read on every spill
        # event made laggard catch-up O(spill_events x history), and
        # never evicting it kept a multi-GB backlog resident forever):
        # built from ONE disk read on the first spilled peek, extended
        # in memory as further entries spill (they are at hand then —
        # no disk read), shrunk by trims, and RELEASED when a peek shows
        # the caller is past the spilled region. A parallel sorted
        # version list gives bisect paging (tiny_peek would otherwise
        # rescan from the front per single-entry page).
        self._spill_cache: list | None = None
        self._spill_cache_versions: list[int] | None = None
        self._version = init_version  # end of applied chain
        # True end of the APPENDED chain: duplicates are judged against
        # this, never against epoch jumps (begin_epoch raises _version
        # without appending — a parked push woken by the jump must fail
        # the gap check, not false-ack as an already-durable duplicate).
        self._last_appended = (seed[-1][0] if seed else 0)
        self._waiters: dict[int, Promise] = {}
        self._popped: dict[int, int] = {}  # tag -> trimmed-below version
        self._retired: set[int] = set(retired_tags or ())
        self._tags_seen: set[int] = {
            t for e in self._log for t in e.tagged if t not in self._retired
        }
        self.locked = False
        # Generation fence (reference: the epoch/recovery-count every
        # TLogCommitRequest carries): pushes stamped with a DIFFERENT
        # epoch are rejected outright. 0 = unfenced (static wiring /
        # direct drivers). Without this, a partitioned old generation's
        # proxy can get its push FALSE-ACKED by a new generation's tlog
        # through the duplicate-retransmit path — the fresh chain's
        # _last_appended sits an epoch-jump ahead, so any stale version
        # reads as "already durable" — and a client receives an ack for
        # a write that exists only on the doomed region's logs (deployed
        # multi-region partition find).
        self.epoch = epoch
        # Operator/system credential gating entries_snapshot (set by the
        # server wiring from the spec's authz_system_token, like
        # StorageServer.system_token): when configured, ONLY a matching
        # token may take the unlocked full-log snapshot.
        self.system_token: str | None = None
        # Highest version the pushing proxies know is durable on EVERY tlog
        # (reference: knownCommittedVersion in TLogCommitRequest). Storage
        # reads this off peek replies and applies ONLY up to it: anything
        # above may be an unacked suffix — in the worst case a partitioned
        # zombie generation's divergent timeline (deployed multi-region
        # find: pri proxies kept appending locally while fenced by the
        # locked satellites; a pri storage applied that fork). Seeded
        # entries are salvage — acked by construction — so they start
        # the bound.
        self.known_committed = self._last_appended
        # Which path moved the bound first, per advance: the proxy's
        # notification at the acknowledgement, or the bound a later push
        # carried (metrics(); the share is how often the notify engages).
        self.kc_advances_by_notify = 0
        self.kc_advances_by_push = 0

    @staticmethod
    def committed_prefix(entries, end_version: int, known_committed: int):
        """Split a peek reply at the known-committed bound: the ONE rule
        every tlog consumer (storage pull loop, backup/DR stream) must
        apply — entries above kc are an unacked suffix (worst case: a
        partitioned zombie generation's divergent fork) and must neither
        be consumed nor advance the consumer's cursor. Returns
        (consumable entries, version to advance through)."""
        return ([e for e in entries if e[0] <= known_committed],
                min(end_version, known_committed))

    @classmethod
    def from_disk(cls, loop: Loop, disk_path: str,
                  retired_tags: set[int] | None = None) -> "TLog":
        """Deployed restart: recover the disk queue's chain and resume
        as this log's content (the sim instead salvages into FRESH tlogs
        during recovery). init_version = last recovered version + 1; the
        booting sequencer's begin_epoch() then jumps the chain start
        safely above everything recovered."""
        import os

        from foundationdb_tpu.runtime.diskqueue import DiskQueue

        entries = (DiskQueue.recover(disk_path)
                   if os.path.exists(disk_path) else [])
        last = entries[-1][0] if entries else 0
        return cls(
            loop,
            init_version=last + 1 if entries else 0,
            seed=entries,
            retired_tags=retired_tags,
            disk_path=disk_path,
            disk_preserved=True,  # resume the SAME chain file: no truncate
        )

    @rpc
    async def truncate_to(self, version: int) -> int:
        """Deployed-restart suffix discipline: drop entries ABOVE
        `version` (present on this log but not fsync'd by every peer —
        the ack required ALL tlogs, so anything above the minimum
        recovered end is unacked and must not be served; serving it
        would apply a transaction on some shards and not others). The
        disk file is rewritten through the tmp+rename path."""
        # Spilled entries all PRECEDE the in-memory window; a truncation
        # reaching into the spilled region would need to also drop spilled
        # state or it resurrects an unacked suffix — enforce the
        # precondition instead of assuming it (review finding; both
        # callers truncate at boot, before any spill can have happened).
        assert version >= self._spilled_through, (
            f"truncate_to v{version} below spilled region "
            f"(through v{self._spilled_through})")
        before = len(self._log)
        kept = [e for e in self._log if e.version <= version]
        if len(kept) != before:
            dropped = sum(e.nbytes for e in self._log if e.version > version)
            self._queue_bytes -= dropped
            self._mem_bytes -= dropped
            self._log = kept
            self._last_appended = kept[-1].version if kept else 0
            self._version = min(self._version, version + 1)
            # The truncated suffix is unacked by definition; the
            # committed bound must not point into it.
            self.known_committed = min(self.known_committed, version)
            if self.disk is not None:
                # Spilled entries are all BELOW the in-memory window, so
                # truncation (which drops a suffix) keeps them whole.
                self.disk.rewrite(
                    self._spilled_entries()
                    + [(e.version, e.tagged) for e in self._log]
                )
        return before - len(self._log)

    @rpc
    async def begin_epoch(self, start_version: int) -> int:
        """Deployed-restart handshake (static wiring; the sim's recovery
        recruits fresh tlogs instead): the booting sequencer announces
        the new chain's start version so the first push's prev_version
        matches. Monotone and idempotent; stale parked pushes are woken
        to observe the jump and fail out."""
        if self.locked:
            raise TLogLocked("begin_epoch after lock")
        if start_version > self._version:
            self._version = start_version
            for p in list(self._waiters.values()):
                p.send(None)
            self._waiters.clear()
        return self._version

    @rpc
    async def push(
        self,
        prev_version: int,
        version: int,
        tagged: dict[int, list[Mutation]],
        known_committed: "int | None" = None,
        epoch: "int | None" = None,
    ) -> int:
        """Append one batch; ack (returning the durable version) after fsync.

        Idempotent under retransmit: a push whose version is already in the
        chain (its ack was lost to a partition) re-acks without re-appending.
        The duplicate re-ack is gated on the epoch fence below: only the
        SAME generation's retransmits qualify — a stale generation's push
        must fail, never false-ack (see self.epoch)."""
        if epoch is not None and self.epoch and epoch != self.epoch:
            raise TLogLocked(
                f"push from epoch {epoch} fenced by epoch {self.epoch} tlog")
        while self._version != prev_version and not self.locked:
            if version <= self._last_appended:
                return version  # duplicate of an already-durable batch
            if prev_version < self._version:
                raise ValueError(
                    f"gap in tlog chain: prev={prev_version} < applied={self._version}"
                )
            p = self._waiters.setdefault(prev_version, Promise())
            await p.future
        if self.locked:
            raise TLogLocked(f"push v{version} after lock at v{self._version}")
        sink = span_sink(self.loop)
        t_fsync = span_now(self.loop) if sink is not None else 0.0
        await self.loop.sleep(self.FSYNC_SECONDS)
        if self.locked:  # lock won the race while we were "fsyncing"
            raise TLogLocked(f"push v{version} after lock at v{self._version}")
        if self.disk is not None:
            # REAL durability before the ack: a crash after this point
            # cannot lose the batch; a crash before it never acked.
            self.disk.append((version, tagged))
            self.disk.fsync()
        entry = TLogEntry(version, tagged)
        self._log.append(entry)
        self._queue_bytes += entry.nbytes
        self._mem_bytes += entry.nbytes
        self._tags_seen.update(t for t in tagged if t not in self._retired)
        self._version = version
        self._last_appended = version
        # None = direct driver (unit tests / single-writer harnesses)
        # without an ack protocol: treat its pushes as committed. Real
        # proxies ALWAYS pass their known-committed bound — that is the
        # fence that keeps a partitioned generation's unacked appends
        # out of storage state.
        kc = version if known_committed is None else known_committed
        if kc > self.known_committed:
            self.known_committed = kc
            self.kc_advances_by_push += 1
        self._maybe_spill()
        if sink is not None:
            # Sub-stage attribution (obs subsystem), interior of the
            # proxy-measured tlog_durable: chain-ordered append ->
            # durable (fsync sleep + disk write), per push.
            sink.stage_tick("tlog_fsync", span_now(self.loop) - t_fsync)
        w = self._waiters.pop(version, None)
        if w is not None:
            w.send(None)
        return version

    @rpc
    async def advance_known_committed(
        self, version: int, epoch: "int | None" = None,
    ) -> int:
        """A proxy's word that `version` is durable on EVERY tlog of the
        generation, sent the moment its last push acknowledgement landed
        — the bound the proxy's NEXT push would carry, one commit-pipeline
        turn sooner, so the storages' next peek may apply `version` and a
        read at it need not wait for another batch (in an idle cluster,
        for IDLE_BATCH_INTERVAL). Fenced like push: refused once locked
        and from another epoch, so a displaced generation's proxy cannot
        move a successor's bound. Monotone, and never above what this log
        holds. Losing the call costs the wait, nothing else: the next
        push carries the same bound. → the bound now in force."""
        if self.locked:
            raise TLogLocked(f"known-committed v{version} after lock")
        if epoch is not None and self.epoch and epoch != self.epoch:
            raise TLogLocked(
                f"known-committed from epoch {epoch} fenced by epoch "
                f"{self.epoch} tlog")
        kc = min(version, self._last_appended)
        if kc > self.known_committed:
            self.known_committed = kc
            self.kc_advances_by_notify += 1
        return self.known_committed

    def _maybe_spill(self) -> None:
        if self.disk is None or self._mem_bytes <= self.SPILL_BYTES:
            return
        # Spill the OLDEST entries (laggard pullers' territory) down to
        # half the budget, so spilling is amortized, not per-push.
        cut = 0
        while cut < len(self._log) - 1 and self._mem_bytes > self.SPILL_BYTES // 2:
            e = self._log[cut]
            self._mem_bytes -= e.nbytes
            self._spilled_meta.append((e.version, e.nbytes))
            if self._spill_cache is not None:
                # Extend the live cache in memory: newly spilled entries
                # are newer than everything cached, so append keeps the
                # version order — no disk re-read.
                self._spill_cache.append((e.version, e.tagged))
                self._spill_cache_versions.append(e.version)
            cut += 1
        if cut:
            self._spilled_through = self._log[cut - 1].version
            self._log = self._log[cut:]

    def _spilled_entries(self):
        """(version, tagged) for the LIVE spilled region (exact
        membership from _spilled_meta — the file may also hold resident
        and already-trimmed versions). One disk read builds the cache;
        spills/trims maintain it incrementally."""
        if not self._spilled_meta:
            return []
        if self._spill_cache is None:
            live = {v for v, _n in self._spilled_meta}
            self._spill_cache = [
                (v, t) for v, t in self.disk.read_all() if v in live
            ]
            self._spill_cache_versions = [v for v, _t in self._spill_cache]
            # Fresh build = fresh TTL: a cache rebuilt by compaction or
            # salvage must not carry a stale stamp, or the next healthy
            # peek evicts it immediately and every compaction re-pays
            # the full-file read (review finding).
            self._spill_cache_used = self.loop.now
        return self._spill_cache

    @rpc
    async def peek(
        self, tag: int, begin_version: int, limit: int = 1000
    ) -> tuple[list[tuple[int, list[Mutation]]], int, int]:
        """→ (entries for `tag` with version >= begin_version, end_version,
        known_committed).

        end_version is the version the puller may advance to after applying
        the returned entries: the durable chain end, unless the scan was
        truncated by `limit` (then the last returned version). Idle tags
        advance through mutation-free versions this way — the reference's
        empty peek replies carrying the tlog version."""
        if self.loop.buggify("tlog.slow_peek"):
            # Late peeks = storage lag spikes: ratekeeper smoothing,
            # FutureVersion waits, and pop-floor logic all get exercised.
            await self.loop.sleep(self.loop.rng.uniform(0, 0.1))
        if self.loop.buggify("tlog.tiny_peek"):
            limit = 1  # single-entry pages: pull-loop pagination on trial
        out = []
        if self._spilled_meta and begin_version <= self._spilled_through:
            # Laggard puller reaching into the spilled region: serve it
            # back from disk (one file read builds the cache; bisect
            # finds the page start so tiny single-entry pages don't
            # rescan the whole region each time).
            entries = self._spilled_entries()
            self._spill_cache_used = self.loop.now
            i = bisect.bisect_left(self._spill_cache_versions, begin_version)
            for j in range(i, len(entries)):  # no entries[i:] copy per page
                v, tagged = entries[j]
                if tag in tagged:
                    out.append((v, tagged[tag]))
                    if len(out) >= limit:
                        return out, out[-1][0], self.known_committed
        elif (self._spill_cache is not None
              and self.loop.now - getattr(self, "_spill_cache_used", 0)
              > self.SPILL_CACHE_TTL):
            # The spilled region has gone COLD (no laggard touched it
            # for a TTL): release the cache so the backlog doesn't stay
            # resident. Keyed on staleness, NOT on "some other puller
            # peeked above the region" — with replicas, the healthy
            # replica's every pull would otherwise evict the cache and
            # force a full-file rebuild per laggard page (review
            # finding).
            self._spill_cache = self._spill_cache_versions = None
        for e in self._log:
            if e.version >= begin_version and tag in e.tagged:
                out.append((e.version, e.tagged[tag]))
                if len(out) >= limit:
                    return out, out[-1][0], self.known_committed
        return out, self._version, self.known_committed

    @rpc
    async def pop(self, tag: int, version: int) -> None:
        """Storage server `tag` is durable through `version`; trim entries
        every live tag has popped past. A tag that has pushed entries but
        never popped holds the floor at 0 (no trim) — correct, if unbounded,
        until recovery replaces its storage server."""
        self._popped[tag] = max(self._popped.get(tag, 0), version)
        self._trim()

    DISK_COMPACT_EVERY = 256  # trims between disk-queue rewrites

    def _trim(self) -> None:
        if not self._tags_seen:
            return  # nothing pushed yet (fresh post-recovery log): no trim
        floor = min(self._popped.get(t, 0) for t in self._tags_seen)
        before = len(self._log)
        dropped_mem = sum(e.nbytes for e in self._log if e.version <= floor)
        self._log = [e for e in self._log if e.version > floor]
        self._queue_bytes -= dropped_mem
        self._mem_bytes -= dropped_mem
        # Spilled entries below the floor retire too (bytes tracked in
        # the meta list; the file reclaims space at the next compaction).
        dropped_spill = sum(n for v, n in self._spilled_meta if v <= floor)
        if dropped_spill:
            self._spilled_meta = [
                (v, n) for v, n in self._spilled_meta if v > floor
            ]
            self._queue_bytes -= dropped_spill
            if self._spill_cache is not None:
                # The floor always removes a PREFIX of the version-sorted
                # cache: bisect + del is O(dropped), not an O(region)
                # rebuild per pop (a laggard pops per applied page —
                # full copies made catch-up O(N^2); review finding).
                i = bisect.bisect_right(self._spill_cache_versions, floor)
                del self._spill_cache[:i]
                del self._spill_cache_versions[:i]
            if not self._spilled_meta:
                self._spilled_through = 0
                self._spill_cache = self._spill_cache_versions = None
        if self.disk is not None and (before != len(self._log) or dropped_spill):
            self._disk_trims = getattr(self, "_disk_trims", 0) + 1
            if self._disk_trims % self.DISK_COMPACT_EVERY == 0:
                # Reclaim queue space: the un-popped suffix a restart
                # still needs = the spilled region (read back from the
                # file) + the in-memory log.
                self.disk.rewrite(
                    self._spilled_entries()
                    + [(e.version, e.tagged) for e in self._log]
                )

    @rpc
    async def lock(self) -> int:
        """Recovery: refuse further pushes; → end version (reference:
        TLogLockResult.end)."""
        self.locked = True
        # Wake parked pushes so they observe the lock and fail out.
        for p in self._waiters.values():
            p.send(None)
        self._waiters.clear()
        return self._version

    @rpc
    async def get_version(self) -> int:
        return self._version

    @rpc
    async def confirm_epoch(self, epoch: int) -> int:
        """GRV liveness confirmation (reference: confirmEpochLive — the
        master pings its tlog set before read versions are handed out).
        A read version is only externally consistent if the generation
        that mints it could still COMMIT at mint time — i.e. its whole
        push set is reachable, unlocked, and un-displaced. A partitioned
        region's chain fails here (its satellite is locked/fenced by the
        new generation), so its zombie proxies can serve NO read version
        — closing the stale-read window where a client reads pre-fork
        state after another client's commit landed in the new region
        (deployed multi-region partition find). Epoch 0 = unfenced
        caller/log (static wiring), matching the push fence."""
        if self.locked:
            raise TLogLocked("confirm_epoch after lock")
        if epoch and self.epoch and epoch != self.epoch:
            raise TLogLocked(
                f"epoch {epoch} displaced by epoch {self.epoch}")
        return self._version

    @rpc
    async def metrics(self) -> dict:
        """Ratekeeper inputs (reference: TLogQueuingMetricsReply — queue
        bytes is the un-popped suffix some storage server still needs)."""
        return {
            "version": self._version,
            "queue_bytes": self._queue_bytes,
            "queue_entries": len(self._log) + len(self._spilled_meta),
            "spilled_entries": len(self._spilled_meta),
            "kc_advances_by_notify": self.kc_advances_by_notify,
            "kc_advances_by_push": self.kc_advances_by_push,
        }

    @rpc
    async def retire_tag(self, tag: int) -> None:
        """Forget a tag that will never pull again (backup stopped): its
        last pop would otherwise pin the trim floor forever. Persistent —
        late pushes still carrying the tag (a batch that read the backup
        flag before the disable) cannot re-add it."""
        self._retired.add(tag)
        self._tags_seen.discard(tag)
        self._popped.pop(tag, None)
        self._trim()

    @rpc
    async def register_tag(self, tag: int) -> None:
        """Un-retire a tag (a NEW backup starting after a stopped one)."""
        self._retired.discard(tag)

    @rpc
    async def recover_entries(self) -> list[tuple[int, dict[int, list[Mutation]]]]:
        """Recovery salvage: the un-popped suffix of the log — everything
        some storage server may not have applied yet (valid once locked).
        Includes the SPILLED region (read back from disk): forgetting it
        would lose acked-but-unpulled commits across a recovery."""
        assert self.locked, "recover_entries on an unlocked tlog"
        return (self._spilled_entries()
                + [(e.version, e.tagged) for e in self._log])

    @rpc
    async def entries_snapshot(
        self, epoch: int = 0, token: str | None = None,
    ) -> list[tuple[int, dict[int, list[Mutation]]]]:
        """recover_entries WITHOUT the lock precondition, for the one
        caller that must not lock: the controller's bootstrap-resume path
        seeds satellite tlogs from the resumed chain (a locked tlog can't
        begin_epoch, and the new generation is about to serve from it).
        Only atomic while nothing pushes — true in that window: chains
        are resumed but no proxy generation is recruited yet.

        GATED (r5 review finding — the precondition used to be docstring-only):
        with a system token configured, only a matching token may read;
        otherwise the caller must either hold the lock-equivalent (tlog
        locked — recover_entries' own precondition) or present a
        generation epoch at/after ours while the tlog is quiescent (no
        parked pushes). A mistimed or displaced caller can no longer read
        a torn snapshot including the unacked fork suffix."""
        if not self._snapshot_allowed(epoch, token):
            raise TLogLocked(
                f"entries_snapshot denied: caller epoch {epoch} vs tlog "
                f"epoch {self.epoch} (locked={self.locked}, "
                f"parked={len(self._waiters)}, "
                f"token={'set' if self.system_token else 'unset'})")
        return (self._spilled_entries()
                + [(e.version, e.tagged) for e in self._log])

    def _snapshot_allowed(self, epoch: int, token: str | None) -> bool:
        if self.system_token is not None:
            return token == self.system_token
        if self.locked:
            return True  # same precondition recover_entries asserts
        return epoch >= self.epoch and not self._waiters
