"""Double-buffered host packing for the wire dispatch path.

The rank pack of every endpoint key (``TPUConflictSet._pack_resident``)
runs on the host — serial with device execution in the plain loop. This
runner puts the pack half (``pack_wire_window``) on ONE worker thread so window N+1 packs while the
device executes window N; the dispatch half (``dispatch_window``, which
threads device state) stays on the submitting thread, in order.

Threading contract (see pack_wire_window's docstring): packs are
commit-version ordered and the single worker serializes them; pack mutates
only host bookkeeping (version floors, base_version) and defers any device
rebase into the PreparedWindow, which dispatch applies — so pack(N+1) may
overlap dispatch(N)'s device execution but never another pack.

``threaded=False`` degrades to inline packing with identical results —
that is the mode deterministic tests use, and the parity the threaded mode
is tested against.

Speculative pipelined resolve (FDB_TPU_SPEC_RESOLVE=1) composes here with
no structural change: ``dispatch_window`` on a speculative engine routes
through the engine's reconcile ring (dispatch N+1 runs against the
optimistically advanced state while N's verdicts are unconfirmed; the
collector reconciles in FIFO order), so the runner's three stages become a
genuine three-deep pipeline — pack N+2 on the worker thread,
speculatively resolve N+1 on the device,
reconcile N at collect. The reconcile ring lives in the ENGINE, not the
runner, because it must also guard the serial entry points (rebase,
resident repack, object-path resolves) that never pass through a runner.
``spec_metrics()`` exposes the engine's speculation counters per runner.
"""

from __future__ import annotations

import queue
import threading
import time
from collections import deque
from typing import Callable


class PipelinedWindowRunner:
    """Pipelines pack → dispatch → collect over a conflict set."""

    def __init__(self, cs, threaded: bool = True, max_pending: int = 8):
        self._cs = cs
        self._threaded = threaded
        self._pending: deque[Callable] = deque()  # dispatched collectors
        self.pack_busy_s = 0.0  # host time inside pack (overlap numerator)
        self.windows_submitted = 0
        self.windows_collected = 0
        if threaded:
            self._req_q: queue.Queue = queue.Queue(maxsize=max_pending)
            self._ready_q: queue.Queue = queue.Queue()
            self._worker = threading.Thread(
                target=self._pack_loop, name="sched-packer", daemon=True
            )
            self._worker.start()
        else:
            self._ready: deque = deque()

    # -- worker --------------------------------------------------------------

    def _pack_loop(self) -> None:
        while True:
            req = self._req_q.get()
            if req is None:
                return
            wire, cvs, count = req
            t0 = time.perf_counter()
            try:
                prepared = self._cs.pack_wire_window(wire, cvs, count)
            except BaseException as e:  # surfaced at dispatch_ready()
                prepared = e
            self.pack_busy_s += time.perf_counter() - t0
            self._ready_q.put(prepared)

    # -- submit / dispatch / collect ------------------------------------------

    def _put_draining(self, item) -> None:
        """Blocking put on the bounded request queue that can never
        deadlock with a deferred resident repack: if the pack worker is
        parked on the mirror gate (a _RepackPlan or tiered-dictionary
        _DemotePlan awaiting dispatch), the
        queue stops draining — so while the put is full-blocked, keep
        dispatching ready windows from THIS (the dispatch) thread, which
        executes the plan, reopens the gate, and unblocks the worker."""
        while True:
            mirror = getattr(self._cs, "_mirror", None)
            if mirror is not None and not mirror.gate.is_set():
                self.dispatch_ready()
            try:
                self._req_q.put(item, timeout=0.05)
                return
            except queue.Full:
                continue

    def submit(self, wire, commit_versions, count: int) -> None:
        """Queue a window for packing (call in commit-version order)."""
        self.windows_submitted += 1
        if self._threaded:
            self._put_draining((wire, list(commit_versions), count))
        else:
            # A deferred resident-dictionary repack or tiered demotion
            # (conflict_set _RepackPlan / _DemotePlan) parks the mirror
            # gate until its window
            # DISPATCHES; packing inline on this same thread would
            # deadlock on the gate, so drain the ready windows first —
            # dispatching them is exactly what the threaded mode's main
            # loop would have done concurrently.
            mirror = getattr(self._cs, "_mirror", None)
            if mirror is not None and not mirror.gate.is_set():
                self.dispatch_ready()
            t0 = time.perf_counter()
            self._ready.append(
                self._cs.pack_wire_window(wire, list(commit_versions), count)
            )
            self.pack_busy_s += time.perf_counter() - t0

    def dispatch_ready(self, block: bool = False) -> int:
        """Move packed windows to the device (in order). Non-blocking by
        default; ``block=True`` waits for at least one pack if any window
        is still owed. Returns how many windows were dispatched."""
        n = 0
        owed = self.windows_submitted - self.windows_collected - len(self._pending)
        while owed > 0:
            prepared = self._take_ready(block=block and n == 0)
            if prepared is None:
                break
            if isinstance(prepared, BaseException):
                raise prepared
            self._pending.append(self._cs.dispatch_window(prepared))
            n += 1
            owed -= 1
        return n

    def _take_ready(self, block: bool):
        if self._threaded:
            try:
                return self._ready_q.get(block=block)
            except queue.Empty:
                return None
        return self._ready.popleft() if self._ready else None

    @property
    def in_flight(self) -> int:
        """Windows dispatched to the device but not yet collected."""
        return len(self._pending)

    def spec_metrics(self) -> dict:
        """The engine's speculation counters (all-zero for serial engines),
        for harnesses that report per-runner mis-speculation rates."""
        fn = getattr(self._cs, "spec_metrics", None)
        if fn is None:
            return {"spec_dispatched": 0, "spec_confirmed": 0,
                    "spec_repaired": 0, "spec_flipped": 0,
                    "chain_rolls": 0, "spec_depth": 0}
        return fn()

    def collect_next(self):
        """Force the oldest outstanding window's verdicts (device sync).
        Dispatches it first if its pack is still in flight."""
        # Feed the device everything already packed before blocking on the
        # oldest window — the sync time then overlaps younger windows.
        self.dispatch_ready(block=False)
        if not self._pending:
            if not self.dispatch_ready(block=True):
                raise IndexError("no window outstanding")
        self.windows_collected += 1
        return self._pending.popleft()()

    def close(self) -> None:
        if self._threaded:
            self._put_draining(None)
            self._worker.join(timeout=5.0)
