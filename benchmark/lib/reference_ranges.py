"""The plain reference for transactions with LISTS of conflict ranges.

`point_verdicts` (benchmark/lib/reference.py) judges transactions that read
and write one key. This is the same rule without the one-key assumption:
MVCC conflict detection over `[begin, end)` byte ranges, the semantics of
upstream's skiplist (fdbserver/SkipList.cpp, ConflictBatch), which has no
limit on how many ranges a transaction brings and never widens one.

In version order, one batch at a time, each transaction in batch order:

- ranges with `begin >= end` are empty and take no part;
- a transaction with a read range whose read version is below
  `oldest_version` is TOO_OLD (one that only writes never is);
- it is a CONFLICT when a read range overlaps a write committed at a version
  above its read version, or a write of an EARLIER ACCEPTED transaction of
  the same batch;
- otherwise it is COMMITTED and its writes count from then on, at the
  batch's version.

Plain on purpose: a dictionary for writes of one key (`[k, k + b"\\x00")`,
with a sorted list of those keys for range reads) and a list for true
ranges. It imports nothing of the program and takes only what the generator
sent: `(read_version, reads, writes)` with `reads` and `writes` lists of
`(begin, end)` byte pairs.
"""

from __future__ import annotations

import bisect

COMMITTED, CONFLICT, TOO_OLD = 0, 1, 2


def _is_point(begin: bytes, end: bytes) -> bool:
    return end == begin + b"\x00"


class RangeHistory:
    """Committed writes: key -> version of its last one-key write, and
    (begin, end, version) for every true range. Nothing is ever dropped:
    a write at or below a judged transaction's read version cannot
    conflict with it, so expiry changes no verdict."""

    def __init__(self) -> None:
        self.points: dict[bytes, int] = {}
        self.point_keys: list[bytes] = []  # sorted, for range reads
        self.ranges: list[tuple[bytes, bytes, int]] = []

    def add(self, begin: bytes, end: bytes, version: int) -> None:
        if _is_point(begin, end):
            if begin not in self.points:
                bisect.insort(self.point_keys, begin)
            self.points[begin] = version
        else:
            self.ranges.append((begin, end, version))

    def newest(self, begin: bytes, end: bytes) -> int:
        """The newest version written anywhere in [begin, end); -1 if
        nothing was."""
        out = -1
        if _is_point(begin, end):
            out = self.points.get(begin, -1)
        else:
            lo = bisect.bisect_left(self.point_keys, begin)
            hi = bisect.bisect_left(self.point_keys, end)
            for key in self.point_keys[lo:hi]:
                out = max(out, self.points[key])
        for b, e, v in self.ranges:
            if b < end and begin < e:
                out = max(out, v)
        return out


def range_verdicts(history: RangeHistory, txns, version: int,
                   oldest_version: int) -> list[int]:
    """Verdicts of one batch, in order; `history` is updated with the
    batch's committed writes."""
    out = []
    batch = RangeHistory()  # accepted writes of this batch so far
    accepted: list[tuple[bytes, bytes]] = []
    for read_version, reads, writes in txns:
        reads = [(b, e) for b, e in reads if b < e]
        if reads and read_version < oldest_version:
            out.append(TOO_OLD)
        elif any(history.newest(b, e) > read_version
                 or batch.newest(b, e) >= 0 for b, e in reads):
            out.append(CONFLICT)
        else:
            out.append(COMMITTED)
            for b, e in writes:
                if b < e:
                    batch.add(b, e, version)
                    accepted.append((b, e))
    for b, e in accepted:
        history.add(b, e, version)
    return out
