"""The plain reference for lists of conflict ranges, fast on a long stream
whose range reads each lie inside one known key prefix.

The SAME rule as benchmark/lib/reference_ranges.py (its docstring states
it: upstream SkipList.cpp's semantics, no range widened, verdict codes
alike), and tests hold the two to each other. What differs is the cost of
a write: that file keeps every written key in ONE sorted list
(`bisect.insort`, a memmove of the whole list a key), which is minutes at
this stream's 1.5 million keys a run. Here a one-key write goes into a
dictionary, and besides into a sorted list of its own PREFIX if it has one
(`prefix_len`: first byte -> the length of the prefix that keys beginning
with that byte are range-read by; a district's new-order keys, an order's
lines): lists of tens of keys. A true range is judged against its prefix's
list where it lies inside one prefix, and against every written key where
it does not, slowly and just as exactly.

It imports nothing of the program and takes only what the generator sent:
`(read_version, reads, writes)` with lists of `(begin, end)` byte pairs.
"""

from __future__ import annotations

import bisect

COMMITTED, CONFLICT, TOO_OLD = 0, 1, 2


def _successor(prefix: bytes) -> bytes:
    """The first key that does not begin with `prefix`; b"" (no bound)
    where every byte is 0xff."""
    stripped = prefix.rstrip(b"\xff")
    return stripped[:-1] + bytes([stripped[-1] + 1]) if stripped else b""


class PrefixHistory:
    """Committed writes: key -> version of its last one-key write, the
    written keys of each prefix in order, and (begin, end, version) for
    every true range written. Nothing is ever dropped: a write at or below
    a judged transaction's read version cannot conflict with it."""

    def __init__(self, prefix_len: dict) -> None:
        self.prefix_len = prefix_len
        self.points: dict[bytes, int] = {}
        self.by_prefix: dict[bytes, list[bytes]] = {}
        self.ranges: list[tuple[bytes, bytes, int]] = []

    def _prefix(self, key: bytes) -> "bytes | None":
        n = self.prefix_len.get(key[0]) if key else None
        return key[:n] if n is not None and len(key) >= n else None

    def add(self, begin: bytes, end: bytes, version: int) -> None:
        if end != begin + b"\x00":
            self.ranges.append((begin, end, version))
            return
        if begin not in self.points:
            prefix = self._prefix(begin)
            if prefix is not None:
                bisect.insort(self.by_prefix.setdefault(prefix, []), begin)
        self.points[begin] = version

    def _keys_in(self, begin: bytes, end: bytes) -> list:
        """The written keys in [begin, end)."""
        prefix = self._prefix(begin)
        beyond = _successor(prefix) if prefix is not None else None
        if prefix is not None and (end <= beyond or not beyond):
            keys = self.by_prefix.get(prefix, ())
            return keys[bisect.bisect_left(keys, begin):
                        bisect.bisect_left(keys, end)]
        return [k for k in self.points if begin <= k < end]

    def newest(self, begin: bytes, end: bytes) -> int:
        """The newest version written anywhere in [begin, end); -1 if
        nothing was."""
        if end == begin + b"\x00":
            out = self.points.get(begin, -1)
        else:
            out = max((self.points[k] for k in self._keys_in(begin, end)),
                      default=-1)
        for b, e, v in self.ranges:
            if b < end and begin < e:
                out = max(out, v)
        return out


def prefix_verdicts(history: PrefixHistory, txns, version: int,
                    oldest_version: int) -> list[int]:
    """Verdicts of one batch, in order; `history` is updated with the
    batch's committed writes."""
    out = []
    batch = PrefixHistory(history.prefix_len)  # accepted writes so far
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


def reads_as_points(txns) -> list:
    """`txns` with every read range cut to its begin's point. Judged in a
    history of their own they give what an engine that knew no interval
    would answer; the verdicts that differ from the stream's own are those
    that rest on a true range."""
    return [(read_version, [(b, b + b"\x00") for b, _e in reads], writes)
            for read_version, reads, writes in txns]
