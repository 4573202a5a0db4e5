"""The plain reference of a deployment with several resolvers, each owning
a range of the keys. It imports nothing of the program.

The semantics are upstream's (fdbserver/Resolver.actor.cpp under
`configure resolvers=<N>`; the program states the same in
`runtime/commit_proxy.py` `_resolve`): the commit proxy clips every
transaction's conflict ranges to each resolver's key range, every resolver
gets every transaction of the batch (with what is left of its ranges, maybe
nothing) and judges it alone against its own history, and the proxy ANDs the
verdicts. A resolver PAINTS the writes of every transaction IT accepted,
also of one that another resolver rejected: so a later reader of those keys
may be refused though the write never committed. Never a missed conflict,
sometimes a spurious one; a reference with one history would not reproduce
those.

One resolver's rule is the skiplist's (fdbserver/SkipList.cpp), in batch
order: a transaction with read ranges here whose read version is below the
oldest version kept is TOO_OLD; one of whose read ranges overlaps a write
painted at a version above its read version, or a write of a transaction
accepted earlier in the same batch, is a CONFLICT; otherwise it is
COMMITTED and its write ranges are painted at the batch's version.
"""

from __future__ import annotations

from benchmark.lib.reference import COMMITTED, CONFLICT, TOO_OLD

MAX_KEY = b"\xff\xff"


def clip(ranges, lo: bytes, hi: bytes) -> list:
    """[(begin, end)] cut to [lo, hi); what is cut to nothing is dropped."""
    out = []
    for begin, end in ranges:
        b, e = max(begin, lo), min(end, hi)
        if b < e:
            out.append((b, e))
    return out


def _overlap(a, b) -> bool:
    return a[0] < b[1] and b[0] < a[1]


class SplitResolvers:
    """`len(splits) + 1` resolvers; resolver i owns [bound i, bound i+1)
    of b"", the splits in order, MAX_KEY."""

    def __init__(self, splits: list):
        bounds = [b""] + list(splits) + [MAX_KEY]
        self.shards = list(zip(bounds, bounds[1:]))
        # per resolver: [((begin, end), version)] of the writes it painted
        self.painted: list[list] = [[] for _ in self.shards]

    def _one(self, i: int, txns, version: int, oldest_version: int) -> list:
        lo, hi = self.shards[i]
        history, accepted, out = self.painted[i], [], []
        for read_version, reads, writes in txns:
            reads = clip(reads, lo, hi)
            if reads and read_version < oldest_version:
                out.append(TOO_OLD)
            elif any(_overlap(r, w) and v > read_version
                     for r in reads for w, v in history) or any(
                    _overlap(r, w) for r in reads for w in accepted):
                out.append(CONFLICT)
            else:
                out.append(COMMITTED)
                accepted.extend(clip(writes, lo, hi))
        history.extend((w, version) for w in accepted)
        return out

    def resolve(self, txns, version: int, oldest_version: int) -> list:
        """Verdicts of one batch: `txns` is [(read version, [(begin, end)]
        read ranges, [(begin, end)] write ranges)] in batch order."""
        per = [self._one(i, txns, version, oldest_version)
               for i in range(len(self.shards))]
        return [TOO_OLD if TOO_OLD in vs else CONFLICT if CONFLICT in vs
                else COMMITTED for vs in zip(*per)]
