"""The plain references that decide `correct`. They import nothing of the
program and take nothing the program made: only the operations the generator
sent, the acknowledgements it got, and what was read back.

- `CounterReplay`: a dictionary replay of the acknowledged read-modify-writes
  of a served cell. A record must hold exactly as many increments as were
  acknowledged (plus at most the commits whose result is unknown), on every
  replica. A lost increment is a missed conflict.
- `point_verdicts`: MVCC conflict detection for transactions that read and
  write single keys, in version order: a transaction conflicts when its key
  was written at a version above its read version. These are the semantics
  of upstream's skiplist (fdbserver/SkipList.cpp) for point ranges.
"""

from __future__ import annotations

from benchmark.lib import ycsb

COMMITTED, CONFLICT, TOO_OLD = 0, 1, 2


class CounterReplay:
    def __init__(self, records: "ycsb.Records"):
        self.records = records
        self.acked: dict[int, int] = {}
        self.unknown: dict[int, int] = {}

    def ack(self, i: int) -> None:
        self.acked[i] = self.acked.get(i, 0) + 1

    def unknown_result(self, i: int) -> None:
        self.unknown[i] = self.unknown.get(i, 0) + 1

    def touched(self) -> list[int]:
        return sorted(set(self.acked) | set(self.unknown))

    def wrong(self, i: int, value: "bytes | None") -> "str | None":
        """None when `value` is what record `i` must hold, else why not."""
        if value is None:
            return "missing"
        if len(value) != ycsb.RECORD_BYTES:
            return f"{len(value)} bytes, not {ycsb.RECORD_BYTES}"
        got = int.from_bytes(value[: ycsb.COUNTER_BYTES], "big")
        lo = self.acked.get(i, 0)
        hi = lo + self.unknown.get(i, 0)
        if not lo <= got <= hi:
            return (f"holds {got} increments, {lo} were acknowledged"
                    + (f" and {hi - lo} more have an unknown result"
                       if hi > lo else ""))
        if value != self.records.value(i, got):
            return "bytes beside the counter differ from the record's own"
        return None

    def count_wrong(self, indices, values) -> tuple[int, "str | None"]:
        """(how many of `indices` hold a wrong value, the first reason)."""
        n, first = 0, None
        for i, v in zip(indices, values):
            why = self.wrong(i, v)
            if why is not None:
                n += 1
                first = first or f"record {i} ({self.records.keys[i]!r}): {why}"
        return n, first


def point_verdicts(last_write: dict, keys, read_versions, version: int,
                   oldest_version: int) -> list[int]:
    """Verdicts of one batch, in order, each transaction reading and writing
    its one key; `last_write` (key -> version of its last committed write)
    is updated with the batch's committed writes."""
    out = []
    for key, rv in zip(keys, read_versions):
        if rv < oldest_version:
            out.append(TOO_OLD)
        elif last_write.get(key, -1) > rv:
            out.append(CONFLICT)
        else:
            out.append(COMMITTED)
            last_write[key] = version
    return out
