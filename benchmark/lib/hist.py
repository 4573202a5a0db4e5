"""Log-binned latency histogram, as the program's obs spans ship it.

Copied from foundationdb_tpu/loadgen/harness.py (`LatencyHistogram`), so that
a later change to the program cannot change how the benchmark reads a span
dump. Only what reading needs is kept, plus `minus`: the dumps the roles
serve are cumulative since boot, and a window is the difference of two.
"""

from __future__ import annotations

import numpy as np


class LatencyHistogram:
    """~4.9 % bin width (48 bins/decade) from 10 us to 600 s, in ms."""

    LO_MS = 1e-2
    HI_MS = 6e5
    BINS_PER_DECADE = 48
    _EDGES = np.logspace(
        np.log10(LO_MS), np.log10(HI_MS),
        int(np.log10(HI_MS / LO_MS) * BINS_PER_DECADE) + 1,
    )

    def __init__(self) -> None:
        # counts[i] = samples in (_EDGES[i-1], _EDGES[i]]; [0] underflow,
        # [-1] overflow.
        self.counts = np.zeros(len(self._EDGES) + 1, np.int64)
        self.max_ms = 0.0
        self.sum_ms = 0.0

    @property
    def count(self) -> int:
        return int(self.counts.sum())

    def merge(self, other: "LatencyHistogram") -> "LatencyHistogram":
        self.counts += other.counts
        self.max_ms = max(self.max_ms, other.max_ms)
        self.sum_ms += other.sum_ms
        return self

    def minus(self, earlier: "LatencyHistogram") -> "LatencyHistogram":
        """The samples recorded since `earlier` was dumped. `max_ms` stays
        the later dump's: a maximum cannot be subtracted."""
        out = LatencyHistogram()
        out.counts = self.counts - earlier.counts
        if (out.counts < 0).any():
            raise ValueError("the earlier dump is not a prefix of this one")
        out.sum_ms = self.sum_ms - earlier.sum_ms
        out.max_ms = self.max_ms
        return out

    def percentile(self, q: float) -> float:
        """Upper edge of the bin holding the q-th percentile sample."""
        total = self.count
        if total == 0:
            raise ValueError("percentile of an empty histogram")
        target = int(np.ceil(total * q / 100.0))
        i = int(np.searchsorted(np.cumsum(self.counts), target))
        if i >= len(self._EDGES):
            return float(self.max_ms)
        return float(self._EDGES[i])

    def mean(self) -> float:
        if not self.count:
            raise ValueError("mean of an empty histogram")
        return self.sum_ms / self.count

    @classmethod
    def from_dict(cls, d: dict) -> "LatencyHistogram":
        h = cls()
        for i, n in d.get("bins", []):
            h.counts[int(i)] = int(n)
        h.max_ms = float(d.get("max_ms", 0.0))
        h.sum_ms = float(d.get("sum_ms", 0.0))
        return h


def merge_stage_dumps(dumps: list) -> dict:
    """{stage: LatencyHistogram} summed over several SpanSink.dump()s."""
    out: dict[str, LatencyHistogram] = {}
    for d in dumps:
        for name, hd in ((d or {}).get("stages") or {}).items():
            out.setdefault(name, LatencyHistogram()).merge(
                LatencyHistogram.from_dict(hd))
    return out


def stages_between(before: list, after: list) -> dict:
    """Per-stage histograms of the samples between two rounds of dumps."""
    b, a = merge_stage_dumps(before), merge_stage_dumps(after)
    return {name: h.minus(b[name]) if name in b else h
            for name, h in a.items()}


def percentile_of(samples, q: float) -> float:
    """The q-th percentile sample itself (nearest rank), not a bin edge."""
    s = np.sort(np.asarray(samples, np.float64))
    if not s.size:
        raise ValueError("percentile of no samples")
    return float(s[max(0, int(np.ceil(s.size * q / 100.0)) - 1)])
