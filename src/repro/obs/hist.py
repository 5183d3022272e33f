"""Log-spaced mergeable histograms.

:class:`LogHistogram` keeps fixed log-spaced buckets whose counts MERGE
by addition (associative and commutative, enforced by the property
tests), so per-(objective, grid mode, bucket) histograms roll up into
one service-wide distribution, and histograms from many service
instances roll up into one fleet-wide distribution, without ever
shipping raw samples.  Percentiles are geometric interpolation within a
bucket: relative error is bounded by the bucket width
(``10^(1/per_decade)``, ~26% at the default 10/decade), which is the
usual dashboard trade for O(1) memory and mergeability.
:func:`percentiles` gives exact percentiles of a raw sample list.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import numpy as np


def percentiles(samples, qs=(50.0, 99.0)) -> Tuple[float, ...]:
    """Percentiles of a sample list; zeros when there are no samples yet
    (a fresh service must report finite stats, never NaN)."""
    if not len(samples):
        return tuple(0.0 for _ in qs)
    arr = np.asarray(samples, np.float64)
    return tuple(float(np.percentile(arr, q)) for q in qs)


class LogHistogram:
    """Fixed log-spaced histogram over ``(0, +inf)`` seconds.

    Buckets span ``[lo, hi]`` with ``per_decade`` geometric buckets per
    decade; samples below ``lo`` land in an underflow bucket (reported
    as ``<= lo``), samples above ``hi`` in an overflow bucket (reported
    via the tracked exact max).  ``merge`` adds counts/sum/count and
    takes the max — integer counts make the merge exactly associative,
    the property the fleet roll-up relies on.
    """

    __slots__ = ("lo", "hi", "per_decade", "edges", "counts",
                 "count", "sum", "max")

    def __init__(self, lo: float = 1e-6, hi: float = 1e3,
                 per_decade: int = 10):
        if not (0.0 < lo < hi):
            raise ValueError(f"need 0 < lo < hi, got lo={lo} hi={hi}")
        if per_decade < 1:
            raise ValueError(f"per_decade must be >= 1, got {per_decade}")
        self.lo = float(lo)
        self.hi = float(hi)
        self.per_decade = int(per_decade)
        n = int(math.ceil((math.log10(hi) - math.log10(lo)) * per_decade))
        # edges[0] == lo; edges[-1] >= hi (the last decade may be partial)
        self.edges = [lo * 10.0 ** (i / per_decade) for i in range(n + 1)]
        # counts[0] = underflow (<= lo); counts[1+i] covers
        # (edges[i], edges[i+1]]; counts[-1] = overflow (> edges[-1])
        self.counts = [0] * (n + 2)
        self.count = 0
        self.sum = 0.0
        self.max = 0.0

    def _bucket(self, x: float) -> int:
        if x <= self.lo:
            return 0
        if x > self.edges[-1]:
            return len(self.counts) - 1
        # ceil of the log-position: x in (edges[i], edges[i+1]] -> 1 + i
        pos = (math.log10(x) - math.log10(self.lo)) * self.per_decade
        idx = int(math.ceil(pos - 1e-12))
        return min(max(idx, 1), len(self.counts) - 2)

    def record(self, x: float) -> None:
        x = float(x)
        if not math.isfinite(x) or x < 0.0:
            raise ValueError(f"histogram samples must be finite >= 0: {x}")
        self.counts[self._bucket(x)] += 1
        self.count += 1
        self.sum += x
        if x > self.max:
            self.max = x

    def compatible(self, other: "LogHistogram") -> bool:
        return (self.lo == other.lo and self.hi == other.hi
                and self.per_decade == other.per_decade)

    def merge(self, other: "LogHistogram") -> "LogHistogram":
        """In-place add of ``other``'s counts (returns self).  Raises on
        mismatched bucket layouts — silently merging different layouts
        would corrupt both distributions."""
        if not self.compatible(other):
            raise ValueError(
                f"cannot merge histograms with different layouts: "
                f"(lo={self.lo}, hi={self.hi}, /dec={self.per_decade}) vs "
                f"(lo={other.lo}, hi={other.hi}, /dec={other.per_decade})")
        for i, c in enumerate(other.counts):
            self.counts[i] += c
        self.count += other.count
        self.sum += other.sum
        self.max = max(self.max, other.max)
        return self

    def copy(self) -> "LogHistogram":
        out = LogHistogram(self.lo, self.hi, self.per_decade)
        out.counts = list(self.counts)
        out.count = self.count
        out.sum = self.sum
        out.max = self.max
        return out

    @classmethod
    def merged(cls, hists: Sequence["LogHistogram"]) -> "LogHistogram":
        """Out-of-place merge of any number of histograms (empty default
        layout when ``hists`` is empty)."""
        hists = list(hists)
        if not hists:
            return cls()
        out = hists[0].copy()
        for h in hists[1:]:
            out.merge(h)
        return out

    def percentile(self, q: float) -> float:
        """The q-th percentile by geometric interpolation within the
        containing bucket; 0.0 when empty.  Clamped to the tracked exact
        max so high quantiles never exceed an observed sample."""
        if not 0.0 <= q <= 100.0:
            raise ValueError(f"percentile q must be in [0, 100], got {q}")
        if self.count == 0:
            return 0.0
        rank = max(1, int(math.ceil(q / 100.0 * self.count)))
        cum = 0
        for i, c in enumerate(self.counts):
            if c == 0:
                continue
            if cum + c >= rank:
                frac = (rank - cum) / c
                if i == 0:                       # underflow: <= lo
                    return min(self.lo, self.max)
                if i == len(self.counts) - 1:    # overflow: > last edge
                    return self.max
                lo, hi = self.edges[i - 1], self.edges[i]
                return min(lo * (hi / lo) ** frac, self.max)
            cum += c
        return self.max  # unreachable when counts sum to count

    def cumulative(self) -> List[Tuple[float, int]]:
        """Prometheus-style cumulative buckets ``[(le, n_le), ...,
        (inf, count)]``: ``n_le`` counts samples ``<= le``."""
        out: List[Tuple[float, int]] = []
        cum = self.counts[0]
        out.append((self.edges[0], cum))
        for i in range(1, len(self.counts) - 1):
            cum += self.counts[i]
            out.append((self.edges[i], cum))
        out.append((math.inf, self.count))
        return out

    def to_dict(self) -> Dict[str, object]:
        """JSON-serialisable snapshot (round-trips via
        :meth:`from_dict`); counts are sparse ``{bucket_index: n}``."""
        return {
            "lo": self.lo, "hi": self.hi, "per_decade": self.per_decade,
            "counts": {str(i): c for i, c in enumerate(self.counts) if c},
            "count": self.count, "sum": self.sum, "max": self.max,
        }

    @classmethod
    def from_dict(cls, d: Dict[str, object]) -> "LogHistogram":
        out = cls(float(d["lo"]), float(d["hi"]), int(d["per_decade"]))
        for i, c in dict(d["counts"]).items():
            out.counts[int(i)] = int(c)
        out.count = int(d["count"])
        out.sum = float(d["sum"])
        out.max = float(d["max"])
        return out
