"""Streaming decision-tree histogram (Ben-Haim & Tom-Tov).

Parity: reference ``utils/src/main/java/.../stats/StreamingHistogram.java``
(builder with spool + closest-centroid merge, interpolated ``sum``) and
``RichStreamingHistogram.scala`` (padded bins + density estimator). Used for
bounded-memory label/score distributions in ModelInsights.

Backend: native C++ (``native/streaming_histogram.cpp``) via ctypes when a
toolchain is present, with a faithful pure-Python fallback. Both share the
exact merge semantics, so shard-built histograms combine deterministically —
this is the monoid the reference reduces over RDD partitions, reduced here
over host shards.
"""

from __future__ import annotations

import bisect
import ctypes
from typing import Iterable, Optional

import numpy as np

__all__ = ["StreamingHistogram", "padded_bins", "density"]

_LIB = None
_LIB_TRIED = False


def _lib():
    global _LIB, _LIB_TRIED
    if not _LIB_TRIED:
        _LIB_TRIED = True
        from transmogrifai_tpu import native
        lib = native.build_and_load("shist")
        if lib is not None:
            lib.shist_new.restype = ctypes.c_void_p
            lib.shist_new.argtypes = [ctypes.c_int, ctypes.c_int, ctypes.c_int]
            lib.shist_free.argtypes = [ctypes.c_void_p]
            lib.shist_update.argtypes = [ctypes.c_void_p, ctypes.c_double,
                                         ctypes.c_int64]
            lib.shist_update_bulk.argtypes = [
                ctypes.c_void_p,
                np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
                ctypes.c_int64]
            lib.shist_size.restype = ctypes.c_int
            lib.shist_size.argtypes = [ctypes.c_void_p]
            lib.shist_get.argtypes = [
                ctypes.c_void_p,
                np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")]
            lib.shist_sum.restype = ctypes.c_double
            lib.shist_sum.argtypes = [ctypes.c_void_p, ctypes.c_double]
            lib.shist_merge.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
        _LIB = lib
    return _LIB


class _PyHist:
    """Pure-Python twin of the C++ histogram (same flush/merge order)."""

    __slots__ = ("centers", "counts", "spool", "max_bins", "max_spool",
                 "round_seconds")

    def __init__(self, max_bins: int, max_spool: int, round_seconds: int):
        self.centers: list = []
        self.counts: list = []
        self.spool: dict = {}
        self.max_bins = max_bins
        self.max_spool = max_spool
        self.round_seconds = max(1, round_seconds)

    def update(self, p: float, m: int = 1) -> None:
        if self.round_seconds > 1:
            # C-style truncated modulo (sign of dividend), matching the C++
            # backend and the reference's Java %: negatives never round up
            lp = int(p)
            d = lp - (abs(lp) // self.round_seconds) * self.round_seconds * (
                1 if lp >= 0 else -1)
            if d > 0:
                p = float(lp + (self.round_seconds - d))
        self.spool[p] = self.spool.get(p, 0) + m
        if len(self.spool) > self.max_spool:
            self.flush()

    def flush(self) -> None:
        if not self.spool:
            return
        for key in sorted(self.spool):
            i = bisect.bisect_left(self.centers, key)
            if i < len(self.centers) and self.centers[i] == key:
                self.counts[i] += self.spool[key]
            else:
                self.centers.insert(i, key)
                self.counts.insert(i, self.spool[key])
            while len(self.centers) > self.max_bins:
                diffs = np.diff(self.centers)
                j = int(np.argmin(diffs))
                k1, k2 = self.counts[j], self.counts[j + 1]
                c = (self.centers[j] * k1 + self.centers[j + 1] * k2) / (k1 + k2)
                self.centers[j: j + 2] = [c]
                self.counts[j: j + 2] = [k1 + k2]
        self.spool.clear()

    def get(self):
        self.flush()
        return (np.asarray(self.centers, np.float64),
                np.asarray(self.counts, np.int64))

    def sum_below(self, b: float) -> float:
        self.flush()
        centers, counts = self.centers, self.counts
        nxt = bisect.bisect_right(centers, b)
        if nxt >= len(centers):
            return float(sum(counts))
        if nxt == 0:
            return 0.0
        pi = nxt - 1
        ki, knext = counts[pi], counts[nxt]
        weight = (b - centers[pi]) / (centers[nxt] - centers[pi])
        mb = ki + (knext - ki) * weight
        return (ki + mb) * weight / 2.0 + ki / 2.0 + float(sum(counts[:pi]))


class StreamingHistogram:
    """Bounded-bin mergeable histogram.

    >>> h = StreamingHistogram(max_bins=10)
    >>> h.update_all(values); centers, counts = h.bins()
    """

    def __init__(self, max_bins: int = 100, max_spool: int = 500,
                 round_seconds: int = 1):
        if max_bins < 2:
            raise ValueError("max_bins must be >= 2")
        self.max_bins = max_bins
        self.max_spool = max_spool
        self.round_seconds = round_seconds
        lib = _lib()
        if lib is not None:
            self._ptr = lib.shist_new(max_bins, max_spool, round_seconds)
            self._py: Optional[_PyHist] = None
        else:
            self._ptr = None
            self._py = _PyHist(max_bins, max_spool, round_seconds)

    @property
    def is_native(self) -> bool:
        return self._ptr is not None

    def __del__(self):
        if getattr(self, "_ptr", None) is not None and _LIB is not None:
            _LIB.shist_free(self._ptr)
            self._ptr = None

    def update(self, p: float, m: int = 1) -> None:
        p = float(p)
        if not np.isfinite(p):
            return  # NaN/inf keys would corrupt the ordered-bin invariant
        if self._ptr is not None:
            _LIB.shist_update(self._ptr, p, int(m))
        else:
            self._py.update(p, int(m))

    def update_all(self, values: Iterable[float]) -> "StreamingHistogram":
        arr = np.ascontiguousarray(np.asarray(values, np.float64).ravel())
        arr = arr[np.isfinite(arr)]
        if self._ptr is not None:
            _LIB.shist_update_bulk(self._ptr, arr, arr.shape[0])
        else:
            for v in arr:
                self._py.update(float(v))
        return self

    def merge(self, other: "StreamingHistogram") -> "StreamingHistogram":
        """Fold ``other``'s bins into this histogram (monoid combine)."""
        if self._ptr is not None and other._ptr is not None:
            _LIB.shist_merge(self._ptr, other._ptr)
        else:
            centers, counts = other.bins()
            for c, k in zip(centers, counts):
                self.update(float(c), int(k))
        return self

    def bins(self):
        """(centers f64[k], counts i64[k]) sorted by center, post-flush."""
        if self._ptr is not None:
            k = _LIB.shist_size(self._ptr)
            centers = np.empty(k, np.float64)
            counts = np.empty(k, np.int64)
            if k:
                _LIB.shist_get(self._ptr, centers, counts)
            return centers, counts
        return self._py.get()

    def sum_below(self, b: float) -> float:
        """Interpolated count of mass at points <= b."""
        if self._ptr is not None:
            return float(_LIB.shist_sum(self._ptr, float(b)))
        return self._py.sum_below(b)

    def quantiles(self, qs) -> np.ndarray:
        """Approximate quantiles by inverting the Ben-Haim/Tom-Tov
        interpolated CDF (mass at a bin center = half its count plus all
        earlier counts — the sum-procedure's trapezoid model). The ingest
        sketch's answer to np.percentile over the full column."""
        centers, counts = self.bins()
        qs = np.atleast_1d(np.asarray(qs, np.float64))
        if centers.size == 0:
            return np.full(qs.shape, np.nan)
        total = float(counts.sum())
        cum = np.cumsum(counts, dtype=np.float64) - counts / 2.0
        return np.interp(np.clip(qs, 0.0, 1.0) * total, cum, centers)

    def to_json(self) -> dict:
        centers, counts = self.bins()
        return {"maxBins": self.max_bins, "centers": centers.tolist(),
                "counts": counts.tolist()}


def padded_bins(centers: np.ndarray, counts: np.ndarray,
                padding: float = 0.1):
    """Zero-mass guard bins beyond min/max (RichStreamingHistogram.getBins)."""
    if centers.size == 0:
        return centers, counts.astype(np.float64)
    c = np.concatenate([[centers.min() - padding], centers,
                        [centers.max() + padding]])
    k = np.concatenate([[0.0], counts.astype(np.float64), [0.0]])
    return c, k


def density(centers: np.ndarray, counts: np.ndarray, padding: float = 0.1):
    """Piecewise-constant density estimator over padded trapezoid bins
    (RichStreamingHistogram.density)."""
    c, k = padded_bins(centers, counts, padding)
    if c.size < 2:
        return lambda x: 0.0
    seg = (k[:-1] + k[1:]) / 2.0
    total = float(seg.sum())

    def f(x: float) -> float:
        if total == 0.0:
            return 0.0
        mass = float(seg[(x >= c[:-1]) & (x < c[1:])].sum())
        return mass / total

    return f
