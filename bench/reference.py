"""The plain reference: the index's semantics by brute force on the host.

It imports nothing of the program and takes nothing the program made
but the answers under test. The live set of any version is rebuilt from
the stream (``bench/stream.py``), never read from the index.

* A live set is a multiset of integer points: a delete removes one copy
  of each point it names; the sliding window only deletes points it
  inserted before.
* kNN is exact: the ``k`` smallest squared distances, computed in
  int64, with ties broken by nothing (only distances are compared).
* A range count is the number of live points in a box, corners
  included.

``*_lowp`` are the controls: the same brute force on coordinates
rounded to bfloat16, the precision below the float32 the program's
distances are exact in. A comparison that cannot tell them from the
program is too loose.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np


def keys(pts: np.ndarray, hi: int) -> np.ndarray:
    """One int64 key per integer point in ``[0, hi)^D``."""
    key = np.zeros(pts.shape[0], np.int64)
    for d in range(pts.shape[1]):
        key = key * hi + pts[:, d].astype(np.int64)
    return key


def multiset_diff(got: np.ndarray, want: np.ndarray, hi: int) -> int:
    """Size of the symmetric difference of two point multisets (0 when
    they hold the same points with the same counts)."""
    gk, gc = np.unique(keys(got, hi), return_counts=True)
    wk, wc = np.unique(keys(want, hi), return_counts=True)
    allk = np.union1d(gk, wk)
    g = np.zeros(allk.shape[0], np.int64)
    w = np.zeros(allk.shape[0], np.int64)
    g[np.searchsorted(allk, gk)] = gc
    w[np.searchsorted(allk, wk)] = wc
    return int(np.abs(g - w).sum())


def sq_dist(pts: np.ndarray, q: np.ndarray) -> np.ndarray:
    """Exact squared distances (int64) from ``q`` to every point."""
    d = pts.astype(np.int64) - q.astype(np.int64)
    return (d * d).sum(-1)


def knn_d2(live: np.ndarray, q: np.ndarray, k: int) -> np.ndarray:
    """The ``k`` smallest exact squared distances, ascending."""
    d2 = sq_dist(live, q)
    return np.sort(np.partition(d2, k - 1)[:k])


def range_count(live: np.ndarray, lo: np.ndarray, hi: np.ndarray) -> int:
    return int(((live >= lo) & (live <= hi)).all(-1).sum())


class LiveSet:
    """One version's live points, sorted on the first coordinate, so
    that each query reads a slab of them instead of all.

    ``knn_d2`` takes the brute force over the points in the box of
    half-side ``r`` around the query, and doubles ``r`` until the k-th
    distance found is at most ``r``: a point outside the box is farther
    than ``r`` in some coordinate, so it cannot be nearer. It returns
    exactly what :func:`knn_d2` over all points returns
    (``tests/bench/test_bench_reference.py``)."""

    def __init__(self, pts: np.ndarray, hi: int):
        order = np.argsort(pts[:, 0], kind="stable")
        self.pts, self.hi = pts[order], hi
        self.x = self.pts[:, 0]
        self.sorted_keys = np.sort(keys(pts, hi))

    def _slab(self, lo0, hi0) -> np.ndarray:
        i0 = np.searchsorted(self.x, lo0, side="left")
        i1 = np.searchsorted(self.x, hi0, side="right")
        return self.pts[i0:i1]

    def knn_d2(self, q: np.ndarray, k: int, r: int = 1024) -> np.ndarray:
        q64 = q.astype(np.int64)
        while r < 2 * self.hi:
            c = self._slab(q64[0] - r, q64[0] + r)
            c = c[(np.abs(c[:, 1:].astype(np.int64) - q64[1:]) <= r)
                  .all(-1)]
            if len(c) >= k:
                d2 = knn_d2(c, q, k)
                if d2[-1] <= r * r:
                    return d2
            r *= 2
        return knn_d2(self.pts, q, k)

    def range_count(self, lo: np.ndarray, hi: np.ndarray) -> int:
        return range_count(self._slab(lo[0], hi[0]), lo, hi)

    def contains(self, pts: np.ndarray) -> np.ndarray:
        """Per point: is it in the live set."""
        kq = keys(pts, self.hi)
        pos = np.minimum(np.searchsorted(self.sorted_keys, kq),
                         len(self.sorted_keys) - 1)
        return self.sorted_keys[pos] == kq


def _bf16(x: np.ndarray) -> np.ndarray:
    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


def knn_points_lowp(live: np.ndarray, q: np.ndarray, k: int) -> np.ndarray:
    """Control: the ``k`` points nearest to ``q`` when coordinates are
    rounded to bfloat16 and distances summed in float32."""
    d = _bf16(live) - _bf16(q)
    d2 = (d * d).sum(-1)
    return live[np.argsort(d2, kind="stable")[:k]]


def range_count_lowp(live: np.ndarray, lo: np.ndarray,
                     hi: np.ndarray) -> int:
    """Control: the range count with coordinates rounded to bfloat16."""
    p = _bf16(live)
    return int(((p >= _bf16(lo)) & (p <= _bf16(hi))).all(-1).sum())
