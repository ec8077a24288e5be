"""The reference's slab search returns what the brute force returns."""

import numpy as np
import pytest

from bench_rehearse import ROOT  # noqa: F401  (puts the checkout on the path)

from bench import reference as ref


@pytest.mark.parametrize("dim, hi, n", [(2, 1 << 20, 20000), (2, 64, 3000),
                                        (3, 1 << 10, 5000)])
def test_live_set_answers_as_the_brute_force(dim, hi, n):
    rng = np.random.default_rng(dim * 7 + n)
    pts = rng.integers(0, hi, (n, dim), dtype=np.int32)
    pts[: n // 10] = pts[n // 10: 2 * (n // 10)]   # duplicates
    live = ref.LiveSet(pts, hi)
    qs = rng.integers(0, hi, (40, dim), dtype=np.int32)
    for q in qs:
        assert np.array_equal(live.knn_d2(q, 10), ref.knn_d2(pts, q, 10))
        lo = np.minimum(q, hi - hi // 8)
        box = (lo, lo + hi // 8 - 1)
        assert live.range_count(*box) == ref.range_count(pts, *box)
    assert live.contains(pts[:50]).all()


def test_multiset_diff_counts_each_copy():
    a = np.array([[1, 2], [1, 2], [3, 4]], np.int32)
    assert ref.multiset_diff(a, a, 8) == 0
    assert ref.multiset_diff(a[:2], a, 8) == 1
    assert ref.multiset_diff(a[1:], a, 8) == 1
    assert ref.multiset_diff(a[:1], a[2:], 8) == 2


def test_control_answers_differ_from_the_reference_at_real_density():
    rng = np.random.default_rng(5)
    pts = rng.integers(0, 1 << 20, (200000, 2), dtype=np.int32)
    qs = rng.integers(0, 1 << 20, (20, 2), dtype=np.int32)
    wrong = sum(not np.array_equal(
        np.sort(ref.sq_dist(ref.knn_points_lowp(pts, q, 10), q)),
        ref.knn_d2(pts, q, 10)) for q in qs)
    assert wrong > 0
