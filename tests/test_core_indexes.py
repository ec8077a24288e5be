"""Exactness of P-Orth and SPaC trees against brute-force oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import porth, queries, spac


# ---------------------------------------------------------------------------
# oracles
# ---------------------------------------------------------------------------

def brute_knn(points, q, k):
    d2 = ((points.astype(np.float64) - q.astype(np.float64)) ** 2).sum(-1)
    idx = np.argsort(d2, kind="stable")[:k]
    return np.sort(d2[idx])


def brute_range_count(points, lo, hi):
    return int(np.all((points >= lo) & (points <= hi), axis=-1).sum())


def gen_points(rng, n, dim, dist="uniform", lo=0, hi=1 << 20):
    if dist == "uniform":
        return rng.integers(lo, hi, size=(n, dim)).astype(np.int32)
    if dist == "varden":  # clustered random walk with restarts
        pts = np.zeros((n, dim), np.int64)
        cur = rng.integers(lo, hi, size=dim)
        for i in range(n):
            if rng.random() < 0.01:
                cur = rng.integers(lo, hi, size=dim)
            cur = np.clip(cur + rng.integers(-50, 51, size=dim), lo, hi - 1)
            pts[i] = cur
        return pts.astype(np.int32)
    if dist == "sweepline":
        p = rng.integers(lo, hi, size=(n, dim))
        return p[np.argsort(p[:, 0])].astype(np.int32)
    raise ValueError(dist)


def check_queries(view, pts_np, rng, k=8, n_q=40, seed_pts=True):
    """Compare engine results against brute force on random queries."""
    dim = pts_np.shape[1]
    qs = gen_points(rng, n_q, dim).astype(np.int32)
    if seed_pts and len(pts_np):  # half the queries ON data points (InD)
        qs[: n_q // 2] = pts_np[rng.integers(0, len(pts_np), n_q // 2)]
    kk = min(k, max(len(pts_np), 1))
    d2, ids = queries.knn(view, jnp.asarray(qs), kk, chunk=4)
    for i in range(n_q):
        want = brute_knn(pts_np, qs[i], kk)
        got = np.asarray(d2[i][: len(want)], np.float64)
        np.testing.assert_allclose(got, want, rtol=1e-6,
                                   err_msg=f"kNN mismatch q={qs[i]}")
    # range queries
    lo = qs
    hi = qs + rng.integers(1, 1 << 18, size=qs.shape).astype(np.int32)
    cnt, trunc = queries.range_count(view, jnp.asarray(lo), jnp.asarray(hi),
                                     max_rows=512)
    assert not np.any(np.asarray(trunc)), "increase max_rows in test"
    for i in range(n_q):
        assert int(cnt[i]) == brute_range_count(pts_np, lo[i], hi[i]), \
            f"range mismatch box={lo[i]},{hi[i]}"


def live_points(view):
    ok = np.asarray(view.valid & view.active[:, None]).reshape(-1)
    pts = np.asarray(view.pts).reshape(-1, view.pts.shape[-1])
    return pts[ok]


ROOT_LO = jnp.zeros(2, jnp.int32)
ROOT_HI = jnp.full(2, 1 << 20, jnp.int32)


def make_index(kind, pts, phi=8):
    if kind == "porth":
        return porth.build(jnp.asarray(pts), ROOT_LO[: pts.shape[1]],
                           jnp.full(pts.shape[1], 1 << 20, jnp.int32),
                           phi=phi, lam=3 if pts.shape[1] == 2 else 2,
                           rounds=5)
    curve = {"spac_h": "hilbert", "spac_z": "morton"}[kind]
    return spac.build(jnp.asarray(pts), phi=phi, curve=curve,
                      coord_bits=20)


def ins_with_headroom(kind, t, extra):
    """Production pattern: grow capacity before a batch insert if needed."""
    mod = porth if kind == "porth" else spac
    need = int(t.num_rows) + len(extra) + 8
    if t.capacity_rows < need:
        t = mod.grow(t, need)
    return mod.insert(t, jnp.asarray(extra),
                      max_overflow_rows=min(128, t.capacity_rows))


INDEX_KINDS = ["porth", "spac_h", "spac_z"]
DISTS = ["uniform", "varden", "sweepline"]


@pytest.mark.parametrize("kind", INDEX_KINDS)
@pytest.mark.parametrize("dist", DISTS)
def test_build_and_query(kind, dist):
    rng = np.random.default_rng(42)
    pts = gen_points(rng, 2000, 2, dist)
    t = make_index(kind, pts)
    assert not bool(t.overflowed)
    assert int(t.size) == len(pts)
    # multiset of stored points survives
    np.testing.assert_array_equal(
        np.sort(live_points(t.view()), axis=0), np.sort(pts, axis=0))
    check_queries(t.view(), pts, rng)


@pytest.mark.parametrize("kind", INDEX_KINDS)
@pytest.mark.parametrize("dist", ["uniform", "varden"])
def test_batch_insert(kind, dist):
    rng = np.random.default_rng(7)
    pts = gen_points(rng, 1500, 2, dist)
    extra = gen_points(rng, 600, 2, dist)
    t = make_index(kind, pts)
    t = ins_with_headroom(kind, t, extra)
    assert not bool(t.overflowed)
    allp = np.concatenate([pts, extra])
    assert int(t.size) == len(allp)
    np.testing.assert_array_equal(
        np.sort(live_points(t.view()), axis=0), np.sort(allp, axis=0))
    check_queries(t.view(), allp, rng)


@pytest.mark.parametrize("kind", INDEX_KINDS)
def test_batch_delete(kind):
    rng = np.random.default_rng(3)
    pts = gen_points(rng, 1500, 2, "uniform")
    t = make_index(kind, pts)
    sel = rng.permutation(len(pts))[:500]
    dels = pts[sel]
    if kind == "porth":
        t = porth.delete(t, jnp.asarray(dels))
    else:
        t = spac.delete(t, jnp.asarray(dels))
    keep = np.delete(pts, sel, axis=0)
    assert int(t.size) == len(keep)
    np.testing.assert_array_equal(
        np.sort(live_points(t.view()), axis=0), np.sort(keep, axis=0))
    check_queries(t.view(), keep, rng)


@pytest.mark.parametrize("kind", INDEX_KINDS)
def test_duplicates_multiset_semantics(kind):
    rng = np.random.default_rng(5)
    base = gen_points(rng, 50, 2, "uniform")
    pts = np.repeat(base, 4, axis=0)  # every point 4 times
    t = make_index(kind, pts)
    assert int(t.size) == 200
    # delete two copies of each of the first 10 points
    dels = np.repeat(base[:10], 2, axis=0)
    t = (porth.delete if kind == "porth" else spac.delete)(
        t, jnp.asarray(dels))
    assert int(t.size) == 180
    live = live_points(t.view())
    for b in base[:10]:
        assert (live == b).all(axis=1).sum() == 2
    check_queries(t.view(), live, rng)


def test_spac_delete_finds_point_after_split_below_duplicate_run():
    """A run of rows sharing one min_code, with a larger code in its last
    row: a split below the run moves the run's first row to a higher
    free row id. The directory must still put the larger code's row last
    in the run, or delete routes past it and misses the point."""
    pts = np.array([[0, 1]] + [[1, 1]] * 95, np.int32)   # codes 3, 2 x95
    t = spac.build(jnp.asarray(pts), phi=8, bits=12, coord_bits=12,
                   capacity_rows=256)
    # code 0 routes below every row: row 0 splits into fresh, higher rows
    t = spac.insert(t, jnp.zeros((32, 2), jnp.int32))
    t = spac.delete(t, jnp.asarray(pts[:32]))
    live = live_points(t.view())
    assert int(t.size) == live.shape[0] == 96
    assert not (live == [0, 1]).all(axis=1).any()


@pytest.mark.parametrize("kind", INDEX_KINDS)
def test_incremental_equals_bulk(kind):
    """insert(build(P), Q) answers every query identically to build(P u Q)."""
    rng = np.random.default_rng(11)
    pts = gen_points(rng, 1200, 2, "uniform")
    t = make_index(kind, pts[:600])
    for s in range(600, 1200, 200):
        t = ins_with_headroom(kind, t, pts[s:s + 200])
    assert not bool(t.overflowed)
    assert int(t.size) == 1200
    check_queries(t.view(), pts, rng)


def test_insert_into_empty_tree():
    rng = np.random.default_rng(13)
    pts = gen_points(rng, 300, 2, "uniform")
    for kind in INDEX_KINDS:
        t = make_index(kind, pts)
        dele = porth.delete if kind == "porth" else spac.delete
        t = dele(t, jnp.asarray(pts))  # empty it
        assert int(t.size) == 0
        t = ins_with_headroom(kind, t, pts[:100])
        assert int(t.size) == 100, kind
        check_queries(t.view(), pts[:100], rng)


def test_porth_3d():
    rng = np.random.default_rng(17)
    pts = gen_points(rng, 1000, 3, "uniform")
    t = porth.build(jnp.asarray(pts), jnp.zeros(3, jnp.int32),
                    jnp.full(3, 1 << 20, jnp.int32), phi=8, lam=2, rounds=5)
    assert int(t.size) == 1000
    check_queries(t.view(), pts, rng)


def test_spac_3d():
    rng = np.random.default_rng(19)
    pts = gen_points(rng, 1000, 3, "varden")
    t = spac.build(jnp.asarray(pts), phi=8, curve="hilbert", bits=10,
                   coord_bits=20)
    assert int(t.size) == 1000
    check_queries(t.view(), pts, rng)


def test_porth_float_coords():
    """The paper's applicability claim: P-Orth works on float coordinates."""
    rng = np.random.default_rng(23)
    pts = rng.random((800, 2)).astype(np.float32)
    t = porth.build(jnp.asarray(pts), jnp.zeros(2, jnp.float32),
                    jnp.ones(2, jnp.float32), phi=8)
    assert int(t.size) == 800
    qs = rng.random((20, 2)).astype(np.float32)
    d2, ids = queries.knn(t.view(), jnp.asarray(qs), 5, chunk=4)
    for i in range(20):
        want = brute_knn(pts, qs[i], 5)
        np.testing.assert_allclose(np.asarray(d2[i], np.float64), want,
                                   rtol=1e-4)


def test_spac_unsorted_flag_lifecycle():
    """Partial-order relaxation: appends mark rows unsorted; splits restore."""
    rng = np.random.default_rng(29)
    pts = gen_points(rng, 400, 2, "uniform")
    t = spac.build(jnp.asarray(pts), phi=8, coord_bits=20)
    assert not bool(jnp.any(t.unsorted))
    t2 = spac.insert(t, jnp.asarray(gen_points(rng, 5, 2, "uniform")))
    assert bool(jnp.any(t2.unsorted & t2.active))
