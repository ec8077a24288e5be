"""Query-engine parity + exactness tests (the Query API contract).

Every registered backend must answer kNN and range queries *exactly*
through the facade — no ``max_rows``/``cap`` knobs, no ``truncated``
flag — and the engine's execution routes (chunked frontier traversal vs
Pallas brute-force flat scan) must agree bit-for-bit with each other
and with a numpy oracle.

The parity data uses integer coordinates < 2^10 so every intermediate
of the distance sum (q-p)^2 — the one expression every route evaluates
— is an integer below 2^24, exactly representable in float32, and the
seed is chosen so no query has a tie at the k boundary. Under those
conditions "identical ids/d2" is well-defined and asserted with
assert_array_equal.

Because the kernels compute (q-p)^2 directly (no |q|^2 - 2qp + |p|^2
expansion), exactness needs only the *differences* in the f32-exact
window, not the absolute coordinates — asserted by the adversarial
large-magnitude test below, where the expanded identity is off by
orders of magnitude.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from helpers import run_on_simulated_mesh
from repro.core import BACKENDS, engine, make_index, queries

PHI = 8
N, Q, K = 700, 16, 5
COORD_HI = 1 << 10          # exact-arithmetic window (see module doc)
IMPLS = ("frontier", "flat", "pallas-interpret", "pallas-frontier",
         "pallas-frontier-interpret", "ref")


def oracle_knn_d2(pts: np.ndarray, qs: np.ndarray, k: int) -> np.ndarray:
    d2 = ((pts[None].astype(np.int64)
           - qs[:, None].astype(np.int64)) ** 2).sum(-1)
    return np.sort(d2, axis=1)[:, :k]


def oracle_range_count(pts: np.ndarray, lo: np.ndarray,
                       hi: np.ndarray) -> np.ndarray:
    inside = ((pts[None] >= lo[:, None]) & (pts[None] <= hi[:, None]))
    return inside.all(-1).sum(-1).astype(np.int64)


def _tie_free_data(n: int, q: int, k: int):
    """Points/queries with no distance tie at any query's k boundary
    (makes top-k id sets unique, so impl outputs must be identical)."""
    for seed in range(64):
        rng = np.random.default_rng(seed)
        pts = rng.integers(0, COORD_HI, size=(n, 2)).astype(np.int32)
        qs = rng.integers(0, COORD_HI, size=(q, 2)).astype(np.int32)
        d2 = np.sort(((pts[None].astype(np.int64)
                       - qs[:, None].astype(np.int64)) ** 2).sum(-1), 1)
        if (d2[:, k - 1] != d2[:, k]).all():
            return pts, qs
    raise AssertionError("no tie-free seed found")


PTS, QS = _tie_free_data(N, Q, K)


@pytest.fixture(scope="module")
def indexes():
    """One facade index per registered backend over the shared data."""
    return {kind: make_index(kind, jnp.asarray(PTS), phi=PHI)
            for kind in sorted(BACKENDS)}


# ---------------------------------------------------------------------------
# kNN parity: engine impls x backends vs the numpy oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", sorted(BACKENDS))
def test_knn_impl_parity(indexes, kind):
    """Every impl route — chunked frontier, flat scan (jnp and Pallas
    interpret), fused frontier (ref and Pallas interpret) — returns
    identical ids/d2 and matches the numpy oracle bit-for-bit."""
    idx = indexes[kind]
    want_d2 = oracle_knn_d2(PTS, np.asarray(QS), K)
    results = {impl: idx.knn(QS, K, impl=impl) for impl in IMPLS}
    for impl, (d2, ids) in results.items():
        np.testing.assert_array_equal(
            np.asarray(d2, np.int64), want_d2,
            err_msg=f"{kind}/{impl}: d2 diverged from the oracle")
        # ids resolve to points at exactly the claimed distances
        nbrs = np.asarray(queries.gather_points(idx.view(), ids),
                          np.int64)
        got = ((nbrs - np.asarray(QS, np.int64)[:, None]) ** 2).sum(-1)
        np.testing.assert_array_equal(got, want_d2, err_msg=f"{kind}/"
                                      f"{impl}: ids decode wrong")
    base_d2, base_ids = results["frontier"]
    for impl in IMPLS[1:]:
        d2, ids = results[impl]
        np.testing.assert_array_equal(np.asarray(d2), np.asarray(base_d2))
        np.testing.assert_array_equal(
            np.asarray(ids), np.asarray(base_ids),
            err_msg=f"{kind}: {impl} ids != frontier ids")


def test_knn_auto_routes_and_matches(indexes):
    """impl="auto" (flat scan at this size) equals the forced paths."""
    idx = indexes["spac-h"]
    rows, cols, _ = idx.view().pts.shape
    assert rows * cols <= idx.engine.flat_budget  # flat route chosen
    d2_a, ids_a = idx.knn(QS, K)
    d2_f, ids_f = idx.knn(QS, K, impl="frontier")
    np.testing.assert_array_equal(np.asarray(d2_a), np.asarray(d2_f))
    np.testing.assert_array_equal(np.asarray(ids_a), np.asarray(ids_f))


def test_knn_fewer_points_than_k(indexes):
    """Both routes pad identically when the index holds < k points."""
    idx = make_index("spac-h", jnp.asarray(PTS[:3]), phi=PHI)
    for impl in IMPLS:
        d2, ids = idx.knn(QS, 8, impl=impl)
        assert (np.asarray(ids)[:, 3:] == -1).all(), impl
        assert (np.asarray(ids)[:, :3] >= 0).all(), impl


def test_knn_engine_rejects_legacy_interpret_alias():
    """One canonical interpret spelling across layers: the engine and
    the kernel boundary both reject the legacy alias with the same
    pointer to the canonical name."""
    idx = make_index("spac-h", jnp.asarray(PTS), phi=PHI)
    with pytest.raises(ValueError, match="pallas-interpret"):
        idx.knn(QS, K, impl="interpret")
    with pytest.raises(ValueError, match="unknown kNN impl"):
        idx.knn(QS, K, impl="bruteforce")


# ---------------------------------------------------------------------------
# direct distances: exact outside the absolute f32 window
# ---------------------------------------------------------------------------

_ADV_OFFSET = 1 << 23       # every coordinate far outside |q|^2 exactness
_ADV_SPREAD = 1 << 9        # tile-local spread well inside the window


def _adversarial_data(n: int, q: int, k: int):
    """Tie-free points/queries at offset 2^23 with spread < 2^9: every
    coordinate is an exactly-representable f32 integer, (q-p) stays
    exact (< 2^10), but |q|^2 ~ 7e13 has ulp 2^23 — the expanded
    identity cannot even represent its own intermediates."""
    for seed in range(64):
        rng = np.random.default_rng(seed + 100)
        pts = (_ADV_OFFSET + rng.integers(0, _ADV_SPREAD, size=(n, 2))
               ).astype(np.int32)
        qs = (_ADV_OFFSET + rng.integers(0, _ADV_SPREAD, size=(q, 2))
              ).astype(np.int32)
        d2 = np.sort(((pts[None].astype(np.int64)
                       - qs[:, None].astype(np.int64)) ** 2).sum(-1), 1)
        if (d2[:, k - 1] != d2[:, k]).all():
            return pts, qs
    raise AssertionError("no tie-free adversarial seed found")


def test_plain_mxu_identity_rounds_at_large_magnitude():
    """Precondition for the parity test below: on the adversarial data
    the expanded |q|^2 - 2qp + |p|^2 form diverges from the exact
    (q-p)^2 distances — catastrophically, not in the last ulp."""
    pts, qs = _adversarial_data(300, 8, K)
    exact = ((pts[None].astype(np.int64)
              - qs[:, None].astype(np.int64)) ** 2).sum(-1)
    qf = jnp.asarray(qs, jnp.float32)
    pf = jnp.asarray(pts, jnp.float32)
    plain = ((qf * qf).sum(-1)[:, None]
             - 2.0 * qf @ pf.T + (pf * pf).sum(-1)[None, :])
    err = np.abs(np.asarray(plain, np.float64) - exact)
    assert err.max() > _ADV_SPREAD ** 2, err.max()


@pytest.mark.parametrize("kind", sorted(BACKENDS))
def test_knn_compensated_parity_outside_f32_window(kind):
    """impl="pallas-frontier" (and its interpret spelling) is bit-exact
    against impl="frontier" and the int64 oracle on coordinates far
    outside the absolute f32-exact window: the direct (q-p)^2 only needs
    the differences in the window."""
    pts, qs = _adversarial_data(300, 8, K)
    idx = make_index(kind, jnp.asarray(pts), phi=PHI)
    want_d2 = oracle_knn_d2(pts, qs, K)
    base_d2, base_ids = idx.knn(jnp.asarray(qs), K, impl="frontier")
    np.testing.assert_array_equal(np.asarray(base_d2, np.int64), want_d2,
                                  err_msg=f"{kind}: frontier not exact")
    for impl in ("pallas-frontier", "pallas-frontier-interpret"):
        d2, ids = idx.knn(jnp.asarray(qs), K, impl=impl)
        np.testing.assert_array_equal(
            np.asarray(d2), np.asarray(base_d2),
            err_msg=f"{kind}/{impl}: d2 != frontier d2")
        np.testing.assert_array_equal(
            np.asarray(ids), np.asarray(base_ids),
            err_msg=f"{kind}/{impl}: ids != frontier ids")


# ---------------------------------------------------------------------------
# _range_rows: top_k candidate selection == old argsort (regression)
# ---------------------------------------------------------------------------

def test_range_rows_topk_matches_argsort_reference():
    """`_range_rows` now selects candidate rows with `lax.top_k` on a
    negated key; it must reproduce the old full-argsort spelling bit
    for bit (same rows, same order, same flags) at every bucket size,
    including buckets past R."""
    rng = np.random.default_rng(5)
    pts = rng.integers(0, 1 << 20, size=(3000, 2)).astype(np.int32)
    idx = make_index("spac-h", jnp.asarray(pts), phi=PHI)
    view = idx.view()
    R = view.pts.shape[0]
    for t in range(10):
        lo = jnp.asarray(rng.integers(0, 1 << 19, 2), jnp.int32)
        hi = lo + jnp.asarray(rng.integers(1, 1 << 19, 2), jnp.int32)
        overlap = np.asarray(
            queries._boxes_overlap(view.bbox_lo, view.bbox_hi,
                                   lo[None, :], hi[None, :])
            & view.active)
        for max_rows in (4, 128, R, 2 * R):
            rows, rows_ok, trunc = queries._range_rows(
                view, lo, hi, max_rows)
            key = np.where(overlap, np.arange(R), R)
            want = np.argsort(key, kind="stable")[:max_rows]
            np.testing.assert_array_equal(np.asarray(rows), want)
            np.testing.assert_array_equal(np.asarray(rows_ok),
                                          overlap[want])
            assert bool(trunc) == (int(overlap.sum()) > max_rows)


# ---------------------------------------------------------------------------
# range exactness: auto-sized buffers, no knobs, no truncated flag
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", sorted(BACKENDS))
def test_range_count_oracle(indexes, kind):
    rng = np.random.default_rng(7)
    lo = rng.integers(0, COORD_HI // 2, size=(Q, 2)).astype(np.int32)
    hi = lo + rng.integers(1, COORD_HI // 2, size=(Q, 2)).astype(np.int32)
    cnt = indexes[kind].range_count(jnp.asarray(lo), jnp.asarray(hi))
    np.testing.assert_array_equal(np.asarray(cnt, np.int64),
                                  oracle_range_count(PTS, lo, hi))


def test_range_exceeding_old_default_is_exact():
    """Regression for the silent-inexactness bug: a query overlapping
    far more rows than the old ``max_rows=128`` default returns the
    exact count/list through the facade (pre-engine, fig5_range.py and
    launch/serve.py dropped ``truncated`` and served short answers)."""
    rng = np.random.default_rng(1)
    n = 4000
    pts = rng.integers(0, 1 << 20, size=(n, 2)).astype(np.int32)
    idx = make_index("spac-h", jnp.asarray(pts), phi=PHI)
    lo = jnp.zeros((2, 2), jnp.int32)
    hi = jnp.full((2, 2), (1 << 20) - 1, jnp.int32)
    # precondition: the old fixed-capacity engine *does* truncate here
    _, trunc = queries.range_count(idx.view(), lo, hi, max_rows=128)
    assert bool(jnp.all(trunc)), "scenario no longer exceeds 128 rows"
    cnt = idx.range_count(lo, hi)
    assert (np.asarray(cnt) == n).all(), np.asarray(cnt)
    ids, cnt_l = idx.range_list(lo, hi)
    assert (np.asarray(cnt_l) == n).all()
    assert int((np.asarray(ids) >= 0).sum()) == 2 * n


@pytest.mark.parametrize("kind", sorted(BACKENDS))
def test_range_list_matches_count(indexes, kind):
    rng = np.random.default_rng(11)
    lo = rng.integers(0, COORD_HI // 2, size=(8, 2)).astype(np.int32)
    hi = lo + np.int32(COORD_HI // 3)
    idx = indexes[kind]
    ids, cnt = idx.range_list(jnp.asarray(lo), jnp.asarray(hi))
    np.testing.assert_array_equal(np.asarray(cnt, np.int64),
                                  oracle_range_count(PTS, lo, hi))
    ids_np = np.asarray(ids)
    np.testing.assert_array_equal((ids_np >= 0).sum(-1), np.asarray(cnt))
    # every reported id decodes to a point inside its box
    nbrs = np.asarray(queries.gather_points(idx.view(), ids))
    for qi in range(lo.shape[0]):
        sel = ids_np[qi] >= 0
        inside = ((nbrs[qi, sel] >= lo[qi]) &
                  (nbrs[qi, sel] <= hi[qi])).all(-1)
        assert inside.all(), (kind, qi)


def test_range_list_non_pow2_slot_width():
    """With a non-power-of-two row width (phi=5 -> C=10) the escalated
    cap clamps to the gathered-slot count, so the returned ids width
    always equals the engine's recorded bucket and no hit is lost."""
    rng = np.random.default_rng(3)
    n = 1500
    pts = rng.integers(0, 1 << 20, size=(n, 2)).astype(np.int32)
    idx = make_index("spac-h", jnp.asarray(pts), phi=5)
    lo = jnp.zeros((2, 2), jnp.int32)
    hi = jnp.full((2, 2), (1 << 20) - 1, jnp.int32)
    ids, cnt = idx.range_list(lo, hi)
    assert (np.asarray(cnt) == n).all()
    assert int((np.asarray(ids) >= 0).sum()) == 2 * n
    _, cap = idx.engine._buckets[("range_list", 2, 2, "int32")]
    assert ids.shape[1] == cap


# ---------------------------------------------------------------------------
# retrace bound: escalation is O(log R) and remembered
# ---------------------------------------------------------------------------

def test_range_escalation_trace_bound():
    """From a deliberately tiny starting bucket, the engine reaches the
    exact answer in <= log2(R) + 1 traces, and an identical follow-up
    query re-traces zero times (bucket remembered + jit cache)."""
    rng = np.random.default_rng(2)
    n = 2000
    pts = rng.integers(0, 1 << 20, size=(n, 2)).astype(np.int32)
    idx = make_index("spac-h", jnp.asarray(pts), phi=PHI)
    idx.engine.start_rows = 8
    rows = idx.capacity_rows
    lo = jnp.zeros((4, 2), jnp.int32)
    hi = jnp.full((4, 2), (1 << 20) - 1, jnp.int32)

    engine._range_count_closure.cache_clear()
    engine.reset_trace_count()
    cnt = idx.range_count(lo, hi)
    assert (np.asarray(cnt) == n).all()
    traces = engine.trace_count()
    bound = int(np.ceil(np.log2(rows))) + 1
    assert 2 <= traces <= bound, (traces, bound)

    # steady state: converged bucket is remembered, nothing re-traces
    cnt2 = idx.range_count(lo, hi)
    assert engine.trace_count() == traces
    np.testing.assert_array_equal(np.asarray(cnt2), np.asarray(cnt))

    # the update stream keeps the engine: queries after an insert reuse
    # the converged bucket (same closure, jax retraces only for the new
    # tree shape if capacity grew)
    idx2 = idx.insert(jnp.asarray(
        rng.integers(0, 1 << 20, size=(64, 2)).astype(np.int32)))
    cnt3 = idx2.range_count(lo, hi)
    assert (np.asarray(cnt3) == n + 64).all()


def test_knn_closures_cached_per_shape():
    """Fixed-shape kNN streams compile once per (Q, k, impl) plan."""
    idx = make_index("spac-h", jnp.asarray(PTS), phi=PHI)
    engine._knn_closure.cache_clear()
    engine.reset_trace_count()
    for _ in range(3):
        idx.knn(QS, K, impl="frontier")
    assert engine.trace_count() == 1
    idx.knn(QS, K, impl="ref")       # different plan, one more trace
    assert engine.trace_count() == 2


# ---------------------------------------------------------------------------
# property tests (hypothesis, where available)
# ---------------------------------------------------------------------------

def test_prop_range_count_exact():
    """Hypothesis sweep (skipped where hypothesis is unavailable):
    facade range counts equal the numpy oracle for arbitrary data."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(20, 200))
    def check(seed, n):
        rng = np.random.default_rng(seed)
        pts = rng.integers(0, 512, size=(n, 2)).astype(np.int32)
        idx = make_index("spac-h", jnp.asarray(pts), phi=PHI)
        lo = rng.integers(0, 400, size=(4, 2)).astype(np.int32)
        hi = lo + rng.integers(0, 300, size=(4, 2)).astype(np.int32)
        cnt = idx.range_count(jnp.asarray(lo), jnp.asarray(hi))
        np.testing.assert_array_equal(np.asarray(cnt, np.int64),
                                      oracle_range_count(pts, lo, hi))

    check()


def test_prop_knn_d2_exact():
    """Hypothesis sweep: engine kNN distances equal the oracle for all
    impls on arbitrary (exact-arithmetic-window) data."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(10, 150),
           st.sampled_from(["frontier", "pallas-interpret",
                            "pallas-frontier", "ref"]))
    def check(seed, n, impl):
        rng = np.random.default_rng(seed)
        pts = rng.integers(0, 512, size=(n, 2)).astype(np.int32)
        qs = rng.integers(0, 512, size=(4, 2)).astype(np.int32)
        k = min(4, n)
        idx = make_index("spac-z", jnp.asarray(pts), phi=PHI)
        d2, _ = idx.knn(jnp.asarray(qs), k, impl=impl)
        np.testing.assert_array_equal(np.asarray(d2, np.int64),
                                      oracle_knn_d2(pts, qs, k))

    check()


# ---------------------------------------------------------------------------
# distributed: same engine, shard-merge step (8 forced host devices)
# ---------------------------------------------------------------------------

_DIST_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core import make_index
from repro.data import points as gen

pts = gen.uniform(jax.random.PRNGKey(0), 4096, 2)
idx = make_index("spac-h", pts, mesh=mesh, phi=8)
qs = gen.uniform(jax.random.PRNGKey(2), 16, 2)

# kNN through the engine: auto (flat scan at this shard size), the
# forced frontier route and the fused frontier kernel agree with host
# brute force
allp = np.asarray(pts, np.float64)
for impl in ("auto", "frontier", "pallas-frontier"):
    d2, bp, ok = idx.knn_points(qs, 5, impl=impl)
    for i in range(16):
        bf = np.sort(((allp - np.asarray(qs[i], np.float64)) ** 2
                      ).sum(-1))[:5]
        got = np.sort(np.asarray(d2[i], np.float64))
        assert np.allclose(got, bf), (impl, i, got, bf)

# range count through the engine from a tiny starting bucket: the
# escalation loop wraps the whole shard_map exchange and converges to
# the exact global count
idx.engine.start_rows = 8
lo = jnp.zeros((2, 2), jnp.int32)
hi = jnp.full((2, 2), (1 << 20) - 1, jnp.int32)
cnt = idx.range_count(lo, hi)
assert (np.asarray(cnt) == 4096).all(), np.asarray(cnt)
print("DIST_ENGINE_OK")
"""


def test_distributed_engine_queries():
    # fast-tier mesh smoke: the 8-device simulated mesh exercises the
    # full distributed query path (see tests/helpers.py)
    run_on_simulated_mesh(_DIST_SCRIPT, 8, timeout_base_s=900,
                          expect="DIST_ENGINE_OK")
