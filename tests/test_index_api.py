"""Facade parity + auto-capacity tests for the unified Index API.

Every registered backend must produce bit-identical trees and query
answers through ``make_index`` as through the raw module calls with the
same parameters, and the facade must absorb capacity overflows without
the caller ever seeing ``overflowed``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BACKENDS, baselines, capacity_for, engine,
                        get_backend, make_index, porth, queries, spac)

PHI = 8
N, M = 1200, 400
ROOT_LO = jnp.zeros(2, jnp.int32)
ROOT_HI = jnp.full(2, 1 << 20, jnp.int32)


def gen_points(seed, n, lo=0, hi=1 << 20):
    rng = np.random.default_rng(seed)
    return rng.integers(lo, hi, size=(n, 2)).astype(np.int32)


PTS = jnp.asarray(gen_points(0, N))
BATCH = jnp.asarray(gen_points(1, M))
QS = jnp.asarray(gen_points(2, 32))


def direct_build(kind, pts, cap):
    if kind == "porth":
        return porth.build(pts, ROOT_LO, ROOT_HI, phi=PHI, lam=3, rounds=5,
                           capacity_rows=cap)
    if kind in ("spac-h", "spac-z", "spac-m", "cpam-h", "cpam-z"):
        return spac.build(pts, phi=PHI, curve=get_backend(kind).curve,
                          bits=16, coord_bits=20, capacity_rows=cap)
    if kind == "kd":
        return baselines.kd_build(pts, phi=PHI, max_depth=24,
                                  capacity_rows=cap)
    if kind == "zd":
        return baselines.zd_build(pts, phi=PHI, bits=15, coord_bits=20,
                                  lam=3, capacity_rows=cap)
    raise AssertionError(kind)


def direct_insert(kind, tree, batch, cap):
    if kind == "porth":
        return porth.insert(tree, batch,
                            max_overflow_rows=min(64, tree.pts.shape[0]))
    if kind in ("spac-h", "spac-z", "spac-m", "cpam-h", "cpam-z"):
        return spac.insert(tree, batch,
                           max_overflow_rows=min(64, tree.pts.shape[0]),
                           sort_rows=kind.startswith("cpam"))
    if kind == "kd":
        return baselines.kd_insert(tree, batch, max_depth=24,
                                   capacity_rows=cap)
    return baselines.zd_insert(tree, batch, bits=15, coord_bits=20, lam=3,
                               capacity_rows=cap)


def direct_delete(kind, tree, batch, cap):
    if kind == "porth":
        return porth.delete(tree, batch)
    if kind in ("spac-h", "spac-z", "spac-m", "cpam-h", "cpam-z"):
        return spac.delete(tree, batch)
    if kind == "kd":
        return baselines.kd_delete(tree, batch, max_depth=24,
                                   capacity_rows=cap)
    return baselines.zd_delete(tree, batch, bits=15, coord_bits=20, lam=3,
                               capacity_rows=cap)


def assert_trees_bitmatch(a, b, kind, stage):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb), (kind, stage)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y),
            err_msg=f"{kind}: {stage} diverged from the direct module call")


@pytest.mark.parametrize("kind", sorted(BACKENDS))
def test_facade_parity(kind):
    """make_index build/insert/delete/knn/range bit-match direct calls."""
    cap = capacity_for(N + M, PHI, get_backend(kind).cap_slack)
    idx = make_index(kind, PTS, phi=PHI, capacity_rows=cap,
                     **(dict(root_lo=ROOT_LO, root_hi=ROOT_HI)
                        if kind == "porth" else {}))
    ref = direct_build(kind, PTS, cap)
    assert_trees_bitmatch(idx.tree, ref, kind, "build")

    idx2 = idx.insert(BATCH)
    ref2 = direct_insert(kind, ref, BATCH, idx2.capacity_rows)
    assert_trees_bitmatch(idx2.tree, ref2, kind, "insert")

    idx3 = idx2.delete(PTS[:200])
    ref3 = direct_delete(kind, ref2, PTS[:200], idx3.capacity_rows)
    assert_trees_bitmatch(idx3.tree, ref3, kind, "delete")

    # facade kNN = canonically-ordered direct engine call (the facade
    # sorts each query's hits by (d2, id) so impls are comparable)
    d2_f, ids_f = idx3.knn(QS, 5, impl="frontier")
    d2_r, ids_r = engine.canonical_knn(*queries.knn(ref3.view(), QS, 5))
    np.testing.assert_array_equal(np.asarray(d2_f), np.asarray(d2_r))
    np.testing.assert_array_equal(np.asarray(ids_f), np.asarray(ids_r))

    lo = QS
    hi = QS + jnp.int32(1 << 17)
    cnt_f = idx3.range_count(lo, hi)
    cnt_r, tr_r = queries.range_count(ref3.view(), lo, hi, max_rows=1024)
    assert not bool(jnp.any(tr_r))
    np.testing.assert_array_equal(np.asarray(cnt_f), np.asarray(cnt_r))
    ids_lf, c_lf = idx3.range_list(lo, hi)
    ids_lr, c_lr, tr_l = queries.range_list(ref3.view(), lo, hi,
                                            max_rows=1024, cap=256)
    assert not bool(jnp.any(tr_l))
    np.testing.assert_array_equal(np.asarray(c_lf), np.asarray(c_lr))
    # same hits in the same (ascending flat-id) order; facade width is
    # the engine's auto-sized bucket, padded with -1 past the count
    for qi in range(QS.shape[0]):
        c = int(c_lr[qi])
        np.testing.assert_array_equal(np.asarray(ids_lf[qi, :c]),
                                      np.asarray(ids_lr[qi, :c]))
        assert (np.asarray(ids_lf[qi, c:]) == -1).all()


@pytest.mark.parametrize("kind", ["porth", "spac-h", "spac-z"])
def test_facade_autogrow(kind):
    """Inserting far past capacity recovers transparently — the caller
    never sees ``overflowed`` and every point survives."""
    idx = make_index(kind, PTS[:64], phi=PHI, capacity_rows=32)
    assert not bool(idx.tree.overflowed)
    idx = idx.insert(PTS[64:])          # ~18x the original capacity
    assert not bool(idx.tree.overflowed)
    assert len(idx) == N
    assert idx.capacity_rows > 32
    # exactness survives the grow/compact ladder
    d2, _ = idx.knn(QS[:8], 5)
    live, ok = idx.extract_points()
    live = np.asarray(live)[np.asarray(ok)]
    for i in range(8):
        bf = np.sort(((live.astype(np.float64)
                       - np.asarray(QS[i], np.float64)) ** 2).sum(-1))[:5]
        np.testing.assert_allclose(np.asarray(d2[i], np.float64), bf,
                                   rtol=1e-6)


def test_facade_autogrow_rebuild_backends():
    """Rebuild-style backends (kd/zd) also absorb growth: capacity is
    re-derived per update so nothing is silently dropped."""
    for kind in ("kd", "zd"):
        idx = make_index(kind, PTS[:64], phi=PHI)
        idx = idx.insert(PTS[64:])
        assert len(idx) == N, kind


def test_rebuild_insert_clustered_no_silent_drop():
    """Clustered data needs far more rows than the slack heuristic; the
    rebuild insert path must size-check and retry, not drop silently
    (regression: zd lost 2902/4950 points before the check)."""
    rng = np.random.default_rng(0)
    centers = rng.integers(0, 1 << 20, size=(150, 2)).astype(np.int32)
    offs = (np.arange(33) * (1 << 5)).astype(np.int32)
    pts = (centers[:, None, :]
           + np.stack([offs, offs], -1)[None]).reshape(-1, 2)
    pts = np.clip(pts, 0, (1 << 20) - 1).astype(np.int32)
    for kind in ("zd", "kd"):
        idx = make_index(kind, pts[:64], phi=PHI)
        idx = idx.insert(pts[64:])
        assert len(idx) == len(pts), (kind, len(idx))


def test_build_overflow_retries():
    """A build at absurdly small explicit capacity succeeds anyway."""
    idx = make_index("spac-h", PTS, phi=PHI, capacity_rows=2)
    assert len(idx) == N
    idx = make_index("porth", PTS, phi=PHI, capacity_rows=2)
    assert len(idx) == N


def test_masked_updates():
    mask = jnp.arange(M) < (M // 2)
    idx = make_index("spac-h", PTS, phi=PHI)
    idx = idx.insert(BATCH, mask)
    assert len(idx) == N + M // 2
    idx = idx.delete(BATCH, mask)
    assert len(idx) == N


def test_registry_errors():
    with pytest.raises(KeyError, match="unknown index kind"):
        make_index("rtree", PTS)
    with pytest.raises(TypeError, match="unknown params"):
        make_index("spac-h", PTS, curve="hilbert", lam=3)  # lam is porth's
    with pytest.raises(ValueError, match="spac-family"):
        from repro.core.index import DistributedIndex
        DistributedIndex.build("kd", PTS, mesh=None)


def test_update_closures_cached():
    """Same (backend, shape, dtype, params) reuses one jitted closure."""
    from repro.core.index import _update_closure
    _update_closure.cache_clear()
    idx = make_index("spac-h", PTS, phi=PHI)
    idx = idx.insert(BATCH).insert(gen_points(7, M)).delete(BATCH)
    info = _update_closure.cache_info()
    assert info.misses == 2          # one insert + one delete closure
    assert info.hits >= 1            # second same-shape insert reused it

    # knn on the facade is the module-level jitted engine: cached too
    d2a, _ = idx.knn(QS, 5)
    d2b, _ = idx.knn(QS, 5)
    np.testing.assert_array_equal(np.asarray(d2a), np.asarray(d2b))


def test_size_and_views():
    idx = make_index("porth", PTS, phi=PHI)
    assert int(idx.size) == len(idx) == N
    view = idx.view()
    assert view.pts.shape[0] == idx.capacity_rows
    pts, ok = idx.extract_points()
    assert int(ok.sum()) == N


def _run_distributed(script: str):
    """Run a distributed scenario on the simulated 8-device mesh (one
    scenario per process keeps each under the compile-time budget of a
    small CPU box)."""
    from helpers import run_on_simulated_mesh
    run_on_simulated_mesh(_DIST_PRELUDE + script, 8,
                          timeout_base_s=1200, expect="RECOVERY_OK")


_DIST_PRELUDE = r"""
import jax
from repro.core import make_index
from repro.data import points as gen
"""


@pytest.mark.slow
def test_distributed_row_overflow_recovery():
    """Shard-row overflow re-shards at doubled capacity: no point lost,
    callers never see ``overflowed``."""
    _run_distributed(r"""
pts = gen.uniform(jax.random.PRNGKey(0), 2048, 2)
idx = make_index("spac-h", pts, mesh=mesh, phi=8, capacity_rows=40)
idx = idx.insert(gen.uniform(jax.random.PRNGKey(1), 4096, 2))
assert len(idx) == 6144, len(idx)
assert int(idx.dropped) == 0
print("RECOVERY_OK")
""")


@pytest.mark.slow
def test_distributed_slab_overflow_recovery():
    """A skewed delete under a deliberately tight routing slab escalates
    slack instead of silently skipping the overflowed deletions."""
    _run_distributed(r"""
sw = gen.sweepline(jax.random.PRNGKey(4), 2048, 2)
sidx = make_index("spac-h", sw, mesh=mesh, phi=8)
sidx.slack = 0.25
sidx = sidx.delete(sw[:512])
assert len(sidx) == 1536, len(sidx)
assert int(sidx.dropped) == 0
print("RECOVERY_OK")
""")
