"""Distributed-index tests on the CI-simulated mesh (subprocess).

The forced host device count must be staged before jax initializes, so
the actual work runs in a child process via
``helpers.run_on_simulated_mesh``; one child covers the full lifecycle
to amortize compile time. The 8-device lifecycle is fast-tier mesh
smoke (it exercises the full shard_map exchange); only the
multi-host-scale sweep stays ``slow``.
"""

from __future__ import annotations

import pytest

from helpers import run_on_simulated_mesh

SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core import distributed as D
from repro.data import points as gen

key = jax.random.PRNGKey(0)
pts = gen.uniform(key, 4096, 2)
idx = D.build(pts, mesh, phi=8)
assert int(idx.dropped) == 0
assert int(D.size(idx)) == 4096

newp = gen.uniform(jax.random.PRNGKey(1), 1024, 2)
idx = D.insert(idx, newp, mesh)
assert int(idx.dropped) == 0
assert int(D.size(idx)) == 5120

idx2 = D.delete(idx, pts[:1024], mesh)
assert int(D.size(idx2)) == 4096, int(D.size(idx2))

# exact kNN vs brute force
qs = gen.uniform(jax.random.PRNGKey(2), 24, 2)
d2, ids = D.knn(idx, qs, 5, mesh)
allp = jnp.concatenate([pts, newp]).astype(jnp.float32)
for i in range(24):
    diff = allp - qs[i].astype(jnp.float32)
    bf = jnp.sort(jnp.sum(diff * diff, -1))[:5]
    assert np.allclose(np.sort(np.asarray(d2[i])), np.asarray(bf)), i

# exact range count
lo, hi = gen.query_boxes(jax.random.PRNGKey(3), 8, 2, gen.DEFAULT_HI // 8)
cnt, overlap = D.range_count(idx, lo, hi, mesh, max_rows=2048)
assert int(overlap) <= 2048, int(overlap)
for i in range(8):
    bf = int(jnp.sum(jnp.all((allp >= lo[i]) & (allp <= hi[i]), -1)))
    assert int(cnt[i]) == bf, (i, int(cnt[i]), bf)

# splitter balance: uniform data must spread over every shard (the
# quantile sample must not be polluted by pad sentinels)
sizes = np.asarray(D.shard_sizes(idx))
assert sizes.min() > 0, sizes
assert sizes.sum() == int(D.size(idx))

# skewed routing (sweepline): slab overflow is *detected*, and a larger
# slack absorbs it
sw = gen.sweepline(jax.random.PRNGKey(4), 4096, 2)
idx3 = D.build(sw, mesh, phi=8, slack=8.0)
assert int(idx3.dropped) == 0
# the skewed *stream*: one batch lands in few shards
batch = sw[:512]
idx4 = D.insert(idx3, batch, mesh, slack=8.0)
tight = D.insert(idx3, batch, mesh, slack=0.25)
assert int(idx4.dropped) == 0
assert int(tight.dropped) > 0   # under-provisioned slab is reported

print("DISTRIBUTED_OK")
"""


def test_distributed_index_lifecycle():
    run_on_simulated_mesh(SCRIPT, 8, timeout_base_s=560,
                          expect="DISTRIBUTED_OK")


_SCALE_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core import distributed as D
from repro.data import points as gen

pts = gen.uniform(jax.random.PRNGKey(0), 1 << 16, 2)
idx = D.build(pts, mesh, phi=32)
assert int(idx.dropped) == 0
assert int(D.size(idx)) == 1 << 16
idx = D.insert(idx, gen.uniform(jax.random.PRNGKey(1), 1 << 14, 2), mesh)
assert int(idx.dropped) == 0
qs = gen.uniform(jax.random.PRNGKey(2), 8, 2)
d2, ids = D.knn(idx, qs, 10, mesh)
allp = jnp.concatenate(
    [pts, gen.uniform(jax.random.PRNGKey(1), 1 << 14, 2)]
).astype(jnp.float32)
for i in range(8):
    diff = allp - qs[i].astype(jnp.float32)
    bf = jnp.sort(jnp.sum(diff * diff, -1))[:10]
    assert np.allclose(np.sort(np.asarray(d2[i])), np.asarray(bf)), i
print("SCALE_OK")
"""


@pytest.mark.slow  # 32-way shard_map at 64K points: multi-host-scale
def test_distributed_index_scale_32shards():
    run_on_simulated_mesh(_SCALE_SCRIPT, 32, timeout_base_s=1200,
                          expect="SCALE_OK")


# -- one kNN result type: global ids into extract_points() (4 devices) ------

_IDS_SCRIPT = r"""
import jax, numpy as np
from repro.core import make_index

rng = np.random.default_rng(7)
HI = 1 << 20
pts = rng.integers(0, HI, (4000, 2), dtype=np.int32)
# twelve points on one circle round the domain's centre, where the
# Hilbert quadrants (and so the shards' key ranges) meet: a query there
# ties all twelve, across shards
c = np.array([HI // 2, HI // 2], np.int32)
ring = np.unique([(a * s, b * t) for a, b in ((500, 0), (0, 500),
                                              (300, 400), (400, 300))
                  for s in (1, -1) for t in (1, -1)], axis=0)
pts = np.concatenate([pts, c + ring.astype(np.int32)])
idx = make_index("spac-h", pts, mesh=mesh, phi=8)
solo = make_index("spac-h", pts, phi=8)
flat, valid = map(np.asarray, idx.extract_points())
per_shard = flat.shape[0] // 4
ring_ids = np.sort([np.flatnonzero(valid & (flat == p).all(-1))[0]
                    for p in c + ring])
assert len(np.unique(ring_ids // per_shard)) >= 2, ring_ids // per_shard

K = 5


def exact(q):
    return ((pts.astype(np.int64) - q) ** 2).sum(-1)


# queries whose K + 1 nearest distances differ (tie-free)
cand = rng.integers(0, HI, (64, 2), dtype=np.int32)
qs = np.stack([q for q in cand
               if len(np.unique(np.sort(exact(q))[:K + 1])) == K + 1][:16])
for impl in ("auto", "frontier", "pallas-frontier"):
    d2, ids = map(np.asarray, idx.knn(qs, K, impl=impl))
    assert ids.dtype == np.int32 and ids.shape == (16, K)
    assert np.array_equal(d2, np.asarray(solo.knn(qs, K, impl=impl)[0]))
    for i, q in enumerate(qs):
        ex = exact(q)
        order = np.argsort(ex, kind="stable")
        assert valid[ids[i]].all(), (impl, i)
        assert np.array_equal(flat[ids[i]], pts[order[:K]]), (impl, i)
        # f32 sums of squares, rounded: exact only below 2**24
        assert np.allclose(d2[i], ex[order[:K]], rtol=2**-22, atol=0)
    d2p, got, ok = map(np.asarray, idx.knn_points(qs, K, impl=impl))
    assert np.array_equal(d2p, d2) and np.array_equal(ok, ids >= 0)
    assert np.array_equal(got, flat[ids])
    # the tie: ascending global ids, the lowest first at any k
    for k in (1, 5, 12):
        d2t, idt = map(np.asarray, idx.knn(c[None], k, impl=impl))
        assert (d2t == 250000).all(), (impl, k, d2t)
        assert np.array_equal(idt[0], ring_ids[:k]), (impl, k, idt)
print("GLOBAL_IDS_OK")
"""


def test_distributed_knn_ids_index_extract_points():
    run_on_simulated_mesh(_IDS_SCRIPT, 4, timeout_base_s=600,
                          expect="GLOBAL_IDS_OK")


_MESH_COUNT_SCRIPT = r"""
import numpy as np
from repro.core import make_index
from repro.core.index import DistributedIndex

pts = np.random.default_rng(1).integers(0, 1 << 20, (2048, 2),
                                        dtype=np.int32)
by_count = make_index("spac-h", pts, mesh=4, phi=8)
assert isinstance(by_count, DistributedIndex)
assert dict(by_count.mesh.shape) == {"data": 4}
assert by_count.tree.pts.shape[0] == 4
assert len(by_count) == 2048
by_mesh = make_index("spac-h", pts, mesh=mesh, phi=8)
assert by_mesh.mesh is mesh and len(by_mesh) == 2048
try:
    make_index("spac-h", pts, mesh=5, phi=8)
except RuntimeError as e:
    assert "5 devices" in str(e) and "only 4" in str(e), e
else:
    raise AssertionError("mesh=5 on 4 devices built an index")
print("MESH_COUNT_OK")
"""


def test_make_index_takes_a_device_count_as_mesh():
    run_on_simulated_mesh(_MESH_COUNT_SCRIPT, 4, timeout_base_s=600,
                          expect="MESH_COUNT_OK")


_OBS_SCRIPT = r"""
import numpy as np
from repro import obs
from repro.serving import MicroBatcher, SpatialServer

rng = np.random.default_rng(3)
pts = rng.integers(0, 1 << 20, (4096, 2), dtype=np.int32)
srv = SpatialServer.build("spac-h", pts, mesh=4, phi=8, window=3)
with obs.recording() as rec:
    snap = srv.snapshot()
    d2, ids = snap.knn(rng.integers(0, 1 << 20, (16, 2), dtype=np.int32), 5)
    assert rec.counters["dist.knn.calls"] == 1
    assert rec.counters["dist.knn.candidates"] == 16 * 4 * 5
    lo = rng.integers(0, 1 << 19, (8, 2), dtype=np.int32)
    snap.range_count(lo, lo + (1 << 16))
    launches = rec.counters["dist.range.calls"]
    assert launches == 1 + rec.counters.get("engine.escalation", 0)
    for name in ("engine.knn", "engine.range_count"):
        ev = [e for e in rec.events if e["name"] == name]
        assert ev and all(e["args"]["shards"] == 4 for e in ev), ev
    # 1,000 rows: 250 a shard, each sends 4 x (int(250 * 2 / 4) + 8)
    srv.insert(rng.integers(0, 1 << 20, (1000, 2), dtype=np.int32))
    srv.delete(pts[:1000])
    assert rec.counters["dist.update.routed"] == 2 * 4 * 4 * 133
    assert "server.shard0.live_points" not in rec.gauges
    srv.commit()
    sizes = np.asarray(srv.head_index.shard_sizes())
    assert sizes.sum() == 4096
    for i in range(4):
        assert rec.gauges[f"server.shard{i}.live_points"]["value"] == sizes[i]
    assert np.isclose(rec.gauges["server.shard.max_over_mean"]["value"],
                      sizes.max() / sizes.mean())
    assert rec.pending == 0
# no recorder: the commit attaches nothing
srv.insert(pts[:8])
srv.commit()
print("DIST_OBS_OK")
"""


def test_distributed_counters_and_shard_gauges():
    run_on_simulated_mesh(_OBS_SCRIPT, 4, timeout_base_s=600,
                          expect="DIST_OBS_OK")


def test_distributed_knn_refuses_ids_past_int32():
    """Global ids are int32: an index of more than 2**31 slots in all
    (4 shards of 2**23 + 1 rows of 64 here) is refused before any
    program runs, not answered with wrapped ids."""
    from types import SimpleNamespace

    import numpy as np

    from repro.core import distributed as D
    shape = (4, (1 << 23) + 1, 64, 2)
    idx = SimpleNamespace(axis="data", tree=SimpleNamespace(
        pts=SimpleNamespace(shape=shape)))
    with pytest.raises(ValueError, match="int32"):
        D.knn(idx, np.zeros((1, 2), np.int32), 10, mesh=None)
