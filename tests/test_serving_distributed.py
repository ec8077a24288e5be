"""Distributed serving on the CI-simulated 8-device mesh (subprocess).

The serving contract (tests/test_serving.py) re-verified when the
server's head index is a mesh-sharded ``DistributedIndex``:

* snapshot isolation + micro-batched answers bit-match the
  *single-device* answers for every mesh-capable backend, even with
  updates in flight behind the snapshot;
* the deferred capacity check replays from the committed base when a
  **shard** overflows (sticky per-shard ``overflowed`` / routing-slab
  ``dropped`` are only read at eviction/commit barriers);
* the batcher's pow2 coalescing keeps the retrace bound across the
  distributed exchange: warm repeat rounds compile nothing.

Each test runs in a child process via
``helpers.run_on_simulated_mesh`` (the forced host device count must
precede jax init); one child per test amortizes the 8-way compiles.
"""

from __future__ import annotations

from helpers import run_on_simulated_mesh

# -- (a) snapshot isolation + batcher bit-parity vs single-device -----------

_PARITY_SCRIPT = r"""
import jax, numpy as np
from repro.core import make_index
from repro.data import points as gen
from repro.serving.batcher import MicroBatcher
from repro.serving.server import SpatialServer

N, Q, K, B = 2048, 16, 5, 8
pts = np.asarray(gen.uniform(jax.random.PRNGKey(0), N, 2))
qs = np.asarray(gen.uniform(jax.random.PRNGKey(2), Q, 2))
lo, hi = gen.query_boxes(jax.random.PRNGKey(3), B, 2, gen.DEFAULT_HI // 8)
lo, hi = np.asarray(lo), np.asarray(hi)
newp = np.asarray(gen.uniform(jax.random.PRNGKey(4), 256, 2))

for kind in ("spac-h", "spac-z", "porth"):
    solo = make_index(kind, pts, phi=8)
    solo_d2, _ = solo.knn(qs, K)
    solo_d2 = np.asarray(solo_d2)
    solo_cnt = np.asarray(solo.range_count(lo, hi))

    srv = SpatialServer.build(kind, pts, mesh=mesh, phi=8, window=3)
    snap = srv.snapshot()
    bat = MicroBatcher(snap, max_batch=1024, max_delay_s=60.0)
    knn_tk = [bat.submit_knn(qs[i], K) for i in range(Q)]
    cnt_tk = [bat.submit_range_count(lo[i], hi[i]) for i in range(B)]
    # dispatch updates *after* the snapshot: answers below must still
    # come from the pre-update version (snapshot isolation)
    srv.insert(newp)
    srv.delete(pts[:256])
    flat, valid = map(np.asarray, snap.index.extract_points())
    for i, t in enumerate(knn_tk):
        d2, ids = (np.asarray(x)[0] for x in t.result())
        np.testing.assert_array_equal(d2, solo_d2[i]), (kind, i)
        # the global ids index the snapshot's flattened points, whose
        # coordinates reproduce the distances
        assert (ids >= 0).all() and valid[ids].all(), (kind, i)
        diff = flat[ids].astype(np.float32) - qs[i].astype(np.float32)
        assert np.allclose((diff * diff).sum(-1), d2), (kind, i)
    for i, t in enumerate(cnt_tk):
        assert int(np.asarray(t.result())[0]) == int(solo_cnt[i]), (kind, i)
    srv.commit()
    assert len(srv.head_index) == N, (kind, len(srv.head_index))
    assert srv.stats["recoveries"] == 0, (kind, srv.stats)
    print(kind, "PARITY_OK")
print("SERVING_PARITY_OK")
"""


def test_distributed_serving_parity_all_mesh_backends():
    run_on_simulated_mesh(_PARITY_SCRIPT, 8, timeout_base_s=1500,
                          expect="SERVING_PARITY_OK")


# -- (b) deferred-overflow replay when a shard overflows --------------------

_REPLAY_SCRIPT = r"""
import jax, numpy as np
from repro.data import points as gen
from repro.serving.server import SpatialServer

pts = np.asarray(gen.uniform(jax.random.PRNGKey(0), 1024, 2))
# deliberately tight per-shard rows: the unchecked inserts overflow a
# shard's leaf slab, the sticky flag rides the lineage, and the next
# barrier (window eviction / commit) replays from the committed base
srv = SpatialServer.build("spac-h", pts, mesh=mesh, phi=8, window=2,
                          capacity_rows=24)
total = 1024
for r in range(4):
    batch = np.asarray(gen.uniform(jax.random.PRNGKey(10 + r), 512, 2))
    srv.insert(batch)
    total += 512
srv.commit()
assert len(srv.head_index) == total, (len(srv.head_index), total)
assert srv.stats["recoveries"] >= 1, srv.stats
assert int(srv.head_index.dropped) == 0
# post-recovery head serves exact answers
qs = np.asarray(gen.uniform(jax.random.PRNGKey(2), 4, 2))
d2, bp, ok = srv.snapshot().knn_points(qs, 5)
assert np.asarray(ok).all()
print("REPLAY_OK")
"""


def test_distributed_shard_overflow_replay():
    run_on_simulated_mesh(_REPLAY_SCRIPT, 8, timeout_base_s=1200,
                          expect="REPLAY_OK")


# -- (c) retrace bound across the distributed exchange ----------------------

_TRACE_SCRIPT = r"""
import jax, numpy as np
from repro.core import engine
from repro.data import points as gen
from repro.serving.batcher import MicroBatcher
from repro.serving.server import SpatialServer

pts = np.asarray(gen.uniform(jax.random.PRNGKey(0), 2048, 2))
srv = SpatialServer.build("spac-h", pts, mesh=mesh, phi=8, window=3)
qs = np.asarray(gen.uniform(jax.random.PRNGKey(2), 16, 2))
lo, hi = gen.query_boxes(jax.random.PRNGKey(3), 8, 2, gen.DEFAULT_HI // 8)
lo, hi = np.asarray(lo), np.asarray(hi)
bat = MicroBatcher(max_batch=1024, max_delay_s=60.0)

def round_(r):
    bat.target = srv.snapshot()
    tks = [bat.submit_knn(qs[i], 5) for i in range(16)]
    tks += [bat.submit_range_count(lo[i], hi[i]) for i in range(8)]
    batch = np.asarray(gen.uniform(jax.random.PRNGKey(100 + r), 128, 2))
    srv.insert(batch)
    srv.delete(batch)
    for t in tks:
        t.result()
    srv.commit()

round_(0)   # warm: compiles + pow2 bucket escalations happen here
engine.reset_trace_count()
for r in range(1, 4):
    round_(r)
assert engine.trace_count() == 0, engine.trace_count()
print("TRACE_BOUND_OK")
"""


def test_distributed_retrace_bound():
    run_on_simulated_mesh(_TRACE_SCRIPT, 8, timeout_base_s=1200,
                          expect="TRACE_BOUND_OK")
