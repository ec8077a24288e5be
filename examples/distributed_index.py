"""The paper's index sharded over a device mesh, behind the facade.

`make_index(kind, pts, mesh=mesh)` returns a `DistributedIndex` with
the same surface as the local facade: SFC-range partitioning with
sampled splitters, one all_to_all per batch update, fan-out/merge kNN.
Runs here on 8 forced host devices; the identical code drives the
256-chip production mesh (see tests/test_distributed.py and DESIGN.md
Sec. 5).

    PYTHONPATH=src python examples/distributed_index.py
"""

import time

from repro.configs import platform

mesh = platform.simulate_mesh(8)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import make_index  # noqa: E402
from repro.data import points as gen  # noqa: E402


def main():
    n = 16_384
    key = jax.random.PRNGKey(0)
    pts = gen.uniform(key, n, 2)

    t0 = time.time()
    idx = make_index("spac-h", pts, mesh=mesh, phi=32)
    idx.block_until_ready()
    print(f"built over {mesh.shape['data']} shards in "
          f"{time.time() - t0:.2f}s; size={len(idx)}, "
          f"dropped={int(idx.dropped)}")

    batch = gen.uniform(jax.random.PRNGKey(1), 2_048, 2)
    t0 = time.time()
    idx = idx.insert(batch).block_until_ready()
    print(f"all_to_all batch insert of {batch.shape[0]}: "
          f"{time.time() - t0:.2f}s; size={len(idx)}")

    qs = gen.uniform(jax.random.PRNGKey(2), 64, 2)
    d2, nbrs, ok = idx.knn_points(qs, 10)
    # exactness: compare one query against brute force
    allp = jnp.concatenate([pts, batch]).astype(jnp.float32)
    diff = allp - qs[0].astype(jnp.float32)
    bf = jnp.sort(jnp.sum(diff * diff, -1))[:10]
    assert jnp.allclose(jnp.sort(d2[0]), bf), "distributed kNN mismatch"
    print(f"distributed kNN exact across shards "
          f"(d2[0,0]={float(d2[0, 0]):.1f})")

    lo = jnp.array([[0, 0]], jnp.int32)
    hi = jnp.array([[1 << 19, 1 << 19]], jnp.int32)
    cnt = idx.range_count(lo, hi)   # exact: engine escalates per-shard
    print(f"distributed range count: {int(cnt[0])}")


if __name__ == "__main__":
    main()
