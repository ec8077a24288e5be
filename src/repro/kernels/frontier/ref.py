"""Pure-jnp mirror of the fused frontier kernel.

Shares ``prep.prepare`` and the kernel's tile expressions
(``_tile_distances`` / ``_merge_topk``) so its outputs are bit-identical
to the interpret-mode kernel: same operands, same expression graph, same
visit prefix (the ``while_loop`` stops at the first failed lower bound —
exactly the set of steps the kernel's ``pl.when`` lets through).

This is also the fast CPU spelling behind ``impl="auto"``: one argsort
over G groups per query *block* and contiguous ``dynamic_slice`` tiles,
versus the chunked frontier's per-query argsort over all R rows and
gather-heavy chunk bodies.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.frontier.kernel import _merge_topk, _tile_distances
from repro.kernels.frontier.prep import BIG, FrontierPrep


def knn_frontier_ref(pr: FrontierPrep, *, k: int):
    """Traverse prepared groups per query block; returns (d2, ids).

    Outputs are in sorted-query order, shape ``(Qp, k)`` — ``ops.py``
    undoes the sort and padding.
    """
    nqb, G = pr.order.shape
    bq, P = pr.block_q, pr.points_per_group
    D = pr.qs.shape[1]
    qblocks = pr.qs.reshape(nqb, bq, D)

    def block(qb, order_b, glb_b):
        def cond(st):
            j, dist, _ = st
            return (j < G) & (glb_b[j] <= jnp.max(dist[:, k - 1]))

        def body(st):
            j, dist, idx = st
            g = order_b[j]
            pk = jax.lax.dynamic_slice_in_dim(pr.pts, g * P, P, axis=1)
            d2 = _tile_distances(qb, pk)
            ids = g * P + jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
            dist, idx = _merge_topk(dist, idx, d2, ids, k)
            return j + 1, dist, idx

        init = (jnp.int32(0),
                jnp.full((bq, k), BIG, jnp.float32),
                jnp.full((bq, k), -1, jnp.int32))
        _, dist, idx = jax.lax.while_loop(cond, body, init)
        return dist, jnp.where(dist >= BIG, -1, idx)

    d2, ids = jax.vmap(block)(qblocks, pr.order, pr.glb)
    return d2.reshape(-1, k), ids.reshape(-1, k)
