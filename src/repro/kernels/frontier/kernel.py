"""Fused frontier-kNN Pallas kernel.

Launches over a ``(query_blocks, groups)`` grid.  The per-block group
visit order and lower bounds arrive as scalar-prefetch operands, so the
point tile for step ``j`` is fetched data-dependently via the BlockSpec
``index_map`` — the gather the chunked frontier did on the host happens
in the kernel's pipeline instead.  The running top-k lives in VMEM
scratch across the inner grid axis, and a per-block ``pl.when`` skips a
tile's distances and merge once the sorted lower bound passes the
block's worst kth-best distance. The pipeline still fetches a skipped
tile; whole chunks of steps past every block's prefix are not launched
at all (:func:`knn_frontier_pallas`).

Points are lane-dense: a tile is a ``(D + 1, P)`` block whose rows are
the D coordinates and the slot validity of P points (``prep.py``), so no
HBM array has a tiny minor axis for the TPU's (8, 128) tiling to pad.
Distances are the direct ``sum_d (q_d - p_d)^2`` on the VPU, so the
selected hits match the chunked frontier's whenever the squared
distances are f32-exact, whatever the coordinates' magnitude; ``ops.py``
re-scores the k hits with the chunked route's own expression.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.frontier.prep import BIG, FrontierPrep

_NO_ID = 2 ** 31 - 1
# scalar-prefetch entries per launch and operand: two int32/f32 tables of
# this many entries take 128 KiB of the 1 MiB SMEM
PREFETCH_ENTRIES = 16384


def _tile_distances(q, pk):
    """``(q - p)^2`` summed over D for one (block_q, P) tile.

    ``q`` is ``(block_q, D)``; ``pk`` is the lane-dense ``(D + 1, P)``
    tile (coordinates, then validity). Invalid slots score ``BIG``.
    Shared verbatim by the jnp mirrors (``ref.py``, the flat kernel) so
    every spelling evaluates the identical expression graph — parity by
    construction, not by tolerance.
    """
    dim = q.shape[1]
    d2 = None
    for d in range(dim):
        diff = q[:, d:d + 1] - pk[d:d + 1, :]
        d2 = diff * diff if d2 is None else d2 + diff * diff
    return jnp.where(pk[dim:dim + 1, :] > 0, d2, BIG)


def _merge_topk(dist, idx, d2, ids, k):
    """Merge a tile into the running top-k by k rounds of min-extraction.

    Each round takes the smallest distance left in the running
    ``(block_q, k)`` list and the ``(block_q, P)`` tile, the lowest id
    among equal distances, and retires that entry. The result is sorted
    by ``(d2, id)``. Only lane reductions and selects — no sort, top_k,
    gather or lane-axis concatenation, none of which Mosaic lowers.
    """
    col = jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)

    def round_(r, carry):
        dist, d2, out_d, out_i = carry
        m = jnp.minimum(jnp.min(dist, axis=1, keepdims=True),
                        jnp.min(d2, axis=1, keepdims=True))
        pick = jnp.minimum(
            jnp.min(jnp.where(dist == m, idx, _NO_ID), axis=1,
                    keepdims=True),
            jnp.min(jnp.where(d2 == m, ids, _NO_ID), axis=1,
                    keepdims=True))
        out_d = jnp.where(col == r, jnp.minimum(m, BIG), out_d)
        out_i = jnp.where(col == r, pick, out_i)
        dist = jnp.where((dist == m) & (idx == pick), jnp.inf, dist)
        d2 = jnp.where((d2 == m) & (ids == pick), jnp.inf, d2)
        return dist, d2, out_d, out_i

    init = (dist, d2, jnp.full_like(dist, BIG), jnp.full_like(idx, -1))
    _, _, out_d, out_i = jax.lax.fori_loop(0, k, round_, init)
    return out_d, out_i


def _frontier_kernel(order_ref, glb_ref, q_ref, p_ref, din_ref, iin_ref,
                     d2_ref, id_ref, dist_scr, idx_scr, *, k, ppg):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dist_scr[...] = din_ref[...]
        idx_scr[...] = iin_ref[...]

    # Early exit: group bounds arrive ascending, and the block's worst
    # kth-best only shrinks, so once a bound fails it fails for every
    # later step — the predicated skip visits exactly the same prefix the
    # reference while_loop does.
    live = glb_ref[i, j] <= jnp.max(dist_scr[:, k - 1:k])

    @pl.when(live)
    def _step():
        g = order_ref[i, j]
        d2 = _tile_distances(q_ref[...], p_ref[...])
        ids = g * ppg + jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
        dist_scr[...], idx_scr[...] = _merge_topk(
            dist_scr[...], idx_scr[...], d2, ids, k)

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        d2_ref[...] = dist_scr[...]
        id_ref[...] = idx_scr[...]


def _launch(order, glb, qs, pts, dist, idx, *, k, block_q, ppg,
            interpret):
    """One ``pallas_call`` over the ``(query_blocks, steps)`` slice of the
    visit order in ``order``/``glb``, continuing from the running top-k
    ``(dist, idx)``; returns the updated ``(dist, idx)``."""
    nqb, steps = order.shape
    D = qs.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nqb, steps),
        in_specs=[
            pl.BlockSpec((block_q, D), lambda i, j, o, b: (i, 0)),
            pl.BlockSpec((D + 1, ppg), lambda i, j, o, b: (0, o[i, j])),
            pl.BlockSpec((block_q, k), lambda i, j, o, b: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i, j, o, b: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda i, j, o, b: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i, j, o, b: (i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, k), jnp.float32),
            pltpu.VMEM((block_q, k), jnp.int32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_frontier_kernel, k=k, ppg=ppg),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(dist.shape, jnp.float32),
                   jax.ShapeDtypeStruct(idx.shape, jnp.int32)],
        interpret=interpret,
    )(order, glb, qs, pts, dist, idx)


def knn_frontier_pallas(pr: FrontierPrep, *, k: int,
                        interpret: bool = False):
    """Run the fused kernel over prepared operands; returns (d2, ids).

    The visit order and bounds are scalar-prefetch operands, which live
    whole in the core's small scalar memory (SMEM, 1 MiB on v5e). So the
    ``(query_blocks, groups)`` order is cut into chunks of at most
    ``PREFETCH_ENTRIES`` entries, one launch each, with the running
    top-k carried between launches. A ``while_loop`` over the chunks
    stops once no query block can still reach the next chunk's first
    bound — the same prefix, chunk by chunk, that the in-kernel skip
    visits step by step.

    Outputs are in sorted-query order, shape ``(Qp, k)`` — ``ops.py``
    undoes the sort and padding.
    """
    nqb, G = pr.order.shape
    bq, P = pr.block_q, pr.points_per_group
    steps = max(1, min(G, PREFETCH_ENTRIES // nqb))
    chunks = -(-G // steps)
    pad = chunks * steps - G
    # padded steps are never live: inf fails every bound test
    order = jnp.pad(pr.order, ((0, 0), (0, pad)))
    glb = jnp.pad(pr.glb, ((0, 0), (0, pad)), constant_values=jnp.inf)
    Qp = pr.qs.shape[0]
    launch = functools.partial(_launch, k=k, block_q=bq, ppg=P,
                               interpret=interpret)

    def cond(st):
        c, dist, _ = st
        first = jax.lax.dynamic_slice_in_dim(glb, c * steps, 1, axis=1)
        kth = dist[:, k - 1].reshape(nqb, bq).max(axis=1)
        return (c < chunks) & jnp.any(first[:, 0] <= kth)

    def body(st):
        c, dist, idx = st
        o = jax.lax.dynamic_slice_in_dim(order, c * steps, steps, axis=1)
        b = jax.lax.dynamic_slice_in_dim(glb, c * steps, steps, axis=1)
        dist, idx = launch(o, b, pr.qs, pr.pts, dist, idx)
        return c + 1, dist, idx

    init = (jnp.int32(0), jnp.full((Qp, k), BIG, jnp.float32),
            jnp.full((Qp, k), -1, jnp.int32))
    _, d2, ids = jax.lax.while_loop(cond, body, init)
    return d2, jnp.where(d2 >= BIG, -1, ids)
