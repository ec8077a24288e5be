"""Pallas kNN kernel: tiled distances + running top-k merge.

The query engine's brute-force route: a block of queries scans candidate
point tiles, maintaining a per-query top-k. TPU mapping:
  * grid = (q_blocks, point_blocks), point axis fastest;
  * points are lane-dense ``(D + 1, block_p)`` tiles (coordinates, then
    validity — ``frontier.prep.pack_points``);
  * distances and the top-k merge are the fused frontier kernel's tile
    expressions (``_tile_distances`` / ``_merge_topk``): the direct
    ``(q - p)^2`` sum, and k rounds of min-extraction kept in VMEM
    scratch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.frontier.kernel import _merge_topk, _tile_distances
from repro.kernels.frontier.prep import BIG, pack_points

_LANES = 128


def _knn_kernel(q_ref, p_ref, d_out, i_out, dist_scr, idx_scr, *,
                k: int, block_p: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dist_scr[...] = jnp.full_like(dist_scr, BIG)
        idx_scr[...] = jnp.full_like(idx_scr, -1)

    d2 = _tile_distances(q_ref[...], p_ref[...])
    ids = j * block_p + jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    dist_scr[...], idx_scr[...] = _merge_topk(
        dist_scr[...], idx_scr[...], d2, ids, k)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        d_out[...] = dist_scr[...]
        i_out[...] = jnp.where(dist_scr[...] >= BIG, -1, idx_scr[...])


def knn_pallas(queries, points, ok, *, k: int, block_q: int = 128,
               block_p: int = 512, interpret: bool = False):
    """Exact brute-force kNN: queries (Q, D) vs points (N, D) with validity
    mask ok (N,). Returns (d2 (Q, k), idx (Q, k)), each query's hits
    sorted by (d2, idx), -1-padded."""
    Q, dim = queries.shape
    N = points.shape[0]
    block_q = min(block_q, Q)
    block_p = min(block_p, -(-N // _LANES) * _LANES)
    n_pad = -(-N // block_p) * block_p
    pk = pack_points(points, ok)
    if n_pad > N:
        pk = jnp.pad(pk, ((0, 0), (0, n_pad - N)))     # validity row -> 0
    grid = (-(-Q // block_q), n_pad // block_p)
    kernel = functools.partial(_knn_kernel, k=k, block_p=block_p)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_q, dim), lambda i, j: (i, 0)),
                  pl.BlockSpec((dim + 1, block_p), lambda i, j: (0, j))],
        out_specs=[pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
                   pl.BlockSpec((block_q, k), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((Q, k), jnp.float32),
                   jax.ShapeDtypeStruct((Q, k), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((block_q, k), jnp.float32),
                        pltpu.VMEM((block_q, k), jnp.int32)],
        interpret=interpret,
    )(queries.astype(jnp.float32), pk)
