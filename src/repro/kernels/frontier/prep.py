"""Shared host-side prep for the fused frontier kernel and its jnp ref.

Everything here is plain jnp (jit-safe, shard_map-safe) and is shared by
both spellings so their inputs — group packing, per-block traversal
order — are bit-identical by construction.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

BIG = 3.4e38  # python float: kernels close over it without a captured const

_MORTON_BITS = 10  # 10 bits/dim -> <= 30-bit codes for D <= 3


def morton_key(q: jnp.ndarray, bits: int = _MORTON_BITS) -> jnp.ndarray:
    """Quantized morton code per query, for spatial blocking.

    Queries are sorted by this key before being cut into blocks of
    ``block_q`` so each block is spatially tight — the per-block shared
    traversal order and early-exit threshold only prune well when the
    block's queries want the same groups.  (Local impl rather than
    ``core.sfc`` to keep kernels importable without the core package.)
    """
    qf = q.astype(jnp.float32)
    lo = jnp.min(qf, axis=0)
    span = jnp.maximum(jnp.max(qf, axis=0) - lo, jnp.float32(1e-30))
    top = jnp.float32((1 << bits) - 1)
    cell = jnp.clip((qf - lo) / span * top, 0.0, top).astype(jnp.uint32)
    code = jnp.zeros(q.shape[0], jnp.uint32)
    for b in range(bits):
        for d in range(q.shape[1]):
            code = code | (((cell[:, d] >> b) & 1) << (b * q.shape[1] + d))
    return code


def pack_points(pts, ok):
    """Lane-dense ``(D + 1, N)`` f32 operand from ``(N, D)`` points and
    ``(N,)`` validity: rows 0..D-1 are the coordinates, row D is 1.0 for
    a live slot and 0.0 otherwise. With points on the lane axis a
    kernel tile is ``(D + 1, P)``, which the TPU's (8, 128) tiling pads
    at most to 8 rows — an ``(N, D)`` array would pad D to 128 lanes."""
    return jnp.concatenate([pts.astype(jnp.float32).T,
                            ok.astype(jnp.float32)[None, :]])


def rescore(points, queries, ids):
    """Re-score each query's hits as ``sum((p - q)^2, axis=-1)`` and
    re-sort them by ``(d2, id)`` (-1 ids score ``BIG``).

    A kernel tile sums ``D`` separate squares, which XLA may fuse into a
    different rounding than the minor-axis reduction the chunked
    frontier and the jnp oracle use; beyond 2^24 the two differ in the
    last ulp. Re-scoring the k hits with the one shared expression makes
    every route return identical distances. ``points`` is ``(N, D)``,
    ``ids`` ``(Q, k)`` indices into it."""
    p = points.astype(jnp.float32)[jnp.clip(ids, 0)]
    diff = p - queries.astype(jnp.float32)[:, None, :]
    d2 = jnp.where(ids < 0, BIG, jnp.sum(diff * diff, axis=-1))
    d2, ids = jax.lax.sort((d2, ids), dimension=-1, num_keys=2)
    return d2, jnp.where(d2 >= BIG, -1, ids)


class FrontierPrep(NamedTuple):
    """Kernel-ready operands; see ``prepare`` for shapes."""

    qs: jnp.ndarray          # (Qp, D) f32 sorted+padded queries
    pts: jnp.ndarray         # (D+1, G*P) f32 grouped points (pack_points)
    order: jnp.ndarray       # (nqb, G) int32 group visit order per block
    glb: jnp.ndarray         # (nqb, G) f32 group lower bounds, ascending
    inv: jnp.ndarray         # (Q,) int32 undoes the query sort
    block_q: int
    points_per_group: int


def prepare(pts, valid, active, bbox_lo, bbox_hi, queries, *,
            block_q: int, block_p: int) -> FrontierPrep:
    """Pack rows into groups and order them per query block.

    Rows are grouped ``block_r = max(1, block_p // C)`` at a time, so one
    kernel tile is ``P = block_r * C`` points and the flat candidate id of
    slot ``o`` in group ``g`` is ``g * P + o`` — the same ``row * C + col``
    id space the engine already uses, because groups are contiguous rows.
    """
    R, C, D = pts.shape
    block_r = max(1, block_p // C)
    P = block_r * C
    G = -(-R // block_r)
    pad_r = G * block_r - R

    ok = valid & active[:, None]
    pts_f = pts.astype(jnp.float32)
    lo_f = jnp.where(active[:, None], bbox_lo.astype(jnp.float32), BIG)
    hi_f = jnp.where(active[:, None], bbox_hi.astype(jnp.float32), -BIG)
    if pad_r:
        pts_f = jnp.concatenate(
            [pts_f, jnp.zeros((pad_r, C, D), jnp.float32)])
        ok = jnp.concatenate([ok, jnp.zeros((pad_r, C), bool)])
        lo_f = jnp.concatenate([lo_f, jnp.full((pad_r, D), BIG)])
        hi_f = jnp.concatenate([hi_f, jnp.full((pad_r, D), -BIG)])

    glo = lo_f.reshape(G, block_r, D).min(axis=1)          # (G, D)
    ghi = hi_f.reshape(G, block_r, D).max(axis=1)
    galive = glo[:, 0] <= ghi[:, 0]
    pk = pack_points(pts_f.reshape(G * P, D), ok.reshape(G * P))

    Q = queries.shape[0]
    qf = queries.astype(jnp.float32)
    perm = jnp.argsort(morton_key(qf)).astype(jnp.int32)
    inv = jnp.argsort(perm).astype(jnp.int32)
    qs = qf[perm]
    nqb = -(-Q // block_q)
    pad_q = nqb * block_q - Q
    if pad_q:
        # Pad with the *last* sorted query so the tail block stays tight.
        qs = jnp.concatenate(
            [qs, jnp.broadcast_to(qs[-1:], (pad_q, D))])

    qb = qs.reshape(nqb, block_q, D)
    blo, bhi = qb.min(axis=1), qb.max(axis=1)              # (nqb, D)
    gap = jnp.maximum(jnp.maximum(glo[None] - bhi[:, None],
                                  blo[:, None] - ghi[None]), 0.0)
    glb = jnp.where(galive[None, :], (gap * gap).sum(-1), BIG)
    order = jnp.argsort(glb, axis=1).astype(jnp.int32)     # (nqb, G)
    glb = jnp.take_along_axis(glb, order, axis=1)

    return FrontierPrep(qs=qs, pts=pk, order=order, glb=glb, inv=inv,
                        block_q=block_q, points_per_group=P)
