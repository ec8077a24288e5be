"""Shared batched query engine over leaf-row indexes.

TPU adaptation of the paper's queries (Sec. 2.2): the best-first kNN with a
priority queue becomes a *chunked frontier traversal* — rows are visited in
ascending order of bbox distance, a running top-k is maintained, and the loop
stops as soon as the next chunk's bbox lower bound exceeds the current k-th
best distance. This is exact (same pruning argument as best-first search) and
fully vectorized over queries via ``vmap``.

Range queries gather candidate rows whose bbox overlaps the query box (fixed
capacity ``max_rows``; each box's count of overlapping rows tells callers
whether it sufficed and how to size it).

The engine only needs the "leaf directory view" every index exposes:
    pts (R, C, D), valid (R, C), active (R,), bbox_lo/hi (R, D)
so P-Orth trees, SPaC trees and the kd/Zd baselines all share it.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .leafstore import BIG


class LeafView(NamedTuple):
    pts: jax.Array      # (R, C, D) float32 or int32
    valid: jax.Array    # (R, C) bool
    active: jax.Array   # (R,) bool
    bbox_lo: jax.Array  # (R, D)
    bbox_hi: jax.Array  # (R, D)


def _f32(x):
    return x.astype(jnp.float32)


def dist2_point_box(q, lo, hi):
    """Squared distance from point q (D,) to boxes (R, D)."""
    d = jnp.maximum(jnp.maximum(_f32(lo) - _f32(q), _f32(q) - _f32(hi)), 0.0)
    return jnp.sum(d * d, axis=-1)


def _knn_single(view: LeafView, q, k: int, chunk: int):
    R, C, dim = view.pts.shape
    n_chunks = (R + chunk - 1) // chunk
    dmin2 = jnp.where(view.active, dist2_point_box(q, view.bbox_lo,
                                                   view.bbox_hi), BIG)
    row_order = jnp.argsort(dmin2).astype(jnp.int32)
    dmin2_sorted = dmin2[row_order]
    pad = n_chunks * chunk - R
    row_order = jnp.pad(row_order, (0, pad), constant_values=0)
    dmin2_sorted = jnp.pad(dmin2_sorted, (0, pad), constant_values=BIG)

    best_d2 = jnp.full((k,), BIG)
    best_id = jnp.full((k,), -1, jnp.int32)

    def cond(state):
        i, best_d2, _ = state
        frontier = jax.lax.dynamic_slice(dmin2_sorted, (i * chunk,), (1,))[0]
        return (i < n_chunks) & (frontier <= best_d2[k - 1])

    def body(state):
        i, best_d2, best_id = state
        rows = jax.lax.dynamic_slice(row_order, (i * chunk,), (chunk,))
        pts = view.pts[rows]                      # (chunk, C, D)
        # mask the tail padding of row_order (pad rows alias row 0 and
        # would re-count its points when the loop reaches the last chunk)
        pos = i * chunk + jnp.arange(chunk, dtype=jnp.int32)
        ok = (view.valid[rows] & view.active[rows][:, None]
              & (pos < R)[:, None])
        diff = _f32(pts) - _f32(q)[None, None, :]
        d2 = jnp.sum(diff * diff, axis=-1)
        d2 = jnp.where(ok, d2, BIG).reshape(-1)
        ids = (rows[:, None] * C + jnp.arange(C, dtype=jnp.int32)[None, :]
               ).reshape(-1)
        cat_d2 = jnp.concatenate([best_d2, d2])
        cat_id = jnp.concatenate([best_id, ids])
        neg, sel = jax.lax.top_k(-cat_d2, k)
        return i + 1, -neg, cat_id[sel]

    _, best_d2, best_id = jax.lax.while_loop(
        cond, body, (jnp.int32(0), best_d2, best_id))
    best_id = jnp.where(best_d2 >= BIG, -1, best_id)
    return best_d2, best_id


def knn_impl(view: LeafView, queries, k: int, chunk: int = 8):
    """Unjitted :func:`knn` — use inside shard_map/pjit regions (a nested
    ``jax.jit`` around the vmapped while_loop miscompiles under shard_map
    on some jax versions; inner jit is a no-op there anyway)."""
    return jax.vmap(lambda q: _knn_single(view, q, k, chunk))(queries)


@functools.partial(jax.jit, static_argnums=(2, 3))
def knn(view: LeafView, queries, k: int, chunk: int = 8):
    """Exact batched k-nearest-neighbors.

    queries: (Q, D). Returns (d2 (Q, k) ascending, flat ids (Q, k) = row*C+slot,
    -1 padded when fewer than k points exist).
    """
    return knn_impl(view, queries, k, chunk)


def gather_points(view: LeafView, flat_ids):
    """Resolve flat ids (row*C+slot) from knn/range_list into coordinates."""
    R, C, dim = view.pts.shape
    safe = jnp.maximum(flat_ids, 0)
    pts = view.pts.reshape(R * C, dim)[safe]
    return jnp.where((flat_ids >= 0)[..., None], pts, 0)


def flatten_view(view: LeafView):
    """Flat (R*C, D) points + validity — the brute-force scan's
    operands. The flat index equals row*C+slot, so ids from a flat kNN
    scan and from the frontier traversal live in the same id space."""
    R, C, dim = view.pts.shape
    ok = (view.valid & view.active[:, None]).reshape(R * C)
    return view.pts.reshape(R * C, dim), ok


def _boxes_overlap(lo_a, hi_a, lo_b, hi_b):
    return jnp.all((_f32(lo_a) <= _f32(hi_b)) & (_f32(lo_b) <= _f32(hi_a)),
                   axis=-1)


# block width of the two-level selection below: one TPU lane row
SELECT_BLOCK = 128


def _first_true(mask, m: int):
    """First ``min(m, L)`` entries of a stable ``argsort(~mask)`` over
    the (L,) ``mask``: the True positions in order, then the False ones.

    Two levels, so nothing sorts or scatters over L: count each block
    of ``SELECT_BLOCK`` entries, find each output slot's block by a
    binary search on the blocks' running counts, then its entry by a
    running count over that one block's bits. Slot j < n_true is the
    j-th True entry; slot j >= n_true the (j - n_true)-th False one (a
    block holds B minus its count of those). The padding to whole
    blocks is False and sorts after every real entry, so no slot
    reaches it."""
    L = mask.shape[0]
    nb = -(-L // SELECT_BLOCK)
    bits = jnp.pad(mask, (0, nb * SELECT_BLOCK - L)).reshape(
        nb, SELECT_BLOCK)
    per_block = jnp.sum(bits, axis=1, dtype=jnp.int32)
    true_end = jnp.cumsum(per_block)               # inclusive
    false_end = (jnp.arange(1, nb + 1, dtype=jnp.int32) * SELECT_BLOCK
                 - true_end)
    n_true = true_end[-1]
    j = jnp.arange(min(int(m), L), dtype=jnp.int32)
    want = j < n_true
    t = jnp.where(want, j, j - n_true)            # rank among its kind
    blk = jnp.where(want,
                    jnp.searchsorted(true_end, t, side="right"),
                    jnp.searchsorted(false_end, t, side="right")
                    ).astype(jnp.int32)
    true_before = true_end[blk] - per_block[blk]
    rank = t - jnp.where(want, true_before,
                         blk * SELECT_BLOCK - true_before)
    kind = bits[blk] == want[:, None]              # (slots, B)
    seen = jnp.cumsum(kind, axis=1, dtype=jnp.int32)
    pos = jnp.sum(seen <= rank[:, None], axis=1, dtype=jnp.int32)
    return blk * SELECT_BLOCK + pos


def _range_rows(view: LeafView, lo, hi, max_rows: int):
    """Candidate rows of one query box: the first ``min(max_rows, R)``
    rows whose bbox overlaps it, in row order, padded with the lowest
    rows that do not (engine buckets can exceed R). Returns
    (rows, rows_ok, truncated, n_overlap)."""
    overlap = _boxes_overlap(view.bbox_lo, view.bbox_hi, lo[None, :],
                             hi[None, :]) & view.active
    n_overlap = jnp.sum(overlap, dtype=jnp.int32)
    rows = _first_true(overlap, max_rows)
    rows_ok = jnp.arange(rows.shape[0], dtype=jnp.int32) < n_overlap
    return rows, rows_ok, n_overlap > max_rows, n_overlap


def _range_count_single(view: LeafView, lo, hi, max_rows: int):
    rows, rows_ok, _, n_overlap = _range_rows(view, lo, hi, max_rows)
    pts = view.pts[rows]
    inside = (jnp.all((_f32(pts) >= _f32(lo)) & (_f32(pts) <= _f32(hi)),
                      axis=-1)
              & view.valid[rows] & rows_ok[:, None])
    return jnp.sum(inside, dtype=jnp.int32), n_overlap


def range_count_impl(view: LeafView, lo, hi, max_rows: int = 128):
    """Unjitted range count — use inside shard_map/pjit regions.

    Returns (counts (Q,), n_overlap (Q,)): each box's count of rows
    whose bbox overlaps it; the count is exact where n_overlap <=
    max_rows."""
    return jax.vmap(lambda l, h: _range_count_single(view, l, h, max_rows))(
        lo, hi)


@functools.partial(jax.jit, static_argnums=(3,))
def range_count(view: LeafView, lo, hi, max_rows: int = 128):
    """Exact batched range-count. lo/hi: (Q, D) inclusive boxes.

    Returns (counts (Q,), truncated (Q,)); a True truncated flag means
    max_rows was too small for exactness (resize and re-run)."""
    cnt, n_overlap = range_count_impl(view, lo, hi, max_rows)
    return cnt, n_overlap > max_rows


def _range_list_single(view: LeafView, lo, hi, max_rows: int, cap: int):
    R, C, dim = view.pts.shape
    rows, rows_ok, truncated, _ = _range_rows(view, lo, hi, max_rows)
    pts = view.pts[rows]
    inside = (jnp.all((_f32(pts) >= _f32(lo)) & (_f32(pts) <= _f32(hi)),
                      axis=-1)
              & view.valid[rows] & rows_ok[:, None])
    flat_in = inside.reshape(-1)
    flat_ids = (rows[:, None] * C
                + jnp.arange(C, dtype=jnp.int32)[None, :]).reshape(-1)
    # stable compaction of hits to the front
    count = jnp.sum(flat_in, dtype=jnp.int32)
    sel = _first_true(flat_in, cap)
    ids = jnp.where(jnp.arange(sel.shape[0]) < count, flat_ids[sel], -1)
    return ids, count, truncated


def range_list_impl(view: LeafView, lo, hi, max_rows: int = 128,
                    cap: int = 512):
    """Unjitted range-report with the *row* truncation flag kept
    separate from output-capacity overflow: (ids, counts, rows_trunc).

    ``counts`` is exact whenever rows_trunc is False, even if it
    exceeds ``cap`` — the engine escalates the two buffers
    independently off these signals."""
    return jax.vmap(
        lambda l, h: _range_list_single(view, l, h, max_rows, cap))(lo, hi)


@functools.partial(jax.jit, static_argnums=(3, 4))
def range_list(view: LeafView, lo, hi, max_rows: int = 128, cap: int = 512):
    """Exact batched range-report with fixed output capacity.

    Returns (ids (Q, cap) flat row*C+slot padded with -1, counts (Q,),
    truncated (Q,))."""
    ids, count, rows_trunc = range_list_impl(view, lo, hi, max_rows, cap)
    return ids, count, rows_trunc | (count > cap)
