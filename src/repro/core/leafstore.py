"""Shared leaf-row machinery for array-based spatial indexes.

TPU adaptation of the paper's blocked leaves (Sec. 2.3 / 4): a leaf is a row of
a ``(R, C)`` array with ``C = 2 * phi`` capacity and slack slots, plus a validity
mask. Batch appends are masked scatters into slack slots (the paper's
partial-order relaxation: nothing is sorted on append); deletions are ranked
multiset matches + an intra-row stable compaction. All helpers are shape-static
and jit-compatible; index structures are functional pytrees.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# +inf stand-in that survives arithmetic. A *numpy* scalar, not a jnp
# one: a device constant here would initialize the jax backend at
# import time and lock the topology before repro.configs.platform can
# stage a simulated mesh (the driver's --mesh flag relies on imports
# staying device-free).
BIG = np.float32(3.4e38)


def chunk_rows_from_sorted(n_total: int, phi: int):
    """Row/slot assignment that packs a sorted sequence into rows of ``phi``.

    Returns (row, slot) for positions 0..n_total-1. Callers mask invalid
    positions themselves (e.g. padded tails).
    """
    pos = jnp.arange(n_total, dtype=jnp.int32)
    return pos // phi, pos % phi


def scatter_to_rows(target, row, slot, values, mask):
    """Masked scatter of ``values[i]`` into ``target[row[i], slot[i]]``."""
    row = jnp.where(mask, row, target.shape[0])  # out-of-bounds => dropped
    return target.at[row, slot].set(values, mode="drop")


def segment_bbox(points, row, mask, num_rows: int):
    """Tight per-row bounding boxes via scatter-min/max.

    points: (N, D); row: (N,) int32; mask: (N,) bool.
    Returns (lo, hi): (num_rows, D). Rows with no points get (+BIG, -BIG).
    """
    dim = points.shape[-1]
    dt = points.dtype
    big = _big_for(dt)
    row = jnp.where(mask, row, num_rows)
    lo = jnp.full((num_rows, dim), big, dt).at[row].min(points, mode="drop")
    hi = jnp.full((num_rows, dim), -big, dt).at[row].max(points, mode="drop")
    return lo, hi


def _big_for(dt):
    if jnp.issubdtype(dt, jnp.floating):
        return jnp.asarray(jnp.finfo(dt).max, dt)
    return jnp.asarray(jnp.iinfo(dt).max, dt)


def row_bbox_from_slots(pts, valid):
    """Recompute (lo, hi) over valid slots of rows. pts: (R, C, D)."""
    dt = pts.dtype
    big = _big_for(dt)
    m = valid[..., None]
    lo = jnp.min(jnp.where(m, pts, big), axis=1)
    hi = jnp.max(jnp.where(m, pts, -big), axis=1)
    return lo, hi


def group_occurrence(group_ids):
    """Occurrence index of each element within its run.

    Equal group ids must be contiguous (the batch is sorted by routing key),
    but runs need not be in ascending id order. occ[i] = i - first index of
    the run containing i (computed with a running-max scan over run starts).
    """
    n = group_ids.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    change = jnp.concatenate(
        [jnp.ones((1,), bool), group_ids[1:] != group_ids[:-1]])
    # cummax, not associative_scan: on the TPU it lowers to one
    # reduce-window, where the scan's log-depth slicing takes minutes to
    # compile at millions of points
    run_first = jax.lax.cummax(jnp.where(change, idx, 0))
    return idx - run_first


def append_unsorted(pts_rows, valid_rows, count, row_of, new_pts, new_mask,
                    extras_rows=(), new_extras=()):
    """The partial-order relaxation: scatter-append a *sorted-by-row* batch
    into row slack slots without sorting row contents (paper Alg. 4 line 9).

    row_of must be non-decreasing where new_mask is True (callers sort the
    batch by routing key first — paper sorts by SFC code which implies this).
    Points that would exceed capacity must be masked out by the caller
    (overflow path). Returns updated (pts_rows, valid_rows, count, extras...).
    """
    C = pts_rows.shape[1]
    occ = group_occurrence(row_of)
    slot = count[row_of] + occ
    ok = new_mask & (slot < C)
    pts_rows = scatter_to_rows(pts_rows, row_of, slot, new_pts, ok)
    valid_rows = scatter_to_rows(valid_rows, row_of, slot,
                                 jnp.ones(new_pts.shape[0], bool), ok)
    adds = jnp.zeros_like(count).at[jnp.where(ok, row_of, count.shape[0])].add(
        1, mode="drop")
    out_extras = []
    for tgt, val in zip(extras_rows, new_extras):
        out_extras.append(scatter_to_rows(tgt, row_of, slot, val, ok))
    return pts_rows, valid_rows, count + adds, tuple(out_extras)


def batch_rank_among_equals(sorted_pts, row_of, window: int, mask=None):
    """Rank of each batch point among equal (row, coords) batch entries.

    The batch is sorted by routing key, so equal points are contiguous;
    a window of ``window`` preceding entries suffices (a row cannot match
    more than C slots anyway). mask: only count masked-in predecessors
    (multi-round deletion ranks among *still-unmatched* entries).
    """
    n, dim = sorted_pts.shape
    if mask is None:
        mask = jnp.ones(n, bool)
    rank = jnp.zeros(n, jnp.int32)
    for s in range(1, window + 1):
        prev_pts = jnp.roll(sorted_pts, s, axis=0)
        prev_row = jnp.roll(row_of, s)
        prev_ok = jnp.roll(mask, s)
        same = ((jnp.arange(n) >= s) & prev_ok & (prev_row == row_of)
                & jnp.all(prev_pts == sorted_pts, axis=-1))
        rank = rank + same.astype(jnp.int32)
    return rank


def slot_rank_among_equals(pts_rows, valid_rows):
    """For every slot: number of earlier valid slots in the same row holding
    an identical point. pts_rows: (n, C, D) -> (n, C) int32."""
    eq = jnp.all(pts_rows[:, :, None, :] == pts_rows[:, None, :, :], axis=-1)
    C = pts_rows.shape[1]
    earlier = jnp.tril(jnp.ones((C, C), bool), k=-1)[None]
    return jnp.sum(eq & earlier & valid_rows[:, None, :], axis=-1,
                   dtype=jnp.int32)


def ranked_delete(pts_rows, valid_rows, count, row_of, del_pts, del_mask,
                  window: int):
    """Delete a sorted-by-row batch from rows with exact multiset semantics.

    Each batch entry removes at most one matching valid slot; duplicate batch
    entries remove distinct copies (rank matching). Returns updated
    (valid_rows, count, matched_mask).
    """
    R = pts_rows.shape[0]
    n = del_pts.shape[0]
    brank = batch_rank_among_equals(del_pts, row_of, window, del_mask)
    # per batch point: candidate slots in its row; slot ranks only for
    # these gathered rows — O(n C^2) temp, not O(R C^2) for the tree
    rows_p = pts_rows[row_of]            # (n, C, D)
    rows_v = valid_rows[row_of]          # (n, C)
    rows_r = slot_rank_among_equals(rows_p, rows_v)     # (n, C)
    eq = jnp.all(rows_p == del_pts[:, None, :], axis=-1)
    hit = eq & rows_v & (rows_r == brank[:, None]) & del_mask[:, None]
    matched = jnp.any(hit, axis=-1)
    slot = jnp.argmax(hit, axis=-1).astype(jnp.int32)
    valid_rows = scatter_to_rows(valid_rows, row_of, slot,
                                 jnp.zeros(n, bool), matched)
    dels = jnp.zeros_like(count).at[
        jnp.where(matched, row_of, R)].add(1, mode="drop")
    return valid_rows, count - dels, matched


def compact_rows(valid_rows, *slot_arrays):
    """Stable push-valid-to-front within each row (after deletions), so that
    ``count`` == number of leading valid slots again. Preserves relative order
    (keeps 'sorted' flags truthful). Applies the same permutation to every
    array in slot_arrays (each (R, C, ...))."""
    order = jnp.argsort(~valid_rows, axis=1, stable=True)   # (R, C)
    out = [jnp.take_along_axis(valid_rows, order, axis=1)]
    for arr in slot_arrays:
        idx = order.reshape(order.shape + (1,) * (arr.ndim - 2))
        out.append(jnp.take_along_axis(arr, jnp.broadcast_to(
            idx, order.shape + arr.shape[2:]) if arr.ndim > 2 else order,
            axis=1))
    return tuple(out)


def compact_touched(touched, max_rows: int, valid_rows, *slot_arrays):
    """:func:`compact_rows` for the touched rows only (a delete batch of
    m entries touches at most m rows, so ``max_rows = min(m, R)`` is
    exact). Returns ``(dest, compacted)``: ``dest`` (max_rows,) row ids
    to scatter back into with ``.at[dest].set(..., mode="drop")`` (``R``
    for padding, which the scatter drops), and the compacted
    (max_rows, C, ...) gathers of ``valid_rows`` and every slot array.
    Temps are O(max_rows * C), not O(R * C)."""
    R = valid_rows.shape[0]
    rows, _ = take_k_where(touched, max_rows)
    safe = jnp.maximum(rows, 0)
    dest = jnp.where(rows >= 0, rows, R)
    return dest, compact_rows(valid_rows[safe],
                              *(a[safe] for a in slot_arrays))


def take_k_where(mask, k: int):
    """Indices of up to k True entries of mask (padded with -1), plus count.

    Deterministic (ascending index order)."""
    n = mask.shape[0]
    # sort key: False -> large, True -> own index (ascending)
    key = jnp.where(mask, jnp.arange(n, dtype=jnp.int32), jnp.int32(n))
    idx = jnp.argsort(key)[:k].astype(jnp.int32)
    good = mask[idx]
    return jnp.where(good, idx, -1), jnp.sum(mask, dtype=jnp.int32)
