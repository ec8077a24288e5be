"""P-Orth tree: the paper's SFC-free parallel orth-tree (Sec. 3), TPU-native.

The paper's construction sieves points through a λ-level tree skeleton per
round — conceptually MSD integer sort of Morton codes *without materializing
codes*. The TPU adaptation keeps exactly that structure:

  * per-point sieve state: current cell bounds (lo, hi), accumulated prefix
    key, depth — the bucket of a point is computed by λ·D **coordinate
    comparisons against cell midpoints** (never from an encoded code, so any
    coordinate dtype works: float32 included — the paper's 'Applicability'
    win, Sec. 3);
  * one round = compute buckets for all active points, extend keys, stable
    sort by key (all levels of the tree advance simultaneously — the
    segmented sieve);
  * groups (= cells) with ≤ φ points stop splitting and become leaf rows.

The accumulated prefix keys double as the directory sort keys (they *are*
Morton codes, but they fall out of the comparisons — nothing is encoded,
stored per point, or binary-searched during construction, faithful to the
paper's 'conceptually equivalent to integer sorting SFC codes' claim).

Orth-trees need no rebalancing (paper Sec. 3.2) and are history-independent
modulo leaf wrapping: batch insert routes points to existing leaf cells
(append — orth leaves are naturally unsorted) or creates leaves for empty
regions at the shallowest empty depth; overflowing cells re-run the sieve
seeded at the cell. Deletions remove points and merge fully-leaf sibling
groups whose total fits a leaf (one level per batch, amortized).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .leafstore import (chunk_rows_from_sorted, compact_touched,
                        ranked_delete, row_bbox_from_slots, scatter_to_rows,
                        segment_bbox, take_k_where)
from .queries import LeafView

KEY_MAX = np.uint32(0xFFFFFFFF)  # numpy: keep import device-free


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["pts", "valid", "count", "active", "bbox_lo", "bbox_hi",
                 "cell_lo", "cell_hi", "cell_key", "cell_depth", "order",
                 "num_rows", "overflowed", "root_lo", "root_hi"],
    meta_fields=["phi", "lam", "rounds"])
@dataclasses.dataclass(frozen=True)
class POrthTree:
    pts: Any         # (R, C, D)
    valid: Any       # (R, C) bool
    count: Any       # (R,) int32
    active: Any      # (R,) bool
    bbox_lo: Any     # (R, D) tight point bbox
    bbox_hi: Any     # (R, D)
    cell_lo: Any     # (R, D) orth cell region
    cell_hi: Any     # (R, D)
    cell_key: Any    # (R,) uint32 — lo-corner prefix key at full shift
    cell_depth: Any  # (R,) int32 — levels of splitting applied
    order: Any       # (R,) int32 rows sorted by cell_key
    num_rows: Any    # () int32
    overflowed: Any  # () bool
    root_lo: Any     # (D,)
    root_hi: Any     # (D,)
    phi: int = 32
    lam: int = 3     # paper: 3 levels/round in 2D, 2 in 3D
    rounds: int = 5  # total depth = lam * rounds; lam*rounds*D <= 32

    @property
    def capacity_rows(self) -> int:
        return self.pts.shape[0]

    @property
    def row_capacity(self) -> int:
        return self.pts.shape[1]

    @property
    def dim(self) -> int:
        return self.pts.shape[2]

    @property
    def total_depth(self) -> int:
        return self.lam * self.rounds

    @property
    def key_bits(self) -> int:
        return self.total_depth * self.dim

    def view(self) -> LeafView:
        return LeafView(self.pts, self.valid, self.active, self.bbox_lo,
                        self.bbox_hi)

    @property
    def size(self):
        return jnp.sum(jnp.where(self.active, self.count, 0))


# ---------------------------------------------------------------------------
# sieve machinery
# ---------------------------------------------------------------------------

def _midpoint(lo, hi):
    if jnp.issubdtype(lo.dtype, jnp.floating):
        return lo + (hi - lo) * 0.5
    return lo + (hi - lo) // 2


def _split_lambda_levels(pts, lo, hi, lam: int, dim: int):
    """Compute the λ-level bucket of each point inside its cell by midpoint
    comparisons (the skeleton descent). Returns (bucket (N,) uint32, lo', hi')."""
    bucket = jnp.zeros(pts.shape[0], jnp.uint32)
    for _ in range(lam):
        mid = _midpoint(lo, hi)
        gt = pts >= mid                                   # (N, D)
        b = jnp.zeros(pts.shape[0], jnp.uint32)
        for d in range(dim):
            b = b | (gt[:, d].astype(jnp.uint32) << (dim - 1 - d))
        bucket = (bucket << dim) | b
        lo = jnp.where(gt, mid, lo)
        hi = jnp.where(gt, hi, mid)
    return bucket, lo, hi


def _group_stats(sorted_key, ok):
    """Per-point group stats over contiguous equal-key runs of a sorted array.

    Returns (gid, cnt, pos): group index, number of *valid* points in the
    group, position of the point within its group (counting valid and invalid
    alike — invalids sort to the tail as their own run)."""
    n = sorted_key.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    change = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_key[1:] != sorted_key[:-1]])
    gid = jnp.cumsum(change.astype(jnp.int32)) - 1
    cnt_per_gid = jnp.zeros(n, jnp.int32).at[gid].add(ok.astype(jnp.int32))
    cnt = cnt_per_gid[gid]
    gstart = jax.lax.cummax(jnp.where(change, idx, 0))
    return gid, cnt, idx - gstart


def _sieve_rounds(pts, ok, lo, hi, key, depth, phi: int, lam: int,
                  rounds: int, total_depth: int, key_bits: int):
    """Run up to ``rounds`` sieve rounds. Points whose group is ≤ φ (or whose
    depth is exhausted) stop. Returns the final sorted per-point state."""
    dim = pts.shape[1]
    n = pts.shape[0]

    def sort_all(sort_key, *arrays):
        perm = jnp.argsort(sort_key, stable=True).astype(jnp.int32)
        return tuple(a[perm] for a in arrays)

    # initial sort so groups (seeded cells) are contiguous
    skey = jnp.where(ok, key, KEY_MAX)
    pts, ok, lo, hi, key, depth, skey = sort_all(
        skey, pts, ok, lo, hi, key, depth, skey)

    for _ in range(rounds):
        _, cnt, _ = _group_stats(skey, ok)
        act = ok & (cnt > phi) & (depth + lam <= total_depth)
        bucket, nlo, nhi = _split_lambda_levels(pts, lo, hi, lam, dim)
        shift = jnp.maximum(key_bits - (depth + lam) * dim, 0).astype(
            jnp.uint32)
        key = jnp.where(act, key | (bucket << shift), key)
        lo = jnp.where(act[:, None], nlo, lo)
        hi = jnp.where(act[:, None], nhi, hi)
        depth = jnp.where(act, depth + lam, depth)
        skey = jnp.where(ok, key, KEY_MAX)
        pts, ok, lo, hi, key, depth, skey = sort_all(
            skey, pts, ok, lo, hi, key, depth, skey)
    return pts, ok, lo, hi, key, depth


def _finalize_rows(tree_arrays, pts, ok, lo, hi, key, depth, phi: int,
                   freelist_ids):
    """Chunk sorted sieve output into leaf rows of φ allocated from
    ``freelist_ids`` (padded with -1). Returns updated row arrays + can_alloc.

    tree_arrays: dict with pts/valid/count/active/bbox_lo/bbox_hi/cell_lo/
    cell_hi/cell_key/cell_depth (each (R, ...))."""
    R, C, dim = tree_arrays["pts"].shape
    n = pts.shape[0]
    NR = freelist_ids.shape[0]

    gid, cnt, pos = _group_stats(jnp.where(ok, key, KEY_MAX), ok)
    rows_per_gid = (cnt + phi - 1) // phi  # per point; constant within group
    # exclusive cumsum of rows_per_group over groups, gathered per point
    change = jnp.concatenate([jnp.ones((1,), bool), gid[1:] != gid[:-1]])
    per_group = jnp.where(change, rows_per_gid, 0)
    offset_incl = jnp.cumsum(per_group)
    group_offset = (offset_incl - per_group)[
        jnp.searchsorted(gid, gid, side="left")]
    local = group_offset.astype(jnp.int32) + pos // phi
    slot = pos % phi
    in_new = ok & (local < NR)
    dest = jnp.where(in_new, jnp.maximum(freelist_ids, 0)[
        jnp.clip(local, 0, NR - 1)], R)
    rows_needed = jnp.max(jnp.where(ok, local + 1, 0), initial=0)
    can_alloc = rows_needed <= jnp.sum(freelist_ids >= 0)
    dest = jnp.where(can_alloc, dest, R)

    a = dict(tree_arrays)
    a["pts"] = scatter_to_rows(a["pts"], dest, slot, pts, in_new)
    a["valid"] = scatter_to_rows(a["valid"], dest, slot,
                                 jnp.ones(n, bool), in_new)
    ncount = jnp.zeros(R, jnp.int32).at[dest].add(1, mode="drop")
    newly = ncount > 0
    a["count"] = jnp.where(newly, ncount, a["count"])
    a["active"] = a["active"] | newly
    nlo, nhi = segment_bbox(pts, jnp.where(in_new, dest, R), in_new, R)
    a["bbox_lo"] = jnp.where(newly[:, None], nlo, a["bbox_lo"])
    a["bbox_hi"] = jnp.where(newly[:, None], nhi, a["bbox_hi"])
    # row leader (first point of each row) carries the cell metadata
    leader = in_new & (slot == 0)
    ldest = jnp.where(leader, dest, R)
    a["cell_lo"] = a["cell_lo"].at[ldest].set(lo, mode="drop")
    a["cell_hi"] = a["cell_hi"].at[ldest].set(hi, mode="drop")
    a["cell_key"] = a["cell_key"].at[ldest].set(key, mode="drop")
    a["cell_depth"] = a["cell_depth"].at[ldest].set(depth, mode="drop")
    return a, can_alloc


def _arrays(tree: POrthTree):
    return dict(pts=tree.pts, valid=tree.valid, count=tree.count,
                active=tree.active, bbox_lo=tree.bbox_lo,
                bbox_hi=tree.bbox_hi, cell_lo=tree.cell_lo,
                cell_hi=tree.cell_hi, cell_key=tree.cell_key,
                cell_depth=tree.cell_depth)


def _rebuild_order(active, cell_key):
    key = jnp.where(active, cell_key, KEY_MAX)
    return jnp.argsort(key).astype(jnp.int32), jnp.sum(
        active, dtype=jnp.int32)


# ---------------------------------------------------------------------------
# construction (paper Alg. 1)
# ---------------------------------------------------------------------------

def _empty_arrays(R: int, C: int, dim: int, dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        big = jnp.asarray(jnp.finfo(dtype).max, dtype)
    else:
        big = jnp.asarray(jnp.iinfo(dtype).max, dtype)
    return dict(
        pts=jnp.zeros((R, C, dim), dtype),
        valid=jnp.zeros((R, C), bool),
        count=jnp.zeros(R, jnp.int32),
        active=jnp.zeros(R, bool),
        bbox_lo=jnp.full((R, dim), big, dtype),
        bbox_hi=jnp.full((R, dim), -big, dtype),
        cell_lo=jnp.zeros((R, dim), dtype),
        cell_hi=jnp.zeros((R, dim), dtype),
        cell_key=jnp.full(R, KEY_MAX, jnp.uint32),
        cell_depth=jnp.zeros(R, jnp.int32),
    )


def build_impl(points, root_lo, root_hi, mask=None, *, phi: int = 32,
               lam: int = 3, rounds: int = 5,
               capacity_rows: int | None = None) -> POrthTree:
    """BuildPOrthTree via the segmented sieve.

    Unjitted spelling — the only legal call inside a shard_map region
    (jax 0.4.x miscompiles a nested jit there; see ROADMAP "Contracts",
    rule jit-in-shard-map). Single-device callers use :data:`build`.
    """
    n, dim = points.shape
    assert lam * rounds * dim <= 31, "key exceeds uint32 (enable x64 path)"
    if mask is None:
        mask = jnp.ones(n, bool)
    if capacity_rows is None:
        # orth cells may hold far fewer than phi points (4/8-ary splits can
        # overshoot), so rows scale with n, not n/phi
        capacity_rows = max(min(2 * n, 8 * ((n + phi - 1) // phi)), 16)
    R, C = capacity_rows, 2 * phi
    total_depth, key_bits = lam * rounds, lam * rounds * dim

    lo = jnp.broadcast_to(root_lo.astype(points.dtype), (n, dim))
    hi = jnp.broadcast_to(root_hi.astype(points.dtype), (n, dim))
    key = jnp.zeros(n, jnp.uint32)
    depth = jnp.zeros(n, jnp.int32)
    s = _sieve_rounds(points, mask, lo, hi, key, depth, phi, lam, rounds,
                      total_depth, key_bits)
    arrays = _empty_arrays(R, C, dim, points.dtype)
    freelist = jnp.arange(R, dtype=jnp.int32)
    arrays, can_alloc = _finalize_rows(arrays, *s, phi, freelist)
    order, num_rows = _rebuild_order(arrays["active"], arrays["cell_key"])
    return POrthTree(**arrays, order=order, num_rows=num_rows,
                     overflowed=~can_alloc,
                     root_lo=root_lo.astype(points.dtype),
                     root_hi=root_hi.astype(points.dtype),
                     phi=phi, lam=lam, rounds=rounds)


build = jax.jit(build_impl, static_argnames=("phi", "lam", "rounds",
                                             "capacity_rows"))


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

def point_keys(pts, root_lo, root_hi, *, lam: int, rounds: int):
    """Full-depth prefix key of each point via midpoint comparisons.

    These keys ARE Morton codes over the orth skeleton — they fall out
    of the sieve's comparisons without encoding, so they work for any
    coordinate dtype (float included). Standalone spelling: the
    distributed router calls it before a tree exists on the shard."""
    n, dim = pts.shape
    lo = jnp.broadcast_to(root_lo, (n, dim)).astype(pts.dtype)
    hi = jnp.broadcast_to(root_hi, (n, dim)).astype(pts.dtype)
    key = jnp.zeros(n, jnp.uint32)
    for _ in range(rounds):
        bucket, lo, hi = _split_lambda_levels(pts, lo, hi, lam, dim)
        key = (key << (lam * dim)) | bucket
    return key


def _point_keys(tree: POrthTree, pts):
    return point_keys(pts, tree.root_lo, tree.root_hi, lam=tree.lam,
                      rounds=tree.rounds)


def _route(tree: POrthTree, pkeys, ok):
    """Directory lookup + containment test.

    Returns (row, contained): row id whose cell-key range the point key lands
    in; contained=False when that cell does not actually cover the point
    (the point falls in an empty region)."""
    R = tree.capacity_rows
    dmc = jnp.where(tree.active, tree.cell_key, KEY_MAX)[tree.order]
    j = jnp.clip(jnp.searchsorted(dmc, pkeys, side="right").astype(jnp.int32)
                 - 1, 0, R - 1)
    row = tree.order[j]
    rem = (tree.key_bits
           - tree.cell_depth[row] * tree.dim).astype(jnp.uint32)
    contained = ((pkeys >> rem) == (tree.cell_key[row] >> rem)) \
        & tree.active[row] & ok
    return jnp.where(ok, row, R), contained


def _empty_cell_seed(tree: POrthTree, pts, pkeys, missed):
    """For points in empty regions: shallowest depth d* whose cell contains no
    existing row; returns (key, depth, lo, hi) of that cell per point."""
    n, dim = pts.shape
    sorted_keys = jnp.where(tree.active, tree.cell_key, KEY_MAX)[tree.order]
    num = tree.num_rows
    lo = jnp.broadcast_to(tree.root_lo, (n, dim)).astype(pts.dtype)
    hi = jnp.broadcast_to(tree.root_hi, (n, dim)).astype(pts.dtype)
    best_depth = jnp.full(n, tree.total_depth, jnp.int32)
    best_key = pkeys
    best_lo, best_hi = lo, hi
    found = jnp.zeros(n, bool)
    cur_lo, cur_hi = lo, hi
    for d in range(tree.total_depth + 1):
        rem = jnp.uint32(tree.key_bits - d * dim)
        prefix = (pkeys >> rem) << rem if d > 0 else jnp.zeros_like(pkeys)
        nxt = prefix + (jnp.uint32(1) << rem) if d > 0 else KEY_MAX
        lo_i = jnp.searchsorted(sorted_keys, prefix, side="left")
        hi_i = jnp.searchsorted(sorted_keys,
                                jnp.minimum(nxt, KEY_MAX), side="left")
        hi_i = jnp.where(d == 0, num, hi_i)
        empty = (hi_i - lo_i) == 0 if d > 0 else (num == 0)
        take = empty & ~found & missed
        best_depth = jnp.where(take, d, best_depth)
        best_key = jnp.where(take, prefix, best_key)
        best_lo = jnp.where(take[:, None], cur_lo, best_lo)
        best_hi = jnp.where(take[:, None], cur_hi, best_hi)
        found = found | take
        if d < tree.total_depth:
            # descend one level to track cell bounds
            mid = _midpoint(cur_lo, cur_hi)
            gt = pts >= mid
            cur_lo = jnp.where(gt, mid, cur_lo)
            cur_hi = jnp.where(gt, cur_hi, mid)
    return best_key, best_depth, best_lo, best_hi


# ---------------------------------------------------------------------------
# batch insertion (paper Alg. 2)
# ---------------------------------------------------------------------------

def insert_impl(tree: POrthTree, new_pts, new_mask=None, *,
                max_overflow_rows: int = 64) -> POrthTree:
    """Batch insertion (all-or-nothing; sticky ``overflowed`` on
    capacity shortfall).

    Unjitted spelling for shard_map regions; use :data:`insert` outside.
    """
    m, dim = new_pts.shape
    new_pts = new_pts.astype(tree.pts.dtype)
    if new_mask is None:
        new_mask = jnp.ones(m, bool)
    R, C, phi = tree.capacity_rows, tree.row_capacity, tree.phi

    pkeys = _point_keys(tree, new_pts)
    skey = jnp.where(new_mask, pkeys, KEY_MAX)
    perm = jnp.argsort(skey, stable=True).astype(jnp.int32)
    s_keys, s_pts, s_ok = skey[perm], new_pts[perm], new_mask[perm]

    row_of, contained = _route(tree, s_keys, s_ok)
    missed = s_ok & ~contained
    row_app = jnp.where(contained, row_of, R)
    adds = jnp.zeros(R, jnp.int32).at[row_app].add(1, mode="drop")
    over = tree.count + adds > C
    goes_over = over[jnp.clip(row_app, 0, R - 1)] & contained
    fits = contained & ~goes_over

    # phase 1: append into leaf cells (orth leaves are naturally unsorted)
    from .leafstore import append_unsorted
    pts_rows, valid_rows, count, _ = append_unsorted(
        tree.pts, tree.valid, tree.count, row_app, s_pts, fits)
    seg_lo, seg_hi = segment_bbox(s_pts, row_app, fits, R)
    bbox_lo = jnp.minimum(tree.bbox_lo, seg_lo)
    bbox_hi = jnp.maximum(tree.bbox_hi, seg_hi)

    # phase 2: rebuild buffer = overflowing cells' contents + their incoming
    # + points in empty regions, sieved from their seed cells.
    MOR = max_overflow_rows
    orow_ids, n_over = take_k_where(over & tree.active, MOR)
    ovalid = orow_ids >= 0
    safe = jnp.maximum(orow_ids, 0)
    old_pts = tree.pts[safe].reshape(MOR * C, dim)
    old_ok = (tree.valid[safe] & ovalid[:, None]).reshape(MOR * C)
    old_lo = jnp.repeat(tree.cell_lo[safe], C, axis=0)
    old_hi = jnp.repeat(tree.cell_hi[safe], C, axis=0)
    old_key = jnp.repeat(tree.cell_key[safe], C)
    old_depth = jnp.repeat(tree.cell_depth[safe], C)

    seed_key, seed_depth, seed_lo, seed_hi = _empty_cell_seed(
        tree, s_pts, s_keys, missed)
    # incoming points for overflowing rows seed at that row's cell
    inc_over = goes_over
    rcl = tree.cell_lo[jnp.clip(row_app, 0, R - 1)]
    rch = tree.cell_hi[jnp.clip(row_app, 0, R - 1)]
    rck = tree.cell_key[jnp.clip(row_app, 0, R - 1)]
    rcd = tree.cell_depth[jnp.clip(row_app, 0, R - 1)]
    root_lo = jnp.broadcast_to(tree.root_lo, (m, dim)).astype(s_pts.dtype)
    root_hi = jnp.broadcast_to(tree.root_hi, (m, dim)).astype(s_pts.dtype)
    new_in = missed | goes_over
    b2_lo = jnp.where(inc_over[:, None], rcl,
                      jnp.where(missed[:, None], seed_lo, root_lo))
    b2_hi = jnp.where(inc_over[:, None], rch,
                      jnp.where(missed[:, None], seed_hi, root_hi))
    b2_key = jnp.where(inc_over, rck, jnp.where(missed, seed_key, 0))
    b2_depth = jnp.where(inc_over, rcd, jnp.where(missed, seed_depth, 0))

    buf_pts = jnp.concatenate([old_pts, s_pts], axis=0)
    buf_ok = jnp.concatenate([old_ok, new_in])
    buf_lo = jnp.concatenate([old_lo, b2_lo], axis=0)
    buf_hi = jnp.concatenate([old_hi, b2_hi], axis=0)
    buf_key = jnp.concatenate([old_key, b2_key])
    buf_depth = jnp.concatenate([old_depth, b2_depth])

    s = _sieve_rounds(buf_pts, buf_ok, buf_lo, buf_hi, buf_key, buf_depth,
                      phi, tree.lam, tree.rounds, tree.total_depth,
                      tree.key_bits)

    dropped = over & tree.active & ovalid_mask(orow_ids, R)
    arrays = dict(pts=pts_rows, valid=valid_rows, count=count,
                  active=tree.active | (adds > 0),
                  bbox_lo=bbox_lo, bbox_hi=bbox_hi,
                  cell_lo=tree.cell_lo, cell_hi=tree.cell_hi,
                  cell_key=tree.cell_key, cell_depth=tree.cell_depth)
    # reset rows being rebuilt before re-filling
    arrays = _reset_rows(arrays, dropped)
    NR = MOR * (C // phi) + m + 2
    freelist, _ = take_k_where(~arrays["active"], NR)
    arrays, can_alloc = _finalize_rows(arrays, *s, phi, freelist)
    order, num_rows = _rebuild_order(arrays["active"], arrays["cell_key"])
    ok_all = can_alloc & (n_over <= MOR)
    new_tree = dataclasses.replace(
        tree, **arrays, order=order, num_rows=num_rows,
        overflowed=tree.overflowed)
    # all-or-nothing: on capacity shortfall return the tree unchanged with the
    # overflowed flag set (caller compacts to a larger capacity and retries)
    failed = dataclasses.replace(tree, overflowed=jnp.array(True))
    return jax.tree.map(lambda a, b: jnp.where(ok_all, a, b),
                        new_tree, failed)


insert = jax.jit(insert_impl, static_argnames=("max_overflow_rows",))


def ovalid_mask(orow_ids, R: int):
    m = jnp.zeros(R + 1, bool).at[
        jnp.where(orow_ids >= 0, orow_ids, R)].set(True)
    return m[:R]


def _reset_rows(arrays, mask):
    a = dict(arrays)
    dt = a["pts"].dtype
    big = (jnp.asarray(jnp.finfo(dt).max, dt)
           if jnp.issubdtype(dt, jnp.floating)
           else jnp.asarray(jnp.iinfo(dt).max, dt))
    a["valid"] = jnp.where(mask[:, None], False, a["valid"])
    a["count"] = jnp.where(mask, 0, a["count"])
    a["active"] = a["active"] & ~mask
    a["bbox_lo"] = jnp.where(mask[:, None], big, a["bbox_lo"])
    a["bbox_hi"] = jnp.where(mask[:, None], -big, a["bbox_hi"])
    a["cell_key"] = jnp.where(mask, KEY_MAX, a["cell_key"])
    a["cell_depth"] = jnp.where(mask, 0, a["cell_depth"])
    return a


# ---------------------------------------------------------------------------
# batch deletion
# ---------------------------------------------------------------------------

def delete_impl(tree: POrthTree, del_pts, del_mask=None) -> POrthTree:
    """Batch deletion + one merge pass.

    Unjitted spelling for shard_map regions — this matters doubly here:
    the while_loop below under a nested jit is the documented jax 0.4.x
    shard_map miscompile, and the trailing merge pass must also run as
    its ``_impl`` (a jitted ``merge_pass`` call nested inside the shard
    region would reintroduce exactly that bug *without* tripping the
    lexical jit-in-shard-map lint). Use :data:`delete` outside."""
    m, dim = del_pts.shape
    del_pts = del_pts.astype(tree.pts.dtype)
    if del_mask is None:
        del_mask = jnp.ones(m, bool)
    R, C = tree.capacity_rows, tree.row_capacity

    pkeys = _point_keys(tree, del_pts)
    skey = jnp.where(del_mask, pkeys, KEY_MAX)
    perm = jnp.argsort(skey, stable=True).astype(jnp.int32)
    s_keys, s_pts, s_ok = skey[perm], del_pts[perm], del_mask[perm]
    row_of, contained = _route(tree, s_keys, s_ok)

    # banded deletion: a cell saturated by > C duplicates spans several
    # rows with an IDENTICAL cell_key (orth cells cannot split equal
    # points); walk every row of the target cell's band (usually 1).
    ck_t = tree.cell_key[jnp.clip(row_of, 0, R - 1)]
    dmc = jnp.where(tree.active, tree.cell_key, KEY_MAX)[tree.order]
    iL = jnp.searchsorted(dmc, ck_t, side="left").astype(jnp.int32)
    iR = jnp.searchsorted(dmc, ck_t, side="right").astype(jnp.int32)

    def cond(state):
        o, _, _, remaining, _ = state
        return jnp.any(remaining & (iL + o <= iR - 1))

    def body(state):
        o, valid_rows, count, remaining, touched = state
        pos = jnp.clip(jnp.minimum(iL + o, iR - 1), 0, R - 1)
        rows = jnp.where(remaining, tree.order[pos], R - 1)
        valid_rows, count, matched = ranked_delete(
            tree.pts, valid_rows, count, rows, s_pts, remaining, window=C)
        touched = touched.at[jnp.where(matched, rows, R)].set(
            True, mode="drop")
        return (o + 1, valid_rows, count, remaining & ~matched, touched)

    _, valid_rows, count, _, touched = jax.lax.while_loop(
        cond, body, (jnp.int32(0), tree.valid, tree.count, contained,
                     jnp.zeros(R, bool)))
    # compact and refresh only the touched rows (at most one per entry)
    active = tree.active & (count > 0)
    dest, (cvalid, cpts) = compact_touched(touched, min(m, R), valid_rows,
                                           tree.pts)
    lo, hi = row_bbox_from_slots(
        cpts, cvalid & active[jnp.minimum(dest, R - 1)][:, None])
    valid_rows = valid_rows.at[dest].set(cvalid, mode="drop")
    pts_rows = tree.pts.at[dest].set(cpts, mode="drop")
    bbox_lo = tree.bbox_lo.at[dest].set(lo, mode="drop")
    bbox_hi = tree.bbox_hi.at[dest].set(hi, mode="drop")
    arrays = dict(pts=pts_rows, valid=valid_rows, count=count, active=active,
                  bbox_lo=bbox_lo, bbox_hi=bbox_hi, cell_lo=tree.cell_lo,
                  cell_hi=tree.cell_hi,
                  cell_key=jnp.where(active, tree.cell_key, KEY_MAX),
                  cell_depth=jnp.where(active, tree.cell_depth, 0))
    order, num_rows = _rebuild_order(arrays["active"], arrays["cell_key"])
    out = dataclasses.replace(tree, **arrays, order=order, num_rows=num_rows)
    return merge_pass_impl(out)


delete = jax.jit(delete_impl)


def merge_pass_impl(tree: POrthTree) -> POrthTree:
    """One level of the paper's post-deletion flattening: sibling groups that
    are all leaves and whose total fits a leaf merge into their parent cell.

    Unjitted spelling (called from ``delete_impl``, which must stay
    jit-free end to end for shard_map); use :data:`merge_pass` outside."""
    R, C, dim = tree.pts.shape
    rem = jnp.clip(tree.key_bits - (tree.cell_depth - 1) * tree.dim,
                   0, 31).astype(jnp.uint32)
    parent_key = jnp.where(tree.cell_depth > 0,
                           (tree.cell_key >> rem) << rem, KEY_MAX)
    parent_key = jnp.where(tree.active, parent_key, KEY_MAX)
    # group rows by (parent_key, depth) via sort
    okey = parent_key
    order = jnp.argsort(okey).astype(jnp.int32)
    skey = okey[order]
    sdepth = tree.cell_depth[order]
    scount = jnp.where(tree.active, tree.count, 0)[order]
    same = jnp.concatenate([jnp.ones((1,), bool),
                            (skey[1:] != skey[:-1])
                            | (sdepth[1:] != sdepth[:-1])])
    gid = jnp.cumsum(same.astype(jnp.int32)) - 1
    gcount = jnp.zeros(R, jnp.int32).at[gid].add(scount)
    gsize = jnp.zeros(R, jnp.int32).at[gid].add(
        tree.active[order].astype(jnp.int32))
    # rows inside the parent's key range (any depth) — must equal group size
    sorted_keys = jnp.where(tree.active, tree.cell_key, KEY_MAX)[tree.order]
    rem_s = jnp.clip(tree.key_bits - (sdepth - 1) * tree.dim,
                     0, 31).astype(jnp.uint32)
    nxt = skey + (jnp.uint32(1) << rem_s)
    lo_i = jnp.searchsorted(sorted_keys, skey, side="left")
    hi_i = jnp.searchsorted(sorted_keys, nxt, side="left")
    hi_i = jnp.where(nxt < skey, tree.num_rows, hi_i)  # wrap => till end
    in_range = (hi_i - lo_i).astype(jnp.int32)
    mergeable = ((gcount[gid] <= tree.phi) & (gsize[gid] > 1)
                 & (in_range == gsize[gid]) & (skey != KEY_MAX)
                 & (sdepth > 0))
    merge_row = jnp.zeros(R, bool).at[
        jnp.where(mergeable, order, R)].set(True, mode="drop")

    # buffer: all points of merging rows, seeded at their *parent* cell.
    # parents with <= phi points stop immediately in finalize (single row).
    MOR = min(64, R)
    mrow_ids, n_m = take_k_where(merge_row, MOR)
    mvalid = mrow_ids >= 0
    safe = jnp.maximum(mrow_ids, 0)
    b_pts = tree.pts[safe].reshape(MOR * C, dim)
    b_ok = (tree.valid[safe] & mvalid[:, None]).reshape(MOR * C)
    # parent cell bounds: halve upward is not tracked; recompute by descent
    pk = jnp.repeat(parent_key[safe], C)
    pd = jnp.repeat(tree.cell_depth[safe] - 1, C)
    p_lo, p_hi = _cell_bounds_at_depth(tree, b_pts, pd)
    proceed = (n_m <= MOR) & (n_m > 0)
    b_ok = b_ok & proceed

    arrays = _reset_rows(_arrays(tree), merge_row & proceed)
    freelist, _ = take_k_where(~arrays["active"], MOR)
    arrays, can_alloc = _finalize_rows(
        arrays, b_pts, b_ok, p_lo, p_hi, pk, pd, tree.phi, freelist)
    order2, num_rows = _rebuild_order(arrays["active"], arrays["cell_key"])
    new_tree = dataclasses.replace(tree, **arrays, order=order2,
                                   num_rows=num_rows)
    ok_all = can_alloc | ~proceed
    return jax.tree.map(lambda a, b: jnp.where(ok_all, a, b), new_tree, tree)


merge_pass = jax.jit(merge_pass_impl)


def _cell_bounds_at_depth(tree: POrthTree, pts, target_depth):
    """Cell bounds containing each point at the given per-point depth."""
    n, dim = pts.shape
    lo = jnp.broadcast_to(tree.root_lo, (n, dim)).astype(pts.dtype)
    hi = jnp.broadcast_to(tree.root_hi, (n, dim)).astype(pts.dtype)
    out_lo, out_hi = lo, hi
    for d in range(tree.total_depth):
        take = target_depth == d
        out_lo = jnp.where(take[:, None], lo, out_lo)
        out_hi = jnp.where(take[:, None], hi, out_hi)
        mid = _midpoint(lo, hi)
        gt = pts >= mid
        lo = jnp.where(gt, mid, lo)
        hi = jnp.where(gt, hi, mid)
    take = target_depth >= tree.total_depth
    out_lo = jnp.where(take[:, None], lo, out_lo)
    out_hi = jnp.where(take[:, None], hi, out_hi)
    return out_lo, out_hi


def grow(tree: POrthTree, capacity_rows: int) -> POrthTree:
    """Pad the row arrays to a larger capacity (outside jit; the production
    check-and-grow pattern between jit steps)."""
    R = tree.capacity_rows
    if capacity_rows <= R:
        return tree
    extra = capacity_rows - R

    def pad(a, fill):
        pw = [(0, extra)] + [(0, 0)] * (a.ndim - 1)
        return jnp.pad(a, pw, constant_values=fill)

    dt = tree.pts.dtype
    big = (jnp.finfo(dt).max if jnp.issubdtype(dt, jnp.floating)
           else jnp.iinfo(dt).max)
    arrays = dict(
        pts=pad(tree.pts, 0), valid=pad(tree.valid, False),
        count=pad(tree.count, 0), active=pad(tree.active, False),
        bbox_lo=pad(tree.bbox_lo, big), bbox_hi=pad(tree.bbox_hi, -big),
        cell_lo=pad(tree.cell_lo, 0), cell_hi=pad(tree.cell_hi, 0),
        cell_key=pad(tree.cell_key, KEY_MAX), cell_depth=pad(
            tree.cell_depth, 0))
    order, num_rows = _rebuild_order(arrays["active"], arrays["cell_key"])
    return dataclasses.replace(tree, **arrays, order=order,
                               num_rows=num_rows)


def free_rows(tree: POrthTree) -> int:
    return int(jnp.sum(~tree.active))


def extract_points(tree: POrthTree):
    R, C, dim = tree.pts.shape
    ok = (tree.valid & tree.active[:, None]).reshape(R * C)
    return tree.pts.reshape(R * C, dim), ok


def compact(tree: POrthTree, capacity_rows: int | None = None) -> POrthTree:
    pts, ok = extract_points(tree)
    return build(pts, tree.root_lo, tree.root_hi, ok, phi=tree.phi,
                 lam=tree.lam, rounds=tree.rounds,
                 capacity_rows=capacity_rows or tree.capacity_rows)
