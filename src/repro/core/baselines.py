"""Baseline indexes the paper compares against (Sec. 5, Fig. 3).

* ``kd``  — parallel kd-tree with object-median splits, built level-wise
  (BHL-tree style [62]); batch updates are full rebuilds, its documented
  update strategy. (The Pkd-tree's sampled-median + sieve construction is
  what P-Orth borrows; the kd baseline here isolates *query* behaviour of
  median splits.)
* ``zd``  — Zd-tree-like orth-tree built by materializing Morton codes and
  sorting them up front [16]. Structurally identical to the P-Orth tree;
  the cost difference against ``porth.build`` is exactly the paper's claim
  that the sieve avoids the encode+sort passes.
* CPAM-like total-order SPaC is ``spac.insert(..., sort_rows=True)``.

Both baselines expose the shared LeafView, so the query engine and all
query benchmarks run on them unchanged.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from . import sfc
from .leafstore import scatter_to_rows, segment_bbox
from .porth import _group_stats
from .queries import LeafView

KEY_MAX = np.uint32(0xFFFFFFFF)  # numpy: keep import device-free


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["pts", "valid", "count", "active", "bbox_lo", "bbox_hi"],
    meta_fields=["phi"])
@dataclasses.dataclass(frozen=True)
class LeafIndex:
    """Minimal static leaf-directory index (kd / zd baselines)."""
    pts: Any
    valid: Any
    count: Any
    active: Any
    bbox_lo: Any
    bbox_hi: Any
    phi: int = 32

    def view(self) -> LeafView:
        return LeafView(self.pts, self.valid, self.active, self.bbox_lo,
                        self.bbox_hi)

    @property
    def size(self):
        return jnp.sum(jnp.where(self.active, self.count, 0))


def _finalize_groups(points, ok, key, phi: int, R: int):
    """Chunk sorted groups into rows of phi (same chunking as porth)."""
    n, dim = points.shape
    gid, cnt, pos = _group_stats(jnp.where(ok, key, KEY_MAX), ok)
    rows_per = (cnt + phi - 1) // phi
    change = jnp.concatenate([jnp.ones((1,), bool), gid[1:] != gid[:-1]])
    per_group = jnp.where(change, rows_per, 0)
    incl = jnp.cumsum(per_group)
    goff = (incl - per_group)[jnp.searchsorted(gid, gid, side="left")]
    row = goff.astype(jnp.int32) + pos // phi
    slot = pos % phi
    in_new = ok & (row < R)
    C = 2 * phi
    pts_rows = scatter_to_rows(jnp.zeros((R, C, dim), points.dtype),
                               row, slot, points, in_new)
    valid_rows = scatter_to_rows(jnp.zeros((R, C), bool), row, slot,
                                 jnp.ones(n, bool), in_new)
    count = jnp.zeros(R, jnp.int32).at[
        jnp.where(in_new, row, R)].add(1, mode="drop")
    lo, hi = segment_bbox(points, jnp.where(in_new, row, R), in_new, R)
    return LeafIndex(pts=pts_rows, valid=valid_rows, count=count,
                     active=count > 0, bbox_lo=lo, bbox_hi=hi, phi=phi)


# ---------------------------------------------------------------------------
# kd-tree: object-median splits, level-synchronous construction
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("phi", "max_depth",
                                             "capacity_rows"))
def kd_build(points, mask=None, *, phi: int = 32, max_depth: int = 24,
             capacity_rows: int | None = None) -> LeafIndex:
    n, dim = points.shape
    if mask is None:
        mask = jnp.ones(n, bool)
    if capacity_rows is None:
        capacity_rows = max(4 * ((n + phi - 1) // phi), 16)
    R = capacity_rows

    key = jnp.zeros(n, jnp.uint32)   # path code: 1 bit per level
    pts, ok = points, mask
    for d in range(max_depth):
        skey = jnp.where(ok, key, KEY_MAX)
        # two stable sorts: by coord then by segment => within-segment sorted
        coord = pts[:, d % dim]
        p1 = jnp.argsort(coord, stable=True).astype(jnp.int32)
        pts, ok, key, skey = pts[p1], ok[p1], key[p1], skey[p1]
        p2 = jnp.argsort(skey, stable=True).astype(jnp.int32)
        pts, ok, key, skey = pts[p2], ok[p2], key[p2], skey[p2]
        _, cnt, pos = _group_stats(skey, ok)
        act = ok & (cnt > phi)
        bit = (pos >= (cnt + 1) // 2).astype(jnp.uint32)  # median split
        key = jnp.where(act, (key << 1) | bit, key << 1)
    skey = jnp.where(ok, key, KEY_MAX)
    perm = jnp.argsort(skey, stable=True).astype(jnp.int32)
    return _finalize_groups(pts[perm], ok[perm], skey[perm], phi, R)


def _live_flat(index: LeafIndex):
    R, C, dim = index.pts.shape
    pts = index.pts.reshape(R * C, dim)
    ok = (index.valid & index.active[:, None]).reshape(R * C)
    return pts, ok


def kd_insert(index: LeafIndex, new_pts, new_mask=None, **kw) -> LeafIndex:
    """BHL-tree semantics: batch update = full rebuild."""
    old, ok = _live_flat(index)
    if new_mask is None:
        new_mask = jnp.ones(new_pts.shape[0], bool)
    pts = jnp.concatenate([old, new_pts.astype(old.dtype)], axis=0)
    mask = jnp.concatenate([ok, new_mask])
    return kd_build(pts, mask, phi=index.phi, **kw)


def multiset_subtract_mask(live_pts, live_ok, del_pts, del_ok=None):
    """keep-mask over live_pts after removing the del_pts multiset.

    Segmented-scan formulation (no 64-bit key packing): lexsort live+del
    together, group equal coordinates, drop as many live copies per group
    as there are delete entries. Returns the keep mask aligned to live_pts.
    """
    dim = live_pts.shape[1]
    n, m = live_pts.shape[0], del_pts.shape[0]
    if del_ok is None:
        del_ok = jnp.ones(m, bool)
    allp = jnp.concatenate([live_pts, del_pts.astype(live_pts.dtype)], 0)
    is_live = jnp.concatenate([jnp.ones(n, bool), jnp.zeros(m, bool)])
    okv = jnp.concatenate([live_ok, del_ok])
    order = jnp.lexsort([allp[:, k] for k in range(dim - 1, -1, -1)])
    sp, sl, so = allp[order], is_live[order], okv[order]
    idx = jnp.arange(n + m, dtype=jnp.int32)
    newrun = jnp.concatenate([jnp.ones((1,), bool),
                              jnp.any(sp[1:] != sp[:-1], axis=-1)])
    runstart = jax.lax.cummax(jnp.where(newrun, idx, 0))
    # deletes per run, broadcast to members via segmented sum
    is_del = (~sl) & so
    cdel = jnp.cumsum(is_del.astype(jnp.int32))
    cdel_start = jnp.where(runstart > 0, cdel[jnp.maximum(runstart - 1, 0)],
                           0)
    run_id = jnp.cumsum(newrun.astype(jnp.int32)) - 1
    run_dels = jnp.zeros(n + m, jnp.int32).at[run_id].add(
        is_del.astype(jnp.int32))[run_id]
    # live rank within run (valid lives only)
    is_lv = sl & so
    clive = jnp.cumsum(is_lv.astype(jnp.int32))
    clive_start = jnp.where(runstart > 0,
                            clive[jnp.maximum(runstart - 1, 0)], 0)
    live_rank = clive - clive_start - 1  # for live entries
    keep_sorted = is_lv & (live_rank >= run_dels)
    keep = jnp.zeros(n + m, bool).at[order].set(keep_sorted)
    return keep[:n]


def kd_delete(index: LeafIndex, del_pts, del_mask=None, **kw) -> LeafIndex:
    """Full rebuild without the deleted multiset (rank-matched)."""
    old, ok = _live_flat(index)
    keep = multiset_subtract_mask(old, ok, del_pts, del_mask)
    return kd_build(old, keep, phi=index.phi, **kw)


# ---------------------------------------------------------------------------
# Zd-tree-like: explicit Morton presort, then orth structure from codes
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("phi", "bits", "coord_bits",
                                             "lam", "capacity_rows"))
def zd_build(points, mask=None, *, phi: int = 32, bits: int = 15,
             coord_bits: int = 20, lam: int = 3,
             capacity_rows: int | None = None) -> LeafIndex:
    """Materialize Morton codes, sort them, then reveal lam*D bits per round
    to derive the orth leaf cells — the extra encode pass + full-precision
    sort is exactly the overhead P-Orth avoids (paper Sec. 3, 'Issues')."""
    n, dim = points.shape
    if mask is None:
        mask = jnp.ones(n, bool)
    if capacity_rows is None:
        capacity_rows = max(min(2 * n, 8 * ((n + phi - 1) // phi)), 16)
    shift = max(0, coord_bits - bits)
    codes = sfc.morton_encode(points.astype(jnp.uint32) >> shift, bits)
    skey = jnp.where(mask, codes, KEY_MAX)
    perm = jnp.argsort(skey, stable=True).astype(jnp.int32)
    pts, ok, codes = points[perm], mask[perm], skey[perm]

    total_bits = bits * dim
    key = jnp.zeros(n, jnp.uint32)  # revealed prefix
    depth_bits = jnp.zeros(n, jnp.int32)
    rounds = (total_bits + lam * dim - 1) // (lam * dim)
    for _ in range(rounds):
        _, cnt, _ = _group_stats(jnp.where(ok, key, KEY_MAX), ok)
        act = ok & (cnt > phi) & (depth_bits < total_bits)
        nb = jnp.minimum(lam * dim, total_bits - depth_bits)
        newly = (codes >> jnp.maximum(
            total_bits - depth_bits - nb, 0).astype(jnp.uint32))
        mask_keep = (jnp.uint32(1) << nb.astype(jnp.uint32)) - 1
        key = jnp.where(act, (key << nb.astype(jnp.uint32))
                        | (newly & mask_keep), key)
        depth_bits = jnp.where(act, depth_bits + nb, depth_bits)
        # already sorted by full code => groups remain contiguous, no re-sort
    # normalize keys to a common shift for grouping
    fkey = jnp.where(ok, key << (total_bits - depth_bits).astype(jnp.uint32),
                     KEY_MAX)
    # groups share prefix but may differ in depth — disjoint cells, distinct
    # lo-corners, and the array is already in code order => contiguous.
    return _finalize_groups(pts, ok, fkey, phi, capacity_rows)


def zd_insert(index: LeafIndex, new_pts, new_mask=None, **kw) -> LeafIndex:
    """Merge-rebuild update (labeled as such in benchmarks — the original
    Zd update algorithm is not reproduced here; this baseline isolates the
    construction-cost claim)."""
    old, ok = _live_flat(index)
    if new_mask is None:
        new_mask = jnp.ones(new_pts.shape[0], bool)
    pts = jnp.concatenate([old, new_pts.astype(old.dtype)], axis=0)
    mask = jnp.concatenate([ok, new_mask])
    return zd_build(pts, mask, phi=index.phi, **kw)


def zd_delete(index: LeafIndex, del_pts, del_mask=None, **kw) -> LeafIndex:
    """Merge-rebuild without the deleted multiset (rank-matched)."""
    old, ok = _live_flat(index)
    keep = multiset_subtract_mask(old, ok, del_pts, del_mask)
    return zd_build(old, keep, phi=index.phi, **kw)
