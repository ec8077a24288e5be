"""SPaC-tree: the paper's parallel R-tree family (Sec. 4), TPU-native form.

Structure-of-arrays representation:
  * points live in rows of ``(R, C=2*phi)`` arrays (blocked leaves),
  * a *directory* (rows sorted by ``min_code``) plays the role of the
    join-balanced search tree: routing a point = one ``searchsorted``,
  * per-row bounding boxes give exact query pruning (queries.py engine).

Paper mechanisms kept intact:
  * HybridSort (Alg. 3): SFC codes are computed fused with the sort pass —
    here ``encode + argsort(codes)`` inside one jit region (XLA fuses the
    encode into the sort's key computation); only ⟨code,id⟩ pairs move
    through the sort, points are gathered once at the end.
  * Partial-order relaxation (Alg. 4): batch inserts append *unsorted* into
    leaf slack slots (`append_unsorted`); a leaf's points are only sorted
    when the leaf overflows and must be split (`Expose`, line 34/43).
  * Leaf-wrapping invariant: rows hold between 1 and C=2*phi points; an
    overflowing leaf's contents (old + incoming) are sorted and re-chunked
    into fresh rows of ``phi`` (fill factor 1/2), allocated from a freelist.

Deviation (documented in DESIGN.md §2): rebalancing is a directory argsort
(O(R log R) on a tiny int array) instead of pointer rotations; per-batch point
data movement remains O(m · phi).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from . import sfc
from .leafstore import (append_unsorted, chunk_rows_from_sorted,
                        compact_touched, group_occurrence, ranked_delete,
                        row_bbox_from_slots, scatter_to_rows, segment_bbox,
                        take_k_where)
from .queries import LeafView

CODE_MAX = np.uint32(0xFFFFFFFF)  # numpy: keep import device-free


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["pts", "codes", "valid", "count", "active", "bbox_lo",
                 "bbox_hi", "min_code", "unsorted", "order", "num_rows",
                 "overflowed"],
    meta_fields=["phi", "curve", "bits", "coord_bits"])
@dataclasses.dataclass(frozen=True)
class SpacTree:
    pts: Any        # (R, C, D) int32 coordinates
    codes: Any      # (R, C) uint32 SFC codes
    valid: Any      # (R, C) bool
    count: Any      # (R,) int32
    active: Any     # (R,) bool
    bbox_lo: Any    # (R, D) int32
    bbox_hi: Any    # (R, D) int32
    min_code: Any   # (R,) uint32 (CODE_MAX when inactive)
    unsorted: Any   # (R,) bool — the partial-order flag
    order: Any      # (R,) int32 row ids sorted by min_code (inactive last)
    num_rows: Any   # () int32
    overflowed: Any  # () bool — capacity exhausted (grow + rebuild needed)
    phi: int = 32
    curve: str = "hilbert"
    bits: int = 16
    coord_bits: int = 30

    @property
    def capacity_rows(self) -> int:
        return self.pts.shape[0]

    @property
    def row_capacity(self) -> int:
        return self.pts.shape[1]

    @property
    def dim(self) -> int:
        return self.pts.shape[2]

    def view(self) -> LeafView:
        return LeafView(self.pts, self.valid, self.active, self.bbox_lo,
                        self.bbox_hi)

    @property
    def size(self):
        return jnp.sum(jnp.where(self.active, self.count, 0))


def _encode(pts, curve: str, bits: int, coord_bits: int):
    """Quantize coordinates to ``bits``/dim and encode. Quantization only
    affects clustering order, never correctness (leaves are unsorted sets and
    queries are bbox-exact)."""
    shift = max(0, coord_bits - bits)
    q = (pts.astype(jnp.uint32) >> shift)
    if curve == "hilbert":
        return sfc.hilbert_encode(q, bits)
    if curve == "morton":
        return sfc.morton_encode(q, bits)
    raise ValueError(f"unknown curve {curve!r}")


def _dir_mincodes(tree: SpacTree):
    mc = jnp.where(tree.active, tree.min_code, CODE_MAX)
    return mc[tree.order]


def _rebuild_order(active, min_code, codes, valid):
    """Directory order: active rows by (min_code, max code), then row id.

    Rows that share a min_code v hold only v, except the last of the run
    in code order, whose max is larger. Row ids alone do not give that
    order once a split has moved a run's leading rows into higher free
    row ids; sorting on the max puts that row last again, where routing
    (the last row with min_code <= code) expects it."""
    key = jnp.where(active, min_code, CODE_MAX)
    max_code = jnp.max(jnp.where(valid, codes, 0), axis=1)
    rows = jnp.arange(key.shape[0], dtype=jnp.int32)
    _, _, order = jax.lax.sort((key, max_code, rows), num_keys=2)
    return order, jnp.sum(active, dtype=jnp.int32)


def _route(tree: SpacTree, codes):
    """Directory lookup: row id owning each code."""
    dmc = _dir_mincodes(tree)
    j = jnp.searchsorted(dmc, codes, side="right").astype(jnp.int32) - 1
    j = jnp.clip(j, 0, tree.capacity_rows - 1)
    return tree.order[j]


# ---------------------------------------------------------------------------
# construction (paper Alg. 3)
# ---------------------------------------------------------------------------

def build_impl(points, mask=None, *, phi: int = 32, curve: str = "hilbert",
               bits: int = 16, coord_bits: int = 30,
               capacity_rows: int | None = None) -> SpacTree:
    """BuildSPaCTree: fused encode+sort, then chunk into phi-blocked rows.

    Unjitted spelling — the only legal call inside a shard_map region
    (jax 0.4.x miscompiles a nested jit there; see ROADMAP "Contracts",
    rule jit-in-shard-map). Single-device callers use :data:`build`.
    """
    n, dim = points.shape
    points = points.astype(jnp.int32)
    if mask is None:
        mask = jnp.ones(n, bool)
    if capacity_rows is None:
        capacity_rows = max(2 * ((n + phi - 1) // phi), 8)
    R, C = capacity_rows, 2 * phi

    codes = _encode(points, curve, bits, coord_bits)
    key = jnp.where(mask, codes, CODE_MAX)
    # HybridSort: only (code, id) pairs move through the sort; points are
    # gathered once afterwards.
    perm = jnp.argsort(key, stable=True).astype(jnp.int32)
    s_codes = key[perm]
    s_pts = points[perm]
    s_ok = mask[perm]

    row, slot = chunk_rows_from_sorted(n, phi)
    pts_rows = jnp.zeros((R, C, dim), jnp.int32)
    codes_rows = jnp.zeros((R, C), jnp.uint32)
    valid_rows = jnp.zeros((R, C), bool)
    pts_rows = scatter_to_rows(pts_rows, row, slot, s_pts, s_ok)
    codes_rows = scatter_to_rows(codes_rows, row, slot, s_codes, s_ok)
    valid_rows = scatter_to_rows(valid_rows, row, slot, jnp.ones(n, bool),
                                 s_ok)
    count = jnp.zeros(R, jnp.int32).at[jnp.where(s_ok, row, R)].add(
        1, mode="drop")
    active = count > 0
    bbox_lo, bbox_hi = segment_bbox(s_pts, row, s_ok, R)
    min_code = jnp.full(R, CODE_MAX, jnp.uint32).at[
        jnp.where(s_ok, row, R)].min(s_codes, mode="drop")
    order, num_rows = _rebuild_order(active, min_code, codes_rows,
                                     valid_rows)
    return SpacTree(pts=pts_rows, codes=codes_rows, valid=valid_rows,
                    count=count, active=active, bbox_lo=bbox_lo,
                    bbox_hi=bbox_hi, min_code=min_code,
                    unsorted=jnp.zeros(R, bool), order=order,
                    num_rows=num_rows, overflowed=jnp.array(False),
                    phi=phi, curve=curve, bits=bits, coord_bits=coord_bits)


build = jax.jit(build_impl, static_argnames=("phi", "curve", "bits",
                                             "coord_bits", "capacity_rows"))


# ---------------------------------------------------------------------------
# batch insertion (paper Alg. 4)
# ---------------------------------------------------------------------------

def insert_impl(tree: SpacTree, new_pts, new_mask=None, *,
                max_overflow_rows: int = 64,
                sort_rows: bool = False) -> SpacTree:
    """Batch insertion. ``sort_rows=True`` disables the partial-order
    relaxation (the CPAM-like total-order baseline of Fig. 3).

    Unjitted spelling for shard_map regions; use :data:`insert` outside.
    """
    m, dim = new_pts.shape
    new_pts = new_pts.astype(jnp.int32)
    if new_mask is None:
        new_mask = jnp.ones(m, bool)
    R, C = tree.capacity_rows, tree.row_capacity
    phi = tree.phi

    # --- sort the batch by code (HybridSort on the batch) ---
    codes = _encode(new_pts, tree.curve, tree.bits, tree.coord_bits)
    key = jnp.where(new_mask, codes, CODE_MAX)
    perm = jnp.argsort(key, stable=True).astype(jnp.int32)
    s_codes, s_pts, s_ok = key[perm], new_pts[perm], new_mask[perm]

    # --- route to rows (sorted batch => equal rows contiguous) ---
    row_of = jnp.where(s_ok, _route(tree, s_codes), R)  # R => dropped
    adds = jnp.zeros(R, jnp.int32).at[row_of].add(1, mode="drop")
    # overflow decision (inactive target rows — empty tree — can overflow too)
    over = tree.count + adds > C
    goes_over = over[jnp.clip(row_of, 0, R - 1)] & s_ok
    fits = s_ok & ~goes_over

    # --- phase 1: relaxed append into slack slots (no sorting!) ---
    pts_rows, valid_rows, count, (codes_rows,) = append_unsorted(
        tree.pts, tree.valid, tree.count, row_of, s_pts, fits,
        extras_rows=(tree.codes,), new_extras=(s_codes,))
    seg_lo, seg_hi = segment_bbox(s_pts, row_of, fits, R)
    bbox_lo = jnp.minimum(tree.bbox_lo, seg_lo)
    bbox_hi = jnp.maximum(tree.bbox_hi, seg_hi)
    min_code = tree.min_code.at[jnp.where(fits, row_of, R)].min(
        s_codes, mode="drop")
    touched = adds > 0
    unsorted = tree.unsorted | (touched & ~over)

    # --- phase 2: Expose + split overflowing rows ---
    MOR = max_overflow_rows
    orow_ids, n_over = take_k_where(over, MOR)
    ovalid_rows = orow_ids >= 0
    safe_rows = jnp.maximum(orow_ids, 0)
    old_pts = tree.pts[safe_rows].reshape(MOR * C, dim)
    old_codes = tree.codes[safe_rows].reshape(MOR * C)
    old_ok = (tree.valid[safe_rows] & ovalid_rows[:, None]
              & tree.active[safe_rows][:, None]).reshape(MOR * C)
    buf_pts = jnp.concatenate([old_pts, s_pts], axis=0)
    buf_codes = jnp.concatenate([old_codes, s_codes])
    buf_ok = jnp.concatenate([old_ok, goes_over])
    n_buf = buf_pts.shape[0]

    # band id = which overflowing row owns each buffer point. Re-chunking
    # happens *within* each band: a fresh row must never span two source
    # rows' key ranges, or the directory interval invariant breaks (a
    # fresh row would overlap rows between the two sources in code
    # space, and route-based delete/insert would miss points there).
    inv_map = jnp.full((R + 1,), MOR, jnp.int32).at[
        jnp.where(ovalid_rows, safe_rows, R)].set(
        jnp.arange(MOR, dtype=jnp.int32), mode="drop")
    old_band = jnp.repeat(jnp.arange(MOR, dtype=jnp.int32), C)
    new_band = inv_map[jnp.clip(row_of, 0, R)]
    buf_band = jnp.where(buf_ok,
                         jnp.concatenate([old_band, new_band]), MOR)

    # Expose: order is restored *here*, lazily (paper line 34/43).
    # Lexicographic (band, code) sort via two stable argsorts.
    bkey = jnp.where(buf_ok, buf_codes, CODE_MAX)
    p1 = jnp.argsort(bkey, stable=True).astype(jnp.int32)
    p2 = jnp.argsort(buf_band[p1], stable=True).astype(jnp.int32)
    bperm = p1[p2]
    b_codes, b_pts = bkey[bperm], buf_pts[bperm]
    b_ok, b_band = buf_ok[bperm], buf_band[bperm]

    # band-local chunking into rows of phi
    occ = group_occurrence(b_band)
    local_chunk = occ // phi
    nslot = occ % phi
    # dense-rank the (band, chunk) keys -> freelist slots (fk is
    # nondecreasing over the sorted buffer, so a change-flag cumsum
    # ranks them)
    K = C // phi + (m + phi - 1) // phi + 1
    fk = b_band * K + local_chunk
    chg = b_ok & jnp.concatenate(
        [jnp.ones((1,), bool), (fk[1:] != fk[:-1])])
    dense = jnp.cumsum(chg.astype(jnp.int32)) - 1
    nrow_needed = jnp.sum(chg, dtype=jnp.int32)

    NR = MOR * (C // phi) + (m + phi - 1) // phi + MOR
    free_ids, _ = take_k_where(~tree.active & (adds == 0), NR)
    in_new = b_ok & (dense < NR)
    dest_row = jnp.where(in_new, jnp.maximum(free_ids, 0)[
        jnp.clip(dense, 0, NR - 1)], R)
    can_alloc = (nrow_needed <= jnp.sum(free_ids >= 0)) & (n_over <= MOR)
    dest_row = jnp.where(can_alloc, dest_row, R)

    pts_rows = scatter_to_rows(pts_rows, dest_row, nslot, b_pts, in_new)
    codes_rows = scatter_to_rows(codes_rows, dest_row, nslot, b_codes, in_new)
    valid_rows = scatter_to_rows(valid_rows, dest_row, nslot,
                                 jnp.ones(n_buf, bool), in_new)
    ncount = jnp.zeros(R, jnp.int32).at[dest_row].add(1, mode="drop")
    nlo, nhi = segment_bbox(b_pts, jnp.where(in_new, dest_row, R), in_new, R)
    nmin = jnp.full(R, CODE_MAX, jnp.uint32).at[dest_row].min(
        b_codes, mode="drop")

    newly_active = ncount > 0
    count = jnp.where(newly_active, ncount, count)
    bbox_lo = jnp.where(newly_active[:, None], nlo, bbox_lo)
    bbox_hi = jnp.where(newly_active[:, None], nhi, bbox_hi)
    min_code = jnp.where(newly_active, nmin, min_code)
    unsorted = jnp.where(newly_active, False, unsorted)

    # activate appended rows; deactivate + fully reset the split rows
    dropped = over & can_alloc
    active = ((tree.active | (adds > 0)) & ~dropped) | newly_active
    valid_rows = jnp.where(dropped[:, None], False, valid_rows)
    count = jnp.where(dropped, 0, count)
    big = jnp.asarray(jnp.iinfo(jnp.int32).max, jnp.int32)
    bbox_lo = jnp.where(dropped[:, None], big, bbox_lo)
    bbox_hi = jnp.where(dropped[:, None], -big, bbox_hi)
    min_code = jnp.where(dropped, CODE_MAX, min_code)
    unsorted = jnp.where(dropped, False, unsorted)

    if sort_rows:  # CPAM-like total-order baseline: sort every touched row
        order_c = jnp.argsort(jnp.where(valid_rows, codes_rows, CODE_MAX),
                              axis=1, stable=True)
        codes_rows = jnp.take_along_axis(codes_rows, order_c, axis=1)
        valid_rows = jnp.take_along_axis(valid_rows, order_c, axis=1)
        pts_rows = jnp.take_along_axis(
            pts_rows, order_c[..., None].repeat(dim, -1), axis=1)
        unsorted = jnp.zeros_like(unsorted)

    order, num_rows = _rebuild_order(active, min_code, codes_rows,
                                     valid_rows)
    new_tree = dataclasses.replace(
        tree, pts=pts_rows, codes=codes_rows, valid=valid_rows, count=count,
        active=active, bbox_lo=bbox_lo, bbox_hi=bbox_hi, min_code=min_code,
        unsorted=unsorted, order=order, num_rows=num_rows)
    ok_all = can_alloc & (n_over <= MOR)
    # all-or-nothing: on capacity shortfall return the tree unchanged with the
    # overflowed flag set (caller compacts to a larger capacity and retries)
    failed = dataclasses.replace(tree, overflowed=jnp.array(True))
    return jax.tree.map(lambda a, b: jnp.where(ok_all, a, b),
                        new_tree, failed)


insert = jax.jit(insert_impl,
                 static_argnames=("max_overflow_rows", "sort_rows"))


# ---------------------------------------------------------------------------
# batch deletion
# ---------------------------------------------------------------------------

def delete_impl(tree: SpacTree, del_pts, del_mask=None) -> SpacTree:
    """Batch deletion: banded route, ranked multiset match, intra-row
    compaction, bbox/min_code refresh for touched rows, directory rebuild.

    Unjitted spelling for shard_map regions — the delete path's
    while_loop is exactly the construct the jax 0.4.x nested-jit
    miscompile corrupts. Use :data:`delete` outside shard_map.

    Banded routing: a code equal to a row's min_code may have copies in
    *preceding* rows too (an equal-code run split across row boundaries
    at build/split time; every interior row of such a band has
    min_code == code exactly). Each entry's candidate band is directory
    positions [searchsorted_left - 1, searchsorted_right - 1]; a
    while_loop walks the band until every remaining entry has exhausted
    its rows — exact for any duplicate load, and the trip count is the
    widest band actually present (1-2 rows for typical data)."""
    m, dim = del_pts.shape
    del_pts = del_pts.astype(jnp.int32)
    if del_mask is None:
        del_mask = jnp.ones(m, bool)
    R, C = tree.capacity_rows, tree.row_capacity

    codes = _encode(del_pts, tree.curve, tree.bits, tree.coord_bits)
    key = jnp.where(del_mask, codes, CODE_MAX)
    perm = jnp.argsort(key, stable=True).astype(jnp.int32)
    s_codes, s_pts, s_ok = key[perm], del_pts[perm], del_mask[perm]

    dm = _dir_mincodes(tree)
    iL = jnp.searchsorted(dm, s_codes, side="left").astype(jnp.int32)
    iR = jnp.searchsorted(dm, s_codes, side="right").astype(jnp.int32)

    def cond(state):
        o, _, _, remaining, _ = state
        return jnp.any(remaining & (iL - 1 + o <= iR - 1))

    def body(state):
        o, valid_rows, count, remaining, touched = state
        pos = jnp.clip(jnp.minimum(iL - 1 + o, iR - 1), 0, R - 1)
        row_of = jnp.where(remaining, tree.order[pos], R - 1)
        valid_rows, count, matched = ranked_delete(
            tree.pts, valid_rows, count, row_of, s_pts, remaining,
            window=C)
        touched = touched.at[jnp.where(matched, row_of, R)].set(
            True, mode="drop")
        return (o + 1, valid_rows, count, remaining & ~matched, touched)

    _, valid_rows, count, _, touched = jax.lax.while_loop(
        cond, body, (jnp.int32(0), tree.valid, tree.count, s_ok,
                     jnp.zeros(R, bool)))
    # intra-row stable compaction keeps `count == leading valid slots`;
    # only touched rows change, so only they are gathered and refreshed
    active = tree.active & (count > 0)
    dest, (cvalid, cpts, ccodes) = compact_touched(
        touched, min(m, R), valid_rows, tree.pts, tree.codes)
    live = cvalid & active[jnp.minimum(dest, R - 1)][:, None]
    lo, hi = row_bbox_from_slots(cpts, live)
    mc = jnp.min(jnp.where(live, ccodes, CODE_MAX), axis=1)
    valid_rows = valid_rows.at[dest].set(cvalid, mode="drop")
    pts_rows = tree.pts.at[dest].set(cpts, mode="drop")
    codes_rows = tree.codes.at[dest].set(ccodes, mode="drop")
    bbox_lo = tree.bbox_lo.at[dest].set(lo, mode="drop")
    bbox_hi = tree.bbox_hi.at[dest].set(hi, mode="drop")
    min_code = tree.min_code.at[dest].set(mc, mode="drop")
    order, num_rows = _rebuild_order(active, min_code, codes_rows,
                                     valid_rows)
    return dataclasses.replace(
        tree, pts=pts_rows, codes=codes_rows, valid=valid_rows, count=count,
        active=active, bbox_lo=bbox_lo, bbox_hi=bbox_hi, min_code=min_code,
        order=order, num_rows=num_rows)


delete = jax.jit(delete_impl)


def grow(tree: SpacTree, capacity_rows: int) -> SpacTree:
    """Pad the row arrays to a larger capacity (outside jit; the production
    check-and-grow pattern between jit steps)."""
    R = tree.capacity_rows
    if capacity_rows <= R:
        return tree
    extra = capacity_rows - R

    def pad(a, fill):
        pw = [(0, extra)] + [(0, 0)] * (a.ndim - 1)
        return jnp.pad(a, pw, constant_values=fill)

    big = jnp.iinfo(jnp.int32).max
    arrays = dict(
        pts=pad(tree.pts, 0), codes=pad(tree.codes, 0),
        valid=pad(tree.valid, False), count=pad(tree.count, 0),
        active=pad(tree.active, False), bbox_lo=pad(tree.bbox_lo, big),
        bbox_hi=pad(tree.bbox_hi, -big),
        min_code=pad(tree.min_code, CODE_MAX),
        unsorted=pad(tree.unsorted, False))
    order, num_rows = _rebuild_order(arrays["active"], arrays["min_code"],
                                     arrays["codes"], arrays["valid"])
    return dataclasses.replace(tree, **arrays, order=order,
                               num_rows=num_rows)


def free_rows(tree: SpacTree) -> int:
    return int(jnp.sum(~tree.active))


def extract_points(tree: SpacTree):
    """All (point, validity) pairs, flattened — for rebuilds/compaction."""
    R, C, dim = tree.pts.shape
    ok = (tree.valid & tree.active[:, None]).reshape(R * C)
    return tree.pts.reshape(R * C, dim), ok


def compact(tree: SpacTree, capacity_rows: int | None = None) -> SpacTree:
    """Full rebuild (bulk rebalance / grow). Not jit — shapes may change."""
    pts, ok = extract_points(tree)
    return build(pts, ok, phi=tree.phi, curve=tree.curve, bits=tree.bits,
                 coord_bits=tree.coord_bits,
                 capacity_rows=capacity_rows or tree.capacity_rows)
