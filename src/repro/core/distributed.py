"""Distributed dynamic spatial index: the paper's workload at pod scale.

The index is *key-range partitioned* over a mesh axis via shard_map —
the multi-node analogue of the paper's shared-memory design:

  * splitters — each shard samples local routing keys; samples
    all_gather and quantile splitters define per-shard key ranges (the
    same sample-based partitioning the paper's HybridSort uses per
    node). The routing key is backend-specific but always a uint32 SFC
    code: ``spac`` encodes the curve (Hilbert/Morton), ``porth`` uses
    the sieve's prefix keys (:func:`repro.core.porth.point_keys` — they
    *are* Morton codes, computed by midpoint comparisons, so float
    coordinates route exactly like the paper's 'Applicability' claim).
  * routing — updates compute keys, searchsorted against splitters,
    pack into fixed-capacity per-destination slabs, and exchange with
    ONE all_to_all (the cross-chip counterpart of the sieve's
    one-round data movement; per-pair capacity + overflow counter
    replace dynamic allocation).
  * local index — each shard owns an independent SPaC-tree or P-Orth
    tree over its key range; batch insert/delete are the paper's
    algorithms unchanged.
  * queries — kNN fans out (queries replicated), each shard answers
    exactly from its range, and a top-k merge over an all_gather of
    each shard's (d2, global id) combines candidates; exact because
    shards partition the point set. A global id is the shard's offset
    in the shard-major flattened points plus its local row*C+slot, so
    kNN returns the same (d2, ids) as a local index. Range-count is a
    local count + psum.

Every collective program here — updates and queries — is built by an
``lru_cache`` closure factory returning ``jax.jit(shard_map(local))``:
jit *around* the shard region, and every local call an unjitted
``*_impl`` spelling (the ``jit-in-shard-map`` contract). The serving hot path
(``SpatialServer`` over a :class:`repro.core.index.DistributedIndex`)
therefore dispatches updates and coalesced queries with zero retraces
after warmup — the query closures bump ``repro.core.engine``'s trace
counter so tests can assert that bound across the exchange.

At 1000+ nodes the axis simply grows; nothing here depends on the
shard count. Skew (the paper's Varden/Sweepline) shows up as routing
imbalance: the `dropped` counter reports slab overflow so callers can
re-shard with a larger slack — tested in tests/test_distributed.py.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from . import engine as _engine
from . import porth
from . import queries as Q
from . import spac
from .leafstore import BIG, group_occurrence

P = jax.sharding.PartitionSpec

CODE_MAX = np.uint32(0xFFFFFFFF)  # numpy: keep import device-free


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["tree", "splitters", "dropped"],
    meta_fields=["axis", "kind", "ckey"])
@dataclasses.dataclass(frozen=True)
class DistIndex:
    tree: Any          # backend pytree, leaves stacked (n_shards, ...)
    splitters: Any     # (n_shards - 1,) uint32, replicated
    dropped: Any       # () int32 — points lost to slab overflow (0 = ok)
    axis: str = "data"
    kind: str = "spac"          # routing-key family: "spac" | "porth"
    # hashable routing-key params (spac: curve/bits/coord_bits; porth:
    # root_lo/root_hi tuples + lam/rounds) — static meta so dispatch
    # closures can key their cache without a device read
    ckey: tuple = (("bits", 16), ("coord_bits", 30),
                   ("curve", "hilbert"))


def _unstack(tree):
    return jax.tree.map(lambda a: a[0], tree)


def _stack(tree):
    return jax.tree.map(lambda a: a[None], tree)


def _codes(pts, kind: str, kw: dict):
    """Routing key of each point (uint32): the backend's SFC spelling."""
    if kind == "porth":
        root_lo = jnp.asarray(kw["root_lo"], pts.dtype)
        root_hi = jnp.asarray(kw["root_hi"], pts.dtype)
        return porth.point_keys(pts, root_lo, root_hi, lam=kw["lam"],
                                rounds=kw["rounds"])
    return spac._encode(pts.astype(jnp.int32), kw["curve"], kw["bits"],
                        kw["coord_bits"])


def _coerce(pts, kind: str):
    """spac shards store int32 coordinates; porth keeps the caller's
    dtype (float routing is the orth tree's applicability win)."""
    return pts if kind == "porth" else pts.astype(jnp.int32)


def _sample_splitters(codes, mask, axis, n_shards, n_samples=256):
    """Deterministic quantile splitters from sorted local samples.

    Each shard contributes exactly ``n_samples`` codes drawn evenly
    (with replacement when it holds fewer valid rows) from the *valid*
    prefix of its locally sorted codes. Padding the sample with
    CODE_MAX sentinels instead would shift the top quantiles to
    CODE_MAX whenever a shard holds fewer than ``n_samples`` rows and
    leave the last shards empty."""
    key = jnp.where(mask, codes, CODE_MAX)
    srt = jnp.sort(key)
    v = jnp.maximum(jnp.sum(mask, dtype=jnp.int32), 1)
    pos = (jnp.arange(n_samples, dtype=jnp.int32) * v) // n_samples
    local = srt[pos]
    allv = jnp.sort(jax.lax.all_gather(local, axis).reshape(-1))
    total = allv.shape[0]
    idx = (jnp.arange(1, n_shards) * total) // n_shards
    return allv[idx]


def _pack(pts, mask, bucket, n_shards: int, cap: int):
    """Pack rows into per-destination slabs (n_shards*cap, ...)."""
    n, dim = pts.shape
    key = jnp.where(mask, bucket, n_shards)
    perm = jnp.argsort(key, stable=True).astype(jnp.int32)
    sb, sp, sm = key[perm], pts[perm], mask[perm]
    occ = group_occurrence(sb)
    keep = sm & (occ < cap)
    slot = jnp.where(keep, sb * cap + occ, n_shards * cap)
    send_pts = jnp.zeros((n_shards * cap, dim), pts.dtype
                         ).at[slot].set(sp, mode="drop")
    send_mask = jnp.zeros((n_shards * cap,), bool
                          ).at[slot].set(keep, mode="drop")
    return send_pts, send_mask, jnp.sum(sm & ~keep, dtype=jnp.int32)


def _route_exchange(pts, mask, splitters, axis, n_shards: int, cap: int,
                    kind: str, kw: dict):
    codes = _codes(pts, kind, kw)
    bucket = jnp.searchsorted(splitters, codes, side="right"
                              ).astype(jnp.int32)
    send_p, send_m, dropped = _pack(_coerce(pts, kind), mask, bucket,
                                    n_shards, cap)
    recv_p = jax.lax.all_to_all(send_p.reshape(n_shards, cap, -1), axis,
                                split_axis=0, concat_axis=0)
    recv_m = jax.lax.all_to_all(send_m.reshape(n_shards, cap), axis,
                                split_axis=0, concat_axis=0)
    dim = pts.shape[1]
    return (recv_p.reshape(n_shards * cap, dim),
            recv_m.reshape(n_shards * cap),
            jax.lax.psum(dropped, axis))


def _smap(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _pad_rows(pts, mask, n_shards: int):
    """Pad the leading (sharded) dim to a multiple of the shard count —
    shape metadata only, so dispatch paths stay host-sync-free."""
    m = pts.shape[0]
    if mask is None:
        mask = jnp.ones(m, bool)
    pad = (-m) % n_shards
    if pad:
        pts = jnp.concatenate(
            [pts, jnp.zeros((pad, pts.shape[1]), pts.dtype)])
        mask = jnp.concatenate([mask, jnp.zeros(pad, bool)])
    return pts, mask


# ---------------------------------------------------------------- closures
#
# Every collective program is cached here: jax.jit wraps the *outside*
# of the shard_map region (the only legal direction — see module
# docstring), keyed on the static routing/shape params. The local fns
# count traces so serving tests can pin the no-retrace contract.

@functools.lru_cache(maxsize=None)
def _build_closure(mesh, axis: str, n_shards: int, cap: int, kind: str,
                   phi: int, capacity_rows: int, n_samples: int,
                   ckey: tuple):
    obs.count("dist.plan_miss")
    kw = dict(ckey)

    def local(pts, msk):
        obs.count("dist.update_trace")
        codes = _codes(pts, kind, kw)
        splitters = _sample_splitters(codes, msk, axis, n_shards,
                                      n_samples)
        rp, rm, dropped = _route_exchange(pts, msk, splitters, axis,
                                          n_shards, cap, kind, kw)
        # _impl spellings: a jitted callee here would nest jax.jit under
        # shard_map, the jax 0.4.x miscompile class (wrong results on
        # shards != 0); shard_map's own trace is the only jit we want
        if kind == "porth":
            tree = porth.build_impl(
                rp, jnp.asarray(kw["root_lo"], rp.dtype),
                jnp.asarray(kw["root_hi"], rp.dtype), rm, phi=phi,
                lam=kw["lam"], rounds=kw["rounds"],
                capacity_rows=capacity_rows)
        else:
            tree = spac.build_impl(rp, rm, phi=phi, curve=kw["curve"],
                                   bits=kw["bits"],
                                   coord_bits=kw["coord_bits"],
                                   capacity_rows=capacity_rows)
        return _stack(tree), splitters, dropped

    return jax.jit(_smap(local, mesh, in_specs=(P(axis), P(axis)),
                         out_specs=(P(axis), P(), P())))


@functools.lru_cache(maxsize=None)
def _update_closure(mesh, axis: str, n_shards: int, cap: int, kind: str,
                    op: str, mor: int, ckey: tuple):
    obs.count("dist.plan_miss")
    kw = dict(ckey)

    def local(tree, p, k, splitters):
        obs.count("dist.update_trace")
        tree = _unstack(tree)
        rp, rm, dropped = _route_exchange(p, k, splitters, axis,
                                          n_shards, cap, kind, kw)
        # _impl spellings: delete's while_loop under a nested jit is the
        # documented jax 0.4.x shard_map miscompile; insert matches for
        # symmetry (and to keep one trace instead of two)
        if op == "insert":
            tree = (porth.insert_impl(tree, rp, rm,
                                      max_overflow_rows=mor)
                    if kind == "porth" else
                    spac.insert_impl(tree, rp, rm,
                                     max_overflow_rows=mor))
        else:
            tree = (porth.delete_impl(tree, rp, rm) if kind == "porth"
                    else spac.delete_impl(tree, rp, rm))
        return _stack(tree), dropped

    smapped = _smap(local, mesh, in_specs=(P(axis), P(axis), P(axis), P()),
                    out_specs=(P(axis), P()))

    def run(tree, p, k, splitters):
        # named ``run`` like every other closure of the index and the
        # engine, so a profiler trace names the program ``jit_run``
        return smapped(tree, p, k, splitters)

    return jax.jit(run)


# Query closures are shard_map programs like the updates: each shard
# answers over its whole subtree with the unjitted local spelling, and
# the merge exchanges only query-sized results — an all_gather of the
# per-shard top-k (kNN) or a psum of per-shard counts (range). A
# GSPMD-partitioned vmap over the stacked shard axis cannot carry the
# Pallas routes: Mosaic kernels are never partitioned automatically.

@functools.lru_cache(maxsize=None)
def _knn_closure(mesh, axis: str, k: int, impl: str, kernel: str,
                 chunk: int):
    obs.count("dist.plan_miss")
    from ..kernels.frontier import ops as frontier_ops
    from ..kernels.knn import ops as knn_ops

    def local(tree, q):
        view = _unstack(tree).view()
        if impl == "frontier":
            d2, ids = Q.knn_impl(view, q, k, chunk)
        elif impl == "pallas-frontier":
            d2, ids = frontier_ops.knn_frontier_impl(
                view.pts, view.valid, view.active, view.bbox_lo,
                view.bbox_hi, q, k=k, impl=kernel)
        else:
            flat_pts, flat_ok = Q.flatten_view(view)
            d2, ids = knn_ops.knn_bruteforce_impl(
                q, flat_pts, flat_ok, k=k, impl=kernel)
        # the shard's hits in (d2, local id) order, BIG-padded
        d2, ids = _engine.canonical_knn(jnp.where(ids >= 0, d2, BIG), ids)
        # (Q, S*k) candidates, shard-major: top_k takes the lower index
        # among equal distances, so ties go to the lower shard, then to
        # the lower local id: the lowest global id
        cat_d2 = jax.lax.all_gather(d2, axis, axis=1, tiled=True)
        neg, sel = jax.lax.top_k(-cat_d2, k)
        d2 = -neg
        # global flat id: the shard's offset in extract_points()'s
        # shard-major (S*R*C, dim) layout plus its local row*C+slot
        rows, cols = view.pts.shape[:2]
        gid = jnp.where(ids >= 0,
                        jax.lax.axis_index(axis) * (rows * cols) + ids, -1)
        cat_gid = jax.lax.all_gather(gid, axis, axis=1, tiled=True)
        best = jnp.take_along_axis(cat_gid, sel, axis=1)
        return d2, jnp.where(d2 < BIG, best, -1)

    smapped = _smap(local, mesh, in_specs=(P(axis), P()),
                    out_specs=(P(), P()))

    def run(tree, q):
        # trace-time counter: same contract as the engine's local query
        # closures, so the O(log) retrace bound is assertable across
        # the distributed merge too
        _engine._STATS["traces"] += 1
        obs.count("engine.trace")
        return smapped(tree, q)

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _range_count_closure(mesh, axis: str, max_rows: int):
    obs.count("dist.plan_miss")

    def local(tree, lo, hi):
        cnt, trunc = Q.range_count_impl(_unstack(tree).view(), lo, hi,
                                        max_rows)
        return (jax.lax.psum(cnt, axis),
                jax.lax.psum(trunc.astype(jnp.int32), axis) > 0)

    smapped = _smap(local, mesh, in_specs=(P(axis), P(), P()),
                    out_specs=(P(), P()))

    def run(tree, lo, hi):
        _engine._STATS["traces"] += 1
        obs.count("engine.trace")
        return smapped(tree, lo, hi)

    return jax.jit(run)


# ----------------------------------------------------------------- build

def build(points, mesh, mask=None, *, axis: str = "data", phi: int = 32,
          kind: str = "spac", curve: str = "hilbert", bits: int = 16,
          coord_bits: int = 30, root_lo=None, root_hi=None, lam: int = 3,
          rounds: int = 5, capacity_rows: int | None = None,
          slack: float = 2.0, n_samples: int = 256) -> DistIndex:
    """points: (N, dim) sharded on dim 0 over `axis` (or host array —
    jax will split it; ragged N is padded to the shard count). Returns
    a DistIndex with one local-tree shard per device along `axis`.

    ``kind="spac"`` routes by curve code (``curve``/``bits``/
    ``coord_bits``); ``kind="porth"`` routes by sieve prefix key
    (``root_lo``/``root_hi`` domain tuples + ``lam``/``rounds``)."""
    n, dim = points.shape
    n_shards = mesh.shape[axis]
    points, mask = _pad_rows(jnp.asarray(points), mask, n_shards)
    n_local = n // max(n_shards, 1)
    cap = int(n_local * slack / n_shards) + 8
    if capacity_rows is None:
        capacity_rows = max(4 * ((n_shards * cap + phi - 1) // phi), 8)
    if kind == "porth":
        if root_lo is None or root_hi is None:
            raise ValueError("kind='porth' needs root_lo/root_hi")
        ckey = (("lam", int(lam)),
                ("root_hi", tuple(np.asarray(root_hi).tolist())),
                ("root_lo", tuple(np.asarray(root_lo).tolist())),
                ("rounds", int(rounds)))
    else:
        ckey = (("bits", int(bits)), ("coord_bits", int(coord_bits)),
                ("curve", curve))
    fn = _build_closure(mesh, axis, n_shards, cap, kind, phi,
                        int(capacity_rows), n_samples, ckey)
    tree, splitters, dropped = fn(points, mask)
    return DistIndex(tree=tree, splitters=splitters, dropped=dropped,
                     axis=axis, kind=kind, ckey=ckey)


# --------------------------------------------------------------- updates

def _update(index: DistIndex, pts, mask, mesh, op: str, slack: float):
    axis = index.axis
    n_shards = mesh.shape[axis]
    pts, mask = _pad_rows(jnp.asarray(pts), mask, n_shards)
    m = pts.shape[0]
    cap = int((m // n_shards) * slack / n_shards) + 8
    R = index.tree.pts.shape[-3]
    obs.count("dist.update.routed", n_shards * n_shards * cap)
    fn = _update_closure(mesh, axis, n_shards, cap, index.kind, op,
                         min(64, R), index.ckey)
    tree, dropped = fn(index.tree, pts, mask, index.splitters)
    return dataclasses.replace(index, tree=tree,
                               dropped=index.dropped + dropped)


def insert(index: DistIndex, pts, mesh, mask=None, *, slack: float = 2.0):
    return _update(index, pts, mask, mesh, "insert", slack)


def delete(index: DistIndex, pts, mesh, mask=None, *, slack: float = 2.0):
    return _update(index, pts, mask, mesh, "delete", slack)


# --------------------------------------------------------------- queries

def knn(index: DistIndex, qpts, k: int, mesh, chunk: int = 8,
        impl: str = "frontier", kernel: str = "auto"):
    """Exact distributed kNN. qpts: (Q, dim) replicated. Returns
    (d2 (Q, k) ascending, ids (Q, k)), the same result type as a local
    index's kNN: ``ids`` are global flat ids, ``s*R*C + row*C + slot``
    on shard ``s``, which index the shard-major flattened points of
    :meth:`repro.core.index.DistributedIndex.extract_points`; -1 pads.
    Equal distances go to the lowest id.

    ``impl="frontier"`` runs the chunked frontier traversal per shard;
    ``impl="pallas-frontier"`` the fused frontier kernel;
    ``impl="flat"`` the brute-force scan (``kernel`` picks the kernel
    flavor: auto/pallas/pallas-interpret/ref)."""
    S, R, C = index.tree.pts.shape[:3]
    if S * R * C > 2**31:
        raise ValueError(
            f"{S} shards x {R} rows x {C} slots = {S * R * C} slots: "
            f"global kNN ids are int32 and would wrap past 2**31 - 1")
    obs.count("dist.knn.calls")
    obs.count("dist.knn.candidates", qpts.shape[0] * S * int(k))
    fn = _knn_closure(mesh, index.axis, int(k), impl, kernel, int(chunk))
    return fn(index.tree, qpts)


def range_count(index: DistIndex, lo, hi, mesh, max_rows: int = 128):
    """Exact distributed range-count: per-shard count + global sum."""
    obs.count("dist.range.calls")
    fn = _range_count_closure(mesh, index.axis, int(max_rows))
    return fn(index.tree, lo, hi)


def size(index: DistIndex) -> jax.Array:
    t = index.tree
    return jnp.sum(jnp.where(t.active, t.count, 0))


def shard_sizes(index: DistIndex) -> jax.Array:
    """Per-shard live point counts, shape (n_shards,) — stacked-array
    arithmetic on metadata-addressable leaves (no shard_map launch), so
    cheap enough for per-shard obs gauges."""
    t = index.tree
    return jnp.sum(jnp.where(t.active, t.count, 0), axis=-1)
