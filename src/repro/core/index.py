"""Unified ``SpatialIndex`` facade over every tree family (the Index API).

The paper's central observation is that the P-Orth tree and the SPaC-tree
family share one operational contract — parallel batch build/insert/delete
plus exact kNN/range queries — and the comparison baselines (kd, Zd) fit the
same contract with rebuild-style updates. This module is that contract as
code: a string-keyed backend registry plus a thin immutable handle so callers
write

    idx = make_index("spac-h", points, phi=32)
    idx = idx.insert(batch)
    d2, ids = idx.knn(queries, k=10)

for any backend, local or distributed (pass ``mesh=``), and never touch
``capacity_rows``, ``overflowed``, ``grow`` or ``compact`` by hand.

Three guarantees the facade adds over the raw modules:

* **Automatic capacity.** Row capacity is sized by one shared heuristic
  (``capacity_for``); builds that overflow (or silently drop, for backends
  without an overflow flag) are retried at doubled capacity, and an insert
  that overflows triggers the transparent recovery ladder
  ``grow -> retry -> compact -> retry`` before giving up. Callers never see
  ``overflowed``.
* **Jit-cached update closures.** Insert/delete run through closures cached
  on ``(backend, batch shape, dtype, static params)`` — the ``ServeEngine``
  pattern — so a serving hot path that feeds fixed-shape batches never
  retraces. ``donate=True`` additionally donates the old tree's buffers to
  the update (serving mode: the caller must drop old handles after each
  update; the default keeps updates pure so benchmarks can re-time them).
* **One registry.** ``register_backend`` makes new tree families available
  to every benchmark, example and test loop that iterates ``BACKENDS``.

Registered kinds:

====== ===================================================================
kind   backend
====== ===================================================================
porth  P-Orth tree (sieve-built parallel orth-tree, paper Sec. 3)
spac-h SPaC-tree over the Hilbert curve (paper Sec. 4)
spac-z SPaC-tree over the Morton (Z-order) curve
spac-m alias of ``spac-z`` (Morton), kept for the paper's naming
cpam-h CPAM-like total-order ablation of spac-h (sorts touched rows)
cpam-z CPAM-like total-order ablation of spac-z
kd     parallel kd-tree baseline (object-median splits, rebuild updates)
zd     Zd-tree-like baseline (Morton presort, merge-rebuild updates)
====== ===================================================================
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from .. import obs
from . import baselines, porth, queries, spac
from .engine import QueryEngine

# Default root domain for orth-style backends on integer coordinates —
# matches ``repro.data.points.DEFAULT_HI``. Pass ``root_lo``/``root_hi`` to
# ``make_index`` for data outside [0, 2^20)^D; float data defaults to the
# unit cube.
DEFAULT_ROOT_HI = 1 << 20


# ---------------------------------------------------------------------------
# capacity policy
# ---------------------------------------------------------------------------

def capacity_for(n_points: int, phi: int = 32, slack: int = 4) -> int:
    """Shared row-capacity heuristic: rows for ``n_points`` with ``slack``x
    headroom over the dense packing (leaves hold >= phi/2 points after a
    split, but cells can run underfull — orth backends use slack=8)."""
    return int(slack) * ((int(n_points) + phi - 1) // phi) + 64


def _round_capacity(rows: int) -> int:
    """Round up to a power of two so rebuild-style backends reuse their jit
    cache across nearby sizes instead of retracing every batch."""
    return 1 << max(int(rows) - 1, 15).bit_length()


# ---------------------------------------------------------------------------
# backend registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Backend:
    """Adapter spec every tree family registers.

    ``build(points, mask, *, phi, capacity_rows, **build_params) -> tree``;
    ``insert/delete(tree, pts, mask, **update_params) -> tree``. ``dynamic``
    backends update in place (fixed arrays + ``overflowed`` flag) and must
    provide ``grow``/``compact``; rebuild backends re-run ``build`` and take
    ``capacity_rows`` as an update param instead.
    """
    name: str
    build: Callable[..., Any]
    insert: Callable[..., Any]
    delete: Callable[..., Any]
    dynamic: bool
    grow: Callable[..., Any] | None = None
    compact: Callable[..., Any] | None = None
    cap_slack: int = 4
    build_params: tuple[str, ...] = ()
    insert_params: tuple[str, ...] = ()
    delete_params: tuple[str, ...] = ()
    defaults: dict[str, Any] = dataclasses.field(default_factory=dict)
    resolve: Callable[[dict, Any], dict] | None = None
    curve: str | None = None   # set for spac-family kinds (distributed)


BACKENDS: dict[str, Backend] = {}


def register_backend(backend: Backend) -> None:
    """Add (or replace) a backend under ``backend.name``."""
    BACKENDS[backend.name] = backend


def get_backend(kind: str) -> Backend:
    try:
        return BACKENDS[kind]
    except KeyError:
        raise KeyError(
            f"unknown index kind {kind!r}; registered: "
            f"{sorted(BACKENDS)}") from None


# ---------------------------------------------------------------------------
# per-family adapters
# ---------------------------------------------------------------------------

def _porth_resolve(params: dict, points) -> dict:
    dim = points.shape[1]
    out = dict(params)
    if out.get("lam") is None:
        out["lam"] = 3 if dim == 2 else 2   # paper: 3 levels/round in 2D
    if jnp.issubdtype(points.dtype, jnp.floating):
        lo, hi = 0.0, 1.0
    else:
        lo, hi = 0, DEFAULT_ROOT_HI
    if out.get("root_lo") is None:
        out["root_lo"] = jnp.full((dim,), lo, points.dtype)
    if out.get("root_hi") is None:
        out["root_hi"] = jnp.full((dim,), hi, points.dtype)
    out["root_lo"] = jnp.asarray(out["root_lo"], points.dtype)
    out["root_hi"] = jnp.asarray(out["root_hi"], points.dtype)
    return out


def _porth_build(points, mask, *, phi, capacity_rows, root_lo, root_hi,
                 lam, rounds):
    return porth.build(points, root_lo, root_hi, mask, phi=phi, lam=lam,
                       rounds=rounds, capacity_rows=capacity_rows)


def _porth_insert(tree, pts, mask, *, max_overflow_rows):
    mor = min(int(max_overflow_rows), tree.pts.shape[0])
    return porth.insert(tree, pts, mask, max_overflow_rows=mor)


def _porth_delete(tree, pts, mask):
    return porth.delete(tree, pts, mask)


def _spac_build(points, mask, *, phi, capacity_rows, curve, bits,
                coord_bits):
    return spac.build(points, mask, phi=phi, curve=curve, bits=bits,
                      coord_bits=coord_bits, capacity_rows=capacity_rows)


def _spac_insert(tree, pts, mask, *, max_overflow_rows, sort_rows):
    mor = min(int(max_overflow_rows), tree.pts.shape[0])
    return spac.insert(tree, pts, mask, max_overflow_rows=mor,
                       sort_rows=sort_rows)


def _spac_delete(tree, pts, mask):
    return spac.delete(tree, pts, mask)


def _kd_build(points, mask, *, phi, capacity_rows, max_depth):
    return baselines.kd_build(points, mask, phi=phi, max_depth=max_depth,
                              capacity_rows=capacity_rows)


def _kd_insert(index, pts, mask, *, capacity_rows, max_depth):
    return baselines.kd_insert(index, pts, mask, max_depth=max_depth,
                               capacity_rows=capacity_rows)


def _kd_delete(index, pts, mask, *, capacity_rows, max_depth):
    return baselines.kd_delete(index, pts, mask, max_depth=max_depth,
                               capacity_rows=capacity_rows)


def _zd_build(points, mask, *, phi, capacity_rows, bits, coord_bits, lam):
    return baselines.zd_build(points, mask, phi=phi, bits=bits,
                              coord_bits=coord_bits, lam=lam,
                              capacity_rows=capacity_rows)


def _zd_insert(index, pts, mask, *, capacity_rows, bits, coord_bits, lam):
    return baselines.zd_insert(index, pts, mask, bits=bits,
                               coord_bits=coord_bits, lam=lam,
                               capacity_rows=capacity_rows)


def _zd_delete(index, pts, mask, *, capacity_rows, bits, coord_bits, lam):
    return baselines.zd_delete(index, pts, mask, bits=bits,
                               coord_bits=coord_bits, lam=lam,
                               capacity_rows=capacity_rows)


register_backend(Backend(
    name="porth", build=_porth_build, insert=_porth_insert,
    delete=_porth_delete, dynamic=True, grow=porth.grow,
    compact=porth.compact, cap_slack=8,
    build_params=("root_lo", "root_hi", "lam", "rounds"),
    insert_params=("max_overflow_rows",),
    defaults=dict(root_lo=None, root_hi=None, lam=None, rounds=5,
                  max_overflow_rows=64),
    resolve=_porth_resolve))

for _name, _curve, _sort in (("spac-h", "hilbert", False),
                             ("spac-z", "morton", False),
                             ("spac-m", "morton", False),
                             ("cpam-h", "hilbert", True),
                             ("cpam-z", "morton", True)):
    register_backend(Backend(
        name=_name, build=_spac_build, insert=_spac_insert,
        delete=_spac_delete, dynamic=True, grow=spac.grow,
        compact=spac.compact, cap_slack=4,
        build_params=("curve", "bits", "coord_bits"),
        insert_params=("max_overflow_rows", "sort_rows"),
        # coord_bits: the default integer domain [0, 2^20) (as
        # DEFAULT_ROOT_HI). Quantizing that domain from 30 bits would keep
        # 6 bits per dim, 4096 codes: at millions of points each code
        # fills a run of rows, all inserts of a code land in the run's
        # last row, and a 1% batch overflows thousands of rows at once
        defaults=dict(curve=_curve, bits=16, coord_bits=20,
                      max_overflow_rows=64, sort_rows=_sort),
        curve=_curve))

register_backend(Backend(
    name="kd", build=_kd_build, insert=_kd_insert, delete=_kd_delete,
    dynamic=False, cap_slack=4,
    build_params=("max_depth",),
    insert_params=("max_depth",), delete_params=("max_depth",),
    defaults=dict(max_depth=24)))

register_backend(Backend(
    name="zd", build=_zd_build, insert=_zd_insert, delete=_zd_delete,
    dynamic=False, cap_slack=8,
    build_params=("bits", "coord_bits", "lam"),
    insert_params=("bits", "coord_bits", "lam"),
    delete_params=("bits", "coord_bits", "lam"),
    defaults=dict(bits=15, coord_bits=20, lam=3)))


# ---------------------------------------------------------------------------
# jit-cached update closures (ServeEngine pattern)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _update_closure(kind: str, op: str, m: int, dim: int, dtype: str,
                    pkey: tuple, donate: bool):
    """One jitted closure per (backend, batch shape, dtype, static params).

    Tree shapes are handled by jax's own trace cache inside the closure, so
    a fixed-shape update stream compiles exactly once. ``donate`` releases
    the old tree's buffers to the update (serving mode)."""
    obs.count("index.update_plan_miss")
    backend = get_backend(kind)
    fn = backend.insert if op == "insert" else backend.delete
    kw = dict(pkey)

    def run(tree, pts, mask):
        return fn(tree, pts, mask, **kw)

    return jax.jit(run, donate_argnums=(0,) if donate else ())


# ---------------------------------------------------------------------------
# the facade
# ---------------------------------------------------------------------------

class SpatialIndex:
    """Immutable handle over one backend tree; updates return new handles.

    Construct via :func:`make_index`. All query methods delegate to the
    :class:`repro.core.engine.QueryEngine` through the backend's
    ``LeafView``: exact by default (no ``max_rows``/``cap``/``truncated``
    on this surface), jit-cached plans, ``impl="auto"`` kernel routing.
    """

    def __init__(self, kind: str, tree, *, phi: int, params: dict,
                 donate: bool = False, size_hint: int = 0,
                 rebuild_rows: int = 0, engine: QueryEngine | None = None):
        self.kind = kind
        self._backend = get_backend(kind)
        self._tree = tree
        self.phi = phi
        self._params = params
        self._donate = donate
        # host-side upper bound on live points (rebuild backends size their
        # next rebuild from it without a device sync; never decremented so
        # capacity stays sufficient)
        self._size_hint = size_hint
        self._rebuild_rows = rebuild_rows
        # planning state (flat-scan budget, converged query buffers)
        # rides along across functional updates
        self._engine = engine if engine is not None else QueryEngine()

    # -- plumbing ----------------------------------------------------------

    def _wrap(self, tree, size_hint=None, rebuild_rows=None) -> \
            "SpatialIndex":
        out = SpatialIndex.__new__(SpatialIndex)
        out.kind = self.kind
        out._backend = self._backend
        out._tree = tree
        out.phi = self.phi
        out._params = self._params
        out._donate = self._donate
        out._size_hint = (self._size_hint if size_hint is None
                          else size_hint)
        out._rebuild_rows = (self._rebuild_rows if rebuild_rows is None
                             else rebuild_rows)
        out._engine = self._engine
        return out

    def _prep(self, pts, mask):
        pts = jnp.asarray(pts)
        if mask is None:
            mask = jnp.ones(pts.shape[0], bool)
        else:
            mask = jnp.asarray(mask, bool)
        return pts, mask

    def _static_kwargs(self, op: str, extra: dict | None = None) -> tuple:
        names = (self._backend.insert_params if op == "insert"
                 else self._backend.delete_params)
        kw = {k: self._params[k] for k in names}
        if extra:
            kw.update(extra)
        return tuple(sorted(kw.items()))

    def _run_update(self, op: str, tree, pts, mask,
                    extra: dict | None = None):
        # donation is a no-op on CPU and only triggers "unusable donated
        # buffer" warnings there — gate it to real accelerators
        donate = self._donate and jax.default_backend() != "cpu"
        fn = _update_closure(self.kind, op, pts.shape[0], pts.shape[1],
                             str(pts.dtype), self._static_kwargs(op, extra),
                             donate)
        # compile-cost attribution (no-op unless a capture_costs recorder
        # is installed): charge this update plan's flops/bytes once per
        # signature, next to the update_plan_miss it corresponds to
        obs.costs.capture(
            fn, (tree, pts, mask),
            f"update.{self.kind}.{op}.m{pts.shape[0]}.d{pts.shape[1]}"
            f".r{tree.pts.shape[0]}")
        return fn(tree, pts, mask)

    # -- introspection -----------------------------------------------------

    @property
    def tree(self):
        """The raw backend pytree (escape hatch; prefer the facade)."""
        return self._tree

    @property
    def capacity_rows(self) -> int:
        """Allocated leaf-row capacity (grows automatically)."""
        return self._tree.pts.shape[0]

    @property
    def num_rows(self):
        """Occupied leaf rows (device scalar; ``int()`` it to sync)."""
        return jnp.sum(self._tree.active, dtype=jnp.int32)

    @property
    def dim(self) -> int:
        return self._tree.pts.shape[2]

    @property
    def size(self):
        """Live point count (device scalar; ``int()`` it to sync)."""
        return self._tree.size

    @property
    def nbytes(self) -> int:
        """Resident bytes of the backend tree's buffers — pure
        shape/dtype arithmetic (``repro.obs.memory.tree_bytes``), never
        a device read, so safe on dispatch paths."""
        return obs.tree_bytes(self._tree)

    def __len__(self) -> int:
        return int(self.size)

    def view(self) -> queries.LeafView:
        return self._tree.view()

    def block_until_ready(self) -> "SpatialIndex":
        """Wait for all device work on the tree (duck-types with
        ``jax.block_until_ready`` so timing harnesses see real latency)."""
        jax.block_until_ready(self._tree)
        return self

    def extract_points(self):
        """All (points, valid) pairs flattened — for rebuilds/export."""
        R, C, dim = self._tree.pts.shape
        ok = (self._tree.valid & self._tree.active[:, None]).reshape(R * C)
        return self._tree.pts.reshape(R * C, dim), ok

    # -- updates -----------------------------------------------------------

    def insert(self, new_pts, new_mask=None) -> "SpatialIndex":
        """Batch insert; auto-grows on overflow, so the result never has
        ``overflowed`` set."""
        pts, mask = self._prep(new_pts, new_mask)
        m = pts.shape[0]
        if not self._backend.dynamic:
            hint = self._size_hint + m
            rows = max(self._rebuild_rows, _round_capacity(
                capacity_for(hint, self.phi, self._backend.cap_slack)))
            # rebuild backends drop silently past row capacity (no
            # overflow flag), so verify the rebuilt size and retry bigger
            # — clustered data can need far more rows than the heuristic
            expected = int(self._tree.size) + int(jnp.sum(mask))
            for _ in range(6):
                tree = self._run_update("insert", self._tree, pts, mask,
                                        extra=dict(capacity_rows=rows))
                if int(tree.size) == expected:
                    break
                obs.count("index.rebuild_retry")
                rows = 2 * rows
            else:
                raise RuntimeError(
                    f"{self.kind}: insert of {m} points still overflows "
                    f"at capacity_rows={rows}")
            return self._wrap(tree, size_hint=hint, rebuild_rows=rows)
        tree = self._run_update("insert", self._tree, pts, mask)
        if bool(tree.overflowed):
            tree = self._recover_insert(tree, pts, mask)
        return self._wrap(tree)

    def _recover_insert(self, failed_tree, pts, mask):
        """The grow -> retry -> compact -> retry ladder (all-or-nothing
        inserts return the old contents with ``overflowed`` set, so the
        failed tree is a valid starting point even under donation)."""
        b = self._backend
        tree = dataclasses.replace(failed_tree,
                                   overflowed=jnp.asarray(False))
        live = int(tree.size) + pts.shape[0]
        need = _round_capacity(capacity_for(live, self.phi, b.cap_slack))
        mor = int(self._params.get("max_overflow_rows", 64))
        recovery = obs.span("index.recover_insert", kind=self.kind).begin()
        for attempt in range(4):
            cap = max(need << attempt, 2 * tree.pts.shape[0])
            obs.count("index.grow" if attempt == 0 else "index.compact")
            tree = (b.grow(tree, cap) if attempt == 0
                    else b.compact(tree, cap))
            mor = min(4 * mor, cap)
            out = self._run_update("insert", tree, pts, mask,
                                   extra=dict(max_overflow_rows=mor))
            if not bool(out.overflowed):
                recovery.set(attempts=attempt + 1, capacity_rows=cap).end()
                return out
            tree = dataclasses.replace(out, overflowed=jnp.asarray(False))
        recovery.set(failed=True).end()
        raise RuntimeError(
            f"{self.kind}: insert of {pts.shape[0]} points still overflows "
            f"at capacity_rows={cap}")

    def insert_unchecked(self, new_pts, new_mask=None) -> "SpatialIndex":
        """Dispatch-only insert for the serving runtime: skips the
        host-side ``overflowed`` read (a full device sync), so the call
        returns as soon as the jit-cached update closure is enqueued and
        queries against *older* versions can overlap with it on device.

        The returned handle may carry a sticky ``overflowed`` flag; the
        caller owns checking it at its next sync point —
        :class:`repro.serving.SpatialServer` defers the check to
        ``commit()`` and replays from the last good version on overflow.
        Rebuild-style backends (kd/zd) fall back to the checked
        :meth:`insert` (their size verification is inherently
        synchronous)."""
        if not self._backend.dynamic:
            return self.insert(new_pts, new_mask)
        pts, mask = self._prep(new_pts, new_mask)
        return self._wrap(self._run_update("insert", self._tree, pts,
                                           mask))

    def delete(self, del_pts, del_mask=None) -> "SpatialIndex":
        """Batch delete (exact multiset semantics; absent points no-op)."""
        pts, mask = self._prep(del_pts, del_mask)
        if not self._backend.dynamic:
            # removal can only shrink groups, never split them, so the
            # rebuild always fits at the current capacity
            rows = max(self._rebuild_rows, self.capacity_rows)
            tree = self._run_update("delete", self._tree, pts, mask,
                                    extra=dict(capacity_rows=rows))
            return self._wrap(tree, rebuild_rows=rows)
        return self._wrap(self._run_update("delete", self._tree, pts, mask))

    def delete_unchecked(self, del_pts, del_mask=None) -> "SpatialIndex":
        """Dispatch-only delete for the serving runtime — the async
        counterpart of :meth:`insert_unchecked`. Deletes cannot overflow
        rows, so for dynamic backends this is :meth:`delete` itself; the
        alias exists so the server can dispatch every update through the
        same ``*_unchecked`` spelling regardless of direction."""
        return self.delete(del_pts, del_mask)

    # -- queries (exact by default; see repro.core.engine) -----------------

    @property
    def engine(self) -> QueryEngine:
        """The query planner riding along with this index (flat-scan
        budget, converged buffer buckets)."""
        return self._engine

    def knn(self, qpts, k: int, *, impl: str = "auto"):
        """Exact batched kNN -> (d2 (Q, k) ascending, flat ids (Q, k)).

        ``impl``: "auto" (planner routes to the Pallas brute-force
        kernel or the fused frontier kernel), or a forced spelling —
        "frontier", "pallas-frontier", "pallas-frontier-interpret",
        "flat", "pallas", "pallas-interpret", "ref"."""
        return self._engine.knn(self.view(), jnp.asarray(qpts), k,
                                impl=impl)

    def knn_points(self, qpts, k: int, *, impl: str = "auto"):
        """kNN returning coordinates: (d2, neighbor points, valid)."""
        view = self.view()
        d2, ids = self._engine.knn(view, jnp.asarray(qpts), k, impl=impl)
        return d2, queries.gather_points(view, ids), ids >= 0

    def range_count(self, lo, hi):
        """Exact batched range count -> counts (Q,). No sizing knobs:
        the engine escalates its row buffer until nothing truncates."""
        return self._engine.range_count(self.view(), jnp.asarray(lo),
                                        jnp.asarray(hi))

    def range_list(self, lo, hi):
        """Exact batched range report -> (ids (Q, cap) padded with -1,
        counts (Q,)); cap is auto-sized so every hit is present."""
        return self._engine.range_list(self.view(), jnp.asarray(lo),
                                       jnp.asarray(hi))

    def __repr__(self):
        return (f"SpatialIndex(kind={self.kind!r}, "
                f"capacity_rows={self.capacity_rows}, phi={self.phi})")


# ---------------------------------------------------------------------------
# constructor
# ---------------------------------------------------------------------------

def make_index(kind: str, points, mask=None, *, phi: int = 32,
               capacity_rows: int | None = None,
               capacity_points: int | None = None, mesh=None,
               donate: bool = False, **params):
    """Build an index of the given registered ``kind`` over ``points``.

    ``capacity_points`` sizes row capacity for the *maximum* live points
    expected over the index's lifetime (defaults to ``len(points)``);
    ``capacity_rows`` overrides the heuristic outright. Backend-specific
    options (``curve``, ``bits``, ``root_lo``, ``lam``, ...) pass through as
    keyword params. With ``mesh=`` the index is built key-range-partitioned
    over the mesh's devices and a :class:`DistributedIndex` is returned
    (mesh-capable kinds: the spac family routes by curve code, porth by
    sieve prefix key). ``mesh`` is a ``jax.sharding.Mesh`` or a device
    count ``n``: a 1-D mesh over the first ``n`` devices JAX sees
    (:func:`repro.configs.platform.device_mesh`; fewer raise).
    """
    if mesh is not None:
        if donate:
            raise ValueError("donate=True is not supported for "
                             "distributed indexes")
        return DistributedIndex.build(kind, points, mesh, mask=mask,
                                      phi=phi, capacity_rows=capacity_rows,
                                      capacity_points=capacity_points,
                                      **params)
    backend = get_backend(kind)
    pts = jnp.asarray(points)
    n = pts.shape[0]
    resolved = dict(backend.defaults)
    unknown = set(params) - set(resolved)
    if unknown:
        raise TypeError(f"{kind}: unknown params {sorted(unknown)}; "
                        f"accepted: {sorted(resolved)}")
    resolved.update(params)
    if backend.resolve is not None:
        resolved = backend.resolve(resolved, pts)

    pts_mask = (jnp.ones(n, bool) if mask is None
                else jnp.asarray(mask, bool))
    expected = n if mask is None else int(jnp.sum(pts_mask))
    cap = capacity_rows if capacity_rows is not None else capacity_for(
        capacity_points if capacity_points is not None else n,
        phi, backend.cap_slack)
    build_kw = {k: resolved[k] for k in backend.build_params}
    for _ in range(8):
        tree = backend.build(pts, pts_mask, phi=phi, capacity_rows=cap,
                             **build_kw)
        # backends without an overflow flag drop silently; the size check
        # catches both
        short = (bool(getattr(tree, "overflowed", False))
                 or int(tree.size) != expected)
        if not short:
            break
        obs.count("index.build_retry")
        # jump at least to the heuristic (explicit caps can be tiny), then
        # keep doubling
        cap = max(2 * cap,
                  capacity_for(expected, phi, backend.cap_slack))
    else:
        raise RuntimeError(f"{kind}: build of {expected} points overflows "
                           f"even at capacity_rows={cap}")
    return SpatialIndex(kind, tree, phi=phi, params=resolved, donate=donate,
                        size_hint=expected,
                        rebuild_rows=0 if backend.dynamic else cap)


# ---------------------------------------------------------------------------
# distributed adapter
# ---------------------------------------------------------------------------

class DistributedIndex:
    """The same surface over an SFC-range-partitioned index on a device
    mesh (:mod:`repro.core.distributed`). kNN returns the local facade's
    result type, (d2, flat ids): an id is global, ``s*R*C + row*C +
    slot`` on shard ``s``, and indexes the shard-major flattened points
    of :meth:`extract_points`; ``knn_points`` returns coordinates.
    ``range_list`` is not offered distributed."""

    def __init__(self, kind: str, index, mesh, *, phi: int,
                 slack: float = 2.0, build_kw: dict | None = None,
                 engine: QueryEngine | None = None):
        self.kind = kind
        self._index = index
        self.mesh = mesh
        self.phi = phi
        self.slack = slack
        # everything needed to re-shard at a larger capacity (overflow
        # recovery keeps the facade's never-see-overflowed contract)
        self._build_kw = build_kw or {}
        self._engine = engine if engine is not None else QueryEngine()

    @classmethod
    def build(cls, kind: str, points, mesh, *, mask=None, phi: int = 32,
              capacity_rows: int | None = None,
              capacity_points: int | None = None, slack: float = 2.0,
              n_samples: int = 256, axis: str = "data", **params):
        from . import distributed as D
        if isinstance(mesh, int):
            from ..configs import platform
            mesh = platform.device_mesh(mesh, (axis,))
        backend = get_backend(kind)
        pts = jnp.asarray(points)
        if kind == "porth":
            # the sieve routes by its own prefix keys (Morton codes from
            # midpoint comparisons), so float domains shard exactly
            allowed = ("root_lo", "root_hi", "lam", "rounds")
            resolved = {k: params.pop(k, backend.defaults[k])
                        for k in allowed}
            if params:
                raise TypeError(f"{kind} (distributed): unknown params "
                                f"{sorted(params)}")
            resolved = _porth_resolve(resolved, pts)
            import numpy as np
            route_kw = dict(
                kind="porth",
                root_lo=tuple(np.asarray(resolved["root_lo"]).tolist()),
                root_hi=tuple(np.asarray(resolved["root_hi"]).tolist()),
                lam=int(resolved["lam"]), rounds=int(resolved["rounds"]))
        elif backend.curve is not None and \
                not backend.defaults.get("sort_rows"):
            bits = params.pop("bits", backend.defaults["bits"])
            coord_bits = params.pop("coord_bits",
                                    backend.defaults["coord_bits"])
            if params:
                raise TypeError(f"{kind} (distributed): unknown params "
                                f"{sorted(params)}")
            route_kw = dict(kind="spac", curve=backend.curve, bits=bits,
                            coord_bits=coord_bits)
        else:
            raise ValueError(
                f"distributed indexes require a mesh-capable kind "
                f"(spac-family or porth), got {kind!r}")
        if capacity_rows is None and capacity_points is not None:
            # per-shard rows for the lifetime maximum, with 2x headroom
            # for routing imbalance
            n_shards = mesh.shape[axis]
            capacity_rows = capacity_for(
                2 * capacity_points // max(n_shards, 1), phi,
                backend.cap_slack)
        build_kw = dict(axis=axis, phi=phi, capacity_rows=capacity_rows,
                        slack=slack, n_samples=n_samples, **route_kw)
        expected = pts.shape[0] if mask is None else int(
            jnp.sum(jnp.asarray(mask, bool)))
        for _ in range(6):
            idx = D.build(pts, mesh, mask, **build_kw)
            # two silent-loss modes: shard-local builds drop past row
            # capacity, and skewed routing overflows the all_to_all slab
            # (reported via `dropped`) — escalate whichever bit
            size, dropped = int(D.size(idx)), int(idx.dropped)
            if size == expected:
                break
            if dropped:
                build_kw["slack"] = 2 * build_kw["slack"]
            if size + dropped != expected:
                build_kw["capacity_rows"] = 2 * idx.tree.pts.shape[-3]
        else:
            raise RuntimeError(
                f"{kind} (distributed): build of {expected} points still "
                f"loses points at capacity_rows="
                f"{build_kw['capacity_rows']}, slack={build_kw['slack']}")
        return cls(kind, idx, mesh, phi=phi, slack=build_kw["slack"],
                   build_kw=build_kw)

    def _wrap(self, idx) -> "DistributedIndex":
        return DistributedIndex(self.kind, idx, self.mesh, phi=self.phi,
                                slack=self.slack, build_kw=self._build_kw,
                                engine=self._engine)

    @property
    def index(self):
        """The raw :class:`repro.core.distributed.DistIndex`."""
        return self._index

    @property
    def size(self):
        from . import distributed as D
        return D.size(self._index)

    def __len__(self) -> int:
        return int(self.size)

    @property
    def dropped(self):
        """Points lost to routing-slab overflow (0 = exact; re-shard with a
        larger ``slack`` if nonzero)."""
        return self._index.dropped

    @property
    def tree(self):
        """The stacked (n_shards, ...) backend pytree — the same handle
        the serving runtime uses for memory accounting and barriers on
        local indexes. Note ``overflowed`` is a stacked per-shard vector
        here; reduce with ``jnp.any`` before branching on it."""
        return self._index.tree

    def shard_sizes(self):
        """Per-shard live point counts, shape (n_shards,) — metadata
        arithmetic on the stacked leaves, cheap enough for the per-shard
        obs gauges that ``SpatialServer.commit`` attaches."""
        from . import distributed as D
        return D.shard_sizes(self._index)

    @property
    def nbytes(self) -> int:
        """Resident bytes across all shards (metadata arithmetic —
        global arrays report their full logical footprint)."""
        return obs.tree_bytes(self._index)

    def insert(self, pts, mask=None) -> "DistributedIndex":
        """Batch insert. Two shard-level failure modes are recovered
        here so (as with the local facade) callers never lose points: a
        shard whose rows fill up keeps its old contents and raises
        ``overflowed`` (all-or-nothing), and a skewed batch can overflow
        the fixed all_to_all routing slab (``dropped`` grows). Either
        way we re-shard the pre-insert snapshot plus the batch at
        doubled per-shard capacity / escalated slack."""
        from . import distributed as D
        pts = jnp.asarray(pts)
        base = int(self._index.dropped)
        slack = self.slack
        for _ in range(3):
            out = D.insert(self._index, pts, self.mesh, mask, slack=slack)
            if bool(jnp.any(out.tree.overflowed)):
                break               # shard rows full: re-shard below
            if int(out.dropped) == base:
                res = self._wrap(out)
                res.slack = slack   # keep the slack that worked
                return res
            # routing slab too tight: a fully-skewed batch (all entries
            # to one shard) needs slack ~ n_shards, so jump there
            slack = max(2 * slack,
                        self.mesh.shape[self._build_kw["axis"]])
        old_pts, old_ok = self.extract_points()
        m = pts.shape[0]
        batch_ok = jnp.ones(m, bool) if mask is None else jnp.asarray(
            mask, bool)
        all_pts = jnp.concatenate([old_pts, pts.astype(old_pts.dtype)])
        all_ok = jnp.concatenate([old_ok, batch_ok])
        # shard_map needs the leading dim divisible by the shard count
        kw = self._build_kw
        n_shards = self.mesh.shape[kw["axis"]]
        pad = (-all_pts.shape[0]) % n_shards
        if pad:
            all_pts = jnp.concatenate(
                [all_pts, jnp.zeros((pad, all_pts.shape[1]),
                                    all_pts.dtype)])
            all_ok = jnp.concatenate([all_ok, jnp.zeros(pad, bool)])
        # the classmethod retries at doubling capacity until the full
        # multiset fits; routing-key params pass through per kind
        extra = {k: kw[k] for k in ("bits", "coord_bits", "root_lo",
                                    "root_hi", "lam", "rounds") if k in kw}
        return DistributedIndex.build(
            self.kind, all_pts, self.mesh, mask=all_ok, phi=self.phi,
            capacity_rows=2 * self._index.tree.pts.shape[-3],
            slack=slack, n_samples=kw["n_samples"], axis=kw["axis"],
            **extra)

    def insert_unchecked(self, pts, mask=None) -> "DistributedIndex":
        """Dispatch-only insert for the serving runtime: no host-side
        reads of ``dropped`` or the per-shard ``overflowed`` flags, so
        the call returns once the cached shard_map program is enqueued
        and queries against older versions overlap with it on device.

        Both failure signals are sticky (``overflowed`` per shard in the
        stacked tree, ``dropped`` accumulated on the DistIndex) — the
        caller owns checking them at its next sync point;
        :class:`repro.serving.SpatialServer` defers both to ``commit()``
        and replays from the last good version."""
        from . import distributed as D
        out = D.insert(self._index, jnp.asarray(pts), self.mesh, mask,
                       slack=self.slack)
        return self._wrap(out)

    def delete_unchecked(self, pts, mask=None) -> "DistributedIndex":
        """Dispatch-only delete: like :meth:`insert_unchecked`, skips the
        host-side ``dropped`` check (a dropped delete entry means a point
        that should have died survives — caught at commit)."""
        from . import distributed as D
        out = D.delete(self._index, jnp.asarray(pts), self.mesh, mask,
                       slack=self.slack)
        return self._wrap(out)

    def delete(self, pts, mask=None) -> "DistributedIndex":
        """Batch delete. A skewed batch can overflow the routing slab, in
        which case the overflowed entries would silently never be deleted
        — retry from the (functional, untouched) pre-delete index with
        escalated slack until nothing is dropped."""
        from . import distributed as D
        pts = jnp.asarray(pts)
        base = int(self._index.dropped)
        slack = self.slack
        for _ in range(5):
            out = D.delete(self._index, pts, self.mesh, mask, slack=slack)
            if int(out.dropped) == base:
                res = self._wrap(out)
                res.slack = slack   # keep the slack that worked
                return res
            # worst case (fully-skewed batch) needs slack ~ n_shards
            slack = max(2 * slack,
                        self.mesh.shape[self._build_kw["axis"]])
        raise RuntimeError(
            f"{self.kind} (distributed): delete batch still overflows "
            f"the routing slab at slack={slack}")

    @property
    def engine(self) -> QueryEngine:
        return self._engine

    def knn(self, qpts, k: int, *, impl: str = "auto"):
        """Exact distributed kNN -> (d2 (Q, k) ascending, global flat ids
        (Q, k), -1 padded): the engine routes each shard's local query
        (frontier vs flat scan) and merges via top-k of per-shard top-k;
        equal distances go to the lowest id."""
        return self._engine.knn_dist(self._index, jnp.asarray(qpts), k,
                                     self.mesh, impl=impl)

    def knn_points(self, qpts, k: int, *, impl: str = "auto"):
        """kNN returning coordinates: (d2, neighbor points, valid), the
        points gathered on the device at :meth:`knn`'s global ids."""
        d2, ids = self.knn(qpts, k, impl=impl)
        ok = ids >= 0
        pts = self.extract_points()[0][jnp.maximum(ids, 0)]
        return d2, jnp.where(ok[..., None], pts, 0), ok

    def range_count(self, lo, hi):
        """Exact distributed range count -> counts (Q,): per-shard
        counts + psum, row buffers escalated until no shard truncates."""
        return self._engine.range_count_dist(
            self._index, jnp.asarray(lo), jnp.asarray(hi), self.mesh)

    def block_until_ready(self) -> "DistributedIndex":
        jax.block_until_ready(self._index)
        return self

    def extract_points(self):
        """(points (S*R*C, dim), valid (S*R*C,)), shard-major: the
        layout :meth:`knn`'s global ids index."""
        t = self._index.tree
        dim = t.pts.shape[-1]
        ok = (t.valid & t.active[..., None]).reshape(-1)
        return t.pts.reshape(-1, dim), ok

    def __repr__(self):
        return (f"DistributedIndex(kind={self.kind!r}, "
                f"mesh={dict(self.mesh.shape)}, phi={self.phi})")
