"""Unified ``QueryEngine``: exact-by-default queries with auto-sized
buffers, jit-cached plans, and Pallas kernel routing (the Query API).

PR 1 gave *updates* one facade; this module does the same for *queries*.
The raw engines in :mod:`repro.core.queries` are exact only when the
caller sizes their fixed-capacity buffers correctly (``max_rows`` rows
gathered per range query, ``cap`` output slots per range-list) and
checks the ``truncated`` flags — a contract benchmarks and servers
silently violated. The :class:`QueryEngine` owns those knobs instead:

* **Exact by default.** Results are checked on device and the engine
  escalates ``max_rows``/``cap`` through power-of-two buckets
  (mirroring ``index._round_capacity``) and re-runs until nothing is
  truncated. A query stream therefore retraces at most O(log R) times
  per (query kind, batch shape); the converged bucket is remembered per
  engine so steady-state workloads never escalate again.
* **Jit-cached plans.** Every query runs through a closure cached on
  ``(op, Q-shape, dtype, k/caps, impl)`` — exactly like the facade's
  ``_update_closure`` — so fixed workloads compile once. The module
  counts closure traces (:func:`trace_count`) so tests can assert the
  retrace bound.
* **Execution planner.** ``impl="auto"`` routes kNN to the Pallas
  brute-force kernel (:mod:`repro.kernels.knn`) when the index's slot
  count ``R*C`` fits a flat-scan budget (small indexes, post-compact
  trees) and to the fused frontier kernel
  (:mod:`repro.kernels.frontier`) otherwise — pruned traversal with
  the running top-k on-chip and the direct ``|q - p|^2`` distances the
  chunked traversal computes, so both return the same distances at any
  coordinate magnitude. On a TPU both kernels run compiled; elsewhere
  ``auto`` runs their jnp mirrors. Forced
  spellings: ``"frontier"`` (chunked host-orchestrated traversal,
  ``chunk`` auto-picked from R), ``"pallas-frontier"``,
  ``"pallas-frontier-interpret"``, ``"flat"`` (brute force, kernel
  auto), ``"pallas"``, ``"pallas-interpret"``, ``"ref"``.
* **Distributed.** The same engine fronts
  :class:`repro.core.index.DistributedIndex`: per-shard queries run the
  unjitted ``*_impl`` spellings inside shard_map (required — see the
  ROADMAP miscompile note), the shard-merge step takes the top-k of
  per-shard top-k (kNN) or the psum of per-shard counts (range), and
  the same bucket escalation wraps the whole exchange. Distributed kNN
  ids are global flat ids into the sharded index's flattened points,
  so a local and a sharded index return one kNN result type.

kNN results are *canonical*: each query's k hits are sorted by
``(d2, id)``, so any two exact impls return bit-identical output on
tie-free data (asserted across backends in tests/test_queries_parity.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import obs
from ..kernels.frontier import ops as frontier_ops
from ..kernels.knn import ops as knn_ops
from . import queries
from .leafstore import BIG

# happy-path starting buckets; the engine escalates from here and
# remembers where it converged, so these only shape the first call
DEFAULT_MAX_ROWS = 128
DEFAULT_CAP = 512
# slot count (R*C) below which a flat brute-force scan beats the
# frontier traversal's sort + while_loop (the whole index is a few
# kernel tiles); above it the bbox pruning wins. Set from CPU runs
DEFAULT_FLAT_BUDGET = 1 << 15

KNN_IMPLS = ("auto", "frontier", "pallas-frontier",
             "pallas-frontier-interpret", "flat", "pallas",
             "pallas-interpret", "ref")

_STATS = {"traces": 0}


def trace_count() -> int:
    """Total query-closure traces this process (compilations, not calls);
    tests assert the O(log R) escalation bound against it."""
    return _STATS["traces"]


def reset_trace_count() -> None:
    _STATS["traces"] = 0


def _pow2(x: int) -> int:
    """Smallest power of two >= x (>= 1)."""
    return 1 << max(int(x) - 1, 0).bit_length()


def auto_chunk(rows: int) -> int:
    """Frontier chunk width from row count: ~R/16 rows per while-loop
    step, power of two, clamped to [8, 128]. Small indexes stop early
    on fine-grained bounds; large ones amortize the loop overhead."""
    return min(128, max(8, _pow2(rows // 16)))


def canonical_knn(d2, ids):
    """Sort each query's k hits by (d2, id) and re-pad invalid slots.

    Makes exact impls comparable bit-for-bit: top-k merge order differs
    between the frontier traversal and the flat scan, so without a
    canonical order equal-distance hits could legally permute."""
    d2, ids = jax.lax.sort((d2, ids), dimension=-1, num_keys=2)
    return d2, jnp.where(d2 >= BIG, -1, ids)


# ---------------------------------------------------------------------------
# jit-cached query closures (the _update_closure pattern)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _knn_closure(q: int, dim: int, dtype: str, k: int, route: str,
                 param):
    """One jitted closure per (Q-shape, dtype, k, route, chunk|kernel).

    View shapes are handled by jax's trace cache inside the closure (a
    retrace bumps the trace counter), so a fixed-shape query stream
    compiles exactly once."""
    obs.count("engine.plan_miss")
    if route == "frontier":
        def run(view, qpts):
            _STATS["traces"] += 1
            obs.count("engine.trace")
            d2, ids = queries.knn_impl(view, qpts, k, param)
            return canonical_knn(d2, ids)
    elif route == "pallas-frontier":
        def run(view, qpts):
            _STATS["traces"] += 1
            obs.count("engine.trace")
            d2, ids = frontier_ops.knn_frontier_impl(
                view.pts, view.valid, view.active, view.bbox_lo,
                view.bbox_hi, qpts, k=k, impl=param)
            return canonical_knn(d2, ids)
    else:
        def run(view, qpts):
            _STATS["traces"] += 1
            obs.count("engine.trace")
            pts, ok = queries.flatten_view(view)
            d2, ids = knn_ops.knn_bruteforce_impl(qpts, pts, ok, k=k,
                                                  impl=param)
            return canonical_knn(d2, ids)
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _range_count_closure(q: int, dim: int, dtype: str, max_rows: int):
    obs.count("engine.plan_miss")

    def run(view, lo, hi):
        _STATS["traces"] += 1
        obs.count("engine.trace")
        return queries.range_count_impl(view, lo, hi, max_rows)
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _range_list_closure(q: int, dim: int, dtype: str, max_rows: int,
                        cap: int):
    obs.count("engine.plan_miss")

    def run(view, lo, hi):
        _STATS["traces"] += 1
        obs.count("engine.trace")
        return queries.range_list_impl(view, lo, hi, max_rows, cap)
    return jax.jit(run)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class QueryEngine:
    """Exact query planner/executor over leaf-row indexes.

    One engine instance rides along with each ``SpatialIndex`` /
    ``DistributedIndex`` handle (shared across functional updates) and
    holds only host-side planning state: the flat-scan budget and the
    converged buffer bucket per query kind. All device-side caching is
    in the module-level closure caches, shared process-wide.
    """

    def __init__(self, *, flat_budget: int = DEFAULT_FLAT_BUDGET,
                 start_rows: int = DEFAULT_MAX_ROWS,
                 start_cap: int = DEFAULT_CAP):
        self.flat_budget = flat_budget
        self.start_rows = start_rows
        self.start_cap = start_cap
        self._buckets: dict = {}

    # -- planner -----------------------------------------------------------

    def plan_knn(self, rows: int, cols: int, impl: str = "auto"):
        """Resolve an impl spelling to (route, static param): one of
        ("frontier", chunk), ("pallas-frontier", kernel_impl) or
        ("flat", kernel_impl)."""
        if impl == "interpret":
            raise ValueError(
                'impl="interpret" is not a spelling; use the canonical '
                '"pallas-interpret" (one name across engine and kernels)')
        if impl not in KNN_IMPLS:
            raise ValueError(f"unknown kNN impl {impl!r}; one of "
                             f"{KNN_IMPLS}")
        if impl == "auto":
            impl = "flat" if rows * cols <= self.flat_budget else \
                "pallas-frontier"
        if impl == "frontier":
            return "frontier", auto_chunk(rows)
        if impl in ("pallas-frontier", "pallas-frontier-interpret"):
            kernel = "auto" if impl == "pallas-frontier" else \
                "pallas-interpret"
            return "pallas-frontier", kernel
        kernel = {"flat": "auto", "pallas": "pallas",
                  "pallas-interpret": "pallas-interpret",
                  "ref": "ref"}[impl]
        return "flat", kernel

    # -- local queries -----------------------------------------------------

    def knn(self, view: queries.LeafView, qpts, k: int,
            impl: str = "auto"):
        """Exact batched kNN -> (d2 (Q, k) ascending, flat ids (Q, k) =
        row*C+slot, -1 padded), canonically (d2, id)-ordered."""
        with obs.span("engine.knn"):
            rows, cols, dim = view.pts.shape
            route, param = self.plan_knn(rows, cols, impl)
            obs.count("engine.plan_request")
            obs.count(f"engine.route.{route}")
            fn = _knn_closure(qpts.shape[0], dim, str(qpts.dtype), int(k),
                              route, param)
            # opt-in compile-cost attribution (repro.obs.costs): charge
            # this plan's flops/bytes once per signature at the site that
            # owns the plan_miss counter; no-op on the default recorder.
            # The view shape is part of the signature — the compiled
            # program (and so its cost) depends on R x C, not just the
            # closure-cache key.
            obs.costs.capture(
                fn, (view, qpts),
                f"knn.q{qpts.shape[0]}.k{int(k)}.{route}-{param}"
                f".v{rows}x{cols}")
            return fn(view, qpts)

    def range_count(self, view: queries.LeafView, lo, hi):
        """Exact batched range count -> counts (Q,). Escalates the row
        buffer through power-of-two buckets until nothing truncates."""
        with obs.span("engine.range_count"):
            rows = view.pts.shape[0]
            key = ("range_count", lo.shape[0], lo.shape[-1], str(lo.dtype))
            max_rows = min(_pow2(self._buckets.get(key, self.start_rows)),
                           _pow2(rows))
            obs.count("engine.plan_request")
            rounds = 0
            while True:
                fn = _range_count_closure(lo.shape[0], lo.shape[-1],
                                          str(lo.dtype), max_rows)
                obs.costs.capture(
                    fn, (view, lo, hi),
                    f"range_count.q{lo.shape[0]}.r{max_rows}"
                    f".v{rows}x{view.pts.shape[1]}")
                cnt, trunc = fn(view, lo, hi)
                with obs.span("engine.range_count.sync"):
                    done = max_rows >= rows or not bool(jnp.any(trunc))
                if done:
                    self._buckets[key] = max_rows
                    obs.observe("engine.escalation_rounds", rounds)
                    return cnt
                rounds += 1
                obs.count("engine.escalation")
                max_rows = min(2 * max_rows, _pow2(rows))

    def range_list(self, view: queries.LeafView, lo, hi):
        """Exact batched range report -> (ids (Q, cap) flat row*C+slot
        padded with -1, counts (Q,)). ``cap`` is auto-sized: the output
        width is the converged power-of-two bucket (clamped to the
        gathered-slot count ``max_rows*C``), so every hit is always
        present."""
        rows, cols, _ = view.pts.shape
        key = ("range_list", lo.shape[0], lo.shape[-1], str(lo.dtype))
        max_rows, cap = self._buckets.get(key,
                                          (self.start_rows,
                                           self.start_cap))
        max_rows = min(_pow2(max_rows), _pow2(rows))
        # cap beyond the gathered slots is dead width (hits can't
        # exceed max_rows*C), so clamp — keeps the recorded bucket
        # equal to the actual output width when C isn't a power of two
        cap = min(_pow2(cap), max_rows * cols)
        obs.count("engine.plan_request")
        rounds = 0
        while True:
            fn = _range_list_closure(lo.shape[0], lo.shape[-1],
                                     str(lo.dtype), max_rows, cap)
            obs.costs.capture(
                fn, (view, lo, hi),
                f"range_list.q{lo.shape[0]}.r{max_rows}.c{cap}"
                f".v{rows}x{cols}")
            ids, cnt, rows_trunc = fn(view, lo, hi)
            with obs.span("engine.range_list.sync"):
                need_rows = max_rows < rows and bool(jnp.any(rows_trunc))
                max_cnt = int(jnp.max(cnt)) if cnt.size else 0
            need_cap = cap < max_cnt
            if not (need_rows or need_cap):
                self._buckets[key] = (max_rows, cap)
                obs.observe("engine.escalation_rounds", rounds)
                return ids, cnt
            rounds += 1
            obs.count("engine.escalation")
            if need_rows:
                max_rows = min(2 * max_rows, _pow2(rows))
            if need_cap:
                # counts are exact once rows fit, so jump straight to
                # the bucket that holds them
                cap = max(2 * cap, _pow2(max_cnt))
            cap = min(cap, max_rows * cols)

    # -- distributed queries (shard-merge step) ----------------------------

    def knn_dist(self, index, qpts, k: int, mesh, impl: str = "auto"):
        """Exact distributed kNN -> (d2, global flat ids) like
        :meth:`knn`: each shard answers locally (frontier or flat scan —
        unjitted inside shard_map), then the merge takes the top-k of
        per-shard top-k."""
        from . import distributed as D
        with obs.span("engine.knn", shards=mesh.shape[index.axis]):
            rows, cols = index.tree.pts.shape[-3], index.tree.pts.shape[-2]
            route, param = self.plan_knn(rows, cols, impl)
            obs.count("engine.plan_request")
            obs.count(f"engine.route.{route}")
            kw = ({"chunk": param} if route == "frontier"
                  else {"impl": route, "kernel": param})
            return D.knn(index, qpts, k, mesh, **kw)

    def range_count_dist(self, index, lo, hi, mesh):
        """Exact distributed range count -> counts (Q,): per-shard
        count + psum, re-run at escalated row buckets until no shard
        truncates."""
        from . import distributed as D
        with obs.span("engine.range_count", shards=mesh.shape[index.axis]):
            rows = index.tree.pts.shape[-3]
            key = ("range_count_dist", lo.shape[0], lo.shape[-1],
                   str(lo.dtype))
            max_rows = min(_pow2(self._buckets.get(key, self.start_rows)),
                           _pow2(rows))
            obs.count("engine.plan_request")
            rounds = 0
            while True:
                cnt, trunc = D.range_count(index, lo, hi, mesh,
                                           max_rows=max_rows)
                with obs.span("engine.range_count.sync"):
                    done = max_rows >= rows or not bool(jnp.any(trunc))
                if done:
                    self._buckets[key] = max_rows
                    obs.observe("engine.escalation_rounds", rounds)
                    return cnt
                rounds += 1
                obs.count("engine.escalation")
                max_rows = min(2 * max_rows, _pow2(rows))
