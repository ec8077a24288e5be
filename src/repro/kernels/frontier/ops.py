"""Impl routing for the fused frontier kernel.

Canonical spellings only (the engine and the kernel layer share one
vocabulary — see ``kernels/knn/ops.py`` for the same rule on the flat
kernel):

* ``auto``             — ``pallas`` on TPU, ``ref`` elsewhere
* ``pallas``           — compiled Pallas TPU kernel
* ``pallas-interpret`` — same kernel under the Pallas interpreter (CPU CI)
* ``ref``              — jnp while_loop mirror, bit-identical to the kernel

``knn_frontier_impl`` is the unjitted spelling for use inside
``shard_map`` regions (the nested-jit miscompile — ROADMAP "Known
constraints"); ``knn_frontier`` is the jitted module-level alias.
"""

from __future__ import annotations

import jax

from repro.kernels.frontier import kernel, ref, tuning
from repro.kernels.frontier.prep import prepare, rescore

FRONTIER_IMPLS = ("auto", "pallas", "pallas-interpret", "ref")


def canonical_impl(impl: str) -> str:
    """Validate an impl spelling; reject legacy aliases loudly."""
    if impl == "interpret":
        raise ValueError(
            'impl="interpret" is not a spelling; use the canonical '
            '"pallas-interpret" (one name across engine and kernels)')
    if impl not in FRONTIER_IMPLS:
        raise ValueError(
            f"unknown frontier impl {impl!r}; expected one of "
            f"{FRONTIER_IMPLS}")
    return impl


def knn_frontier_impl(pts, valid, active, bbox_lo, bbox_hi, queries, *,
                      k: int, impl: str = "auto",
                      block_q=None, block_p=None):
    """Fused frontier kNN over leaf-view arrays; returns (d2, ids).

    ``ids`` are flat ``row * C + col`` candidate ids (-1 past the end),
    matching the chunked frontier in ``core/queries.py``; each query's
    hits come back sorted by ``(d2, id)``, re-scored with the direct
    ``|q - p|^2`` expression the chunked traversal computes
    (:func:`prep.rescore`).
    """
    impl = canonical_impl(impl)
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    bq, bp = tuning.tiles(impl, block_q, block_p)
    pr = prepare(pts, valid, active, bbox_lo, bbox_hi, queries,
                 block_q=bq, block_p=bp)
    if impl == "ref":
        d2, ids = ref.knn_frontier_ref(pr, k=k)
    else:
        d2, ids = kernel.knn_frontier_pallas(
            pr, k=k, interpret=(impl == "pallas-interpret"))
    q = queries.shape[0]
    return rescore(pts.reshape(-1, pts.shape[-1]), queries,
                   ids[:q][pr.inv])


knn_frontier = jax.jit(
    knn_frontier_impl,
    static_argnames=("k", "impl", "block_q", "block_p"))
