from __future__ import annotations

import functools

import jax

from ..frontier.prep import rescore
from .kernel import knn_pallas
from .ref import knn_ref

# Canonical impl spellings, shared verbatim with the engine's routing
# table and kernels/frontier: one vocabulary across layers.
KNN_KERNEL_IMPLS = ("auto", "pallas", "pallas-interpret", "ref")


def canonical_impl(impl: str) -> str:
    """Validate an impl spelling; reject legacy aliases loudly."""
    if impl == "interpret":
        raise ValueError(
            'impl="interpret" is not a spelling; use the canonical '
            '"pallas-interpret" (one name across engine and kernels)')
    if impl not in KNN_KERNEL_IMPLS:
        raise ValueError(
            f"unknown knn kernel impl {impl!r}; expected one of "
            f"{KNN_KERNEL_IMPLS}")
    return impl


def knn_bruteforce_impl(queries, points, ok, *, k: int, block_q: int = 128,
                        block_p: int = 512, impl: str = "auto"):
    """Unjitted :func:`knn_bruteforce` — use inside shard_map/pjit
    regions (nested ``jax.jit`` miscompiles there on some jax versions;
    see the query-engine note in ROADMAP.md)."""
    impl = canonical_impl(impl)
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    if impl == "ref":
        return knn_ref(queries, points, ok, k=k)
    _, ids = knn_pallas(queries, points, ok, k=k, block_q=block_q,
                        block_p=block_p,
                        interpret=(impl == "pallas-interpret"))
    # the oracle's distance expression, whatever the tile's rounding
    return rescore(points, queries, ids)


@functools.partial(jax.jit, static_argnames=("k", "block_q", "block_p",
                                             "impl"))
def knn_bruteforce(queries, points, ok, *, k: int, block_q: int = 128,
                   block_p: int = 512, impl: str = "auto"):
    return knn_bruteforce_impl(queries, points, ok, k=k, block_q=block_q,
                               block_p=block_p, impl=impl)
