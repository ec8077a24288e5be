"""Fused frontier-kNN kernel: on-chip traversal with exact distances.

The chunked frontier in ``core/queries.py`` pays a per-query ``argsort``
over all R rows plus gather-heavy ``while_loop`` chunk bodies.  This
package fuses that traversal into a kernel:

* rows are packed into contiguous *groups* of ``block_r`` rows
  (``prep.py``), so the traversal order is a per-query-*block* argsort
  over G = ceil(R / block_r) group lower bounds — not R rows per query;
  points are stored lane-dense, ``(D + 1, G * P)`` (coordinates, then
  validity);
* a tile's distances are the direct ``sum_d (q_d - p_d)^2`` on the VPU,
  exact whenever the squared differences are f32-exact, whatever the
  coordinates' magnitude (a D <= 3 contraction would use a sliver of
  the MXU anyway); the k hits are then re-scored with the chunked
  frontier's own expression (``prep.rescore``), so every route returns
  the same distances;
* the running top-k merge is k rounds of min-extraction (lowest id on
  ties) in VMEM scratch, and the bbox-lower-bound early exit is a
  per-block ``pl.when`` skip plus a ``while_loop`` over launches that
  stops at the first chunk no block can reach (``kernel.py``);
* ``ref.py`` is a pure-jnp ``while_loop`` mirror sharing the same prep
  and the same tile expressions — bit-identical to the kernel in
  interpret mode and the fast CPU spelling behind ``impl="auto"``.

Routing lives in ``ops.py`` (canonical spellings: ``auto`` / ``pallas`` /
``pallas-interpret`` / ``ref``); tile defaults in ``tuning.py`` come from
``benchmarks/roofline.py --block-sweep``, not guesses.
"""

from repro.kernels.frontier.ops import (  # noqa: F401
    FRONTIER_IMPLS,
    knn_frontier,
    knn_frontier_impl,
)
