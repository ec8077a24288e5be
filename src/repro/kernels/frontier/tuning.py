"""Tile defaults for the fused frontier kernel.

These come from ``benchmarks/roofline.py --block-sweep`` (achieved GB/s
per (block_q, block_p) cell is recorded as obs counters and the chosen
cell is emitted in ``results/roofline.json`` under ``block_sweep``), not
from guesses.  Re-run the sweep and update here when the kernel or the
smoke-scale workload changes:

    PYTHONPATH=src python benchmarks/roofline.py --block-sweep --json
"""

from __future__ import annotations

# impl -> (block_q, block_p).  block_p is a *point* budget per tile; prep
# rounds it to whole rows (block_r = block_p // C, P = block_r * C).
_DEFAULT_TILES = {
    # CPU while_loop spelling: small query blocks keep the early exit
    # tight (one straggler query can't pin a whole block on the scan).
    "ref": (8, 512),
    # kernel spellings: 128-query tiles amortize the point-tile reads
    # (never swept on a TPU).
    "pallas": (128, 512),
    "pallas-interpret": (16, 512),
}


def tiles(impl: str, block_q=None, block_p=None):
    """Resolve (block_q, block_p), honoring explicit overrides."""
    dq, dp = _DEFAULT_TILES[impl]
    return int(block_q or dq), int(block_p or dp)
