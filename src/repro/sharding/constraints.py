"""Activation sharding constraints (MaxText-style logical axes).

Why: FSDP puts the "data" axis on weight contraction dims. Without
activation pins, GSPMD may resolve the x@W ambiguity the wrong way —
replicate the *batch* across data ranks and partial-sum the output
(measured: 16x attention FLOPs + TB-scale gather all-reduces on the
train cells). Pinning activations to batch-sharded forces the intended
FSDP resolution: gather the (small) weight shard, keep tokens sharded.

All helpers no-op when no ambient mesh is set (single-device tests) and
silently drop axes that don't exist or don't divide — the same model
code runs everywhere. Launchers call :func:`set_ambient_mesh` (dryrun
does it per cell).
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

TP = "model"
BATCH_AXES = ("pod", "data")


def set_ambient_mesh(mesh):
    """Make ``mesh`` ambient for :func:`constrain`
    (``jax.sharding.set_mesh``). Process-lifetime: launcher use only."""
    jax.sharding.set_mesh(mesh)


def _mesh():
    am = jax.sharding.get_abstract_mesh()
    if am is None or not am.axis_names:
        return None
    return am


def constrain(x, *spec):
    """with_sharding_constraint that validates axes against the ambient
    mesh and dim divisibility; returns x unchanged when impossible."""
    am = _mesh()
    if am is None:
        return x
    shape = dict(am.shape)
    clean = []
    for i, s in enumerate(spec):
        if s is None:
            clean.append(None)
            continue
        parts = tuple(p for p in (s if isinstance(s, tuple) else (s,))
                      if p in shape)
        n = 1
        for p in parts:
            n *= shape[p]
        if parts and n > 0 and x.shape[i] % n == 0:
            clean.append(parts if len(parts) > 1 else parts[0])
        else:
            clean.append(None)
    return jax.lax.with_sharding_constraint(x, P(*clean))


def batch_axes():
    am = _mesh()
    if am is None:
        return ()
    return tuple(a for a in BATCH_AXES if a in am.axis_names)


def bsd(x):
    """(batch, seq, d_model) activations: batch over DP axes."""
    return constrain(x, batch_axes() or None, None, None)


def sp_boundary(x):
    """Sequence-parallel layer-group boundary: (batch, S/tp, D).

    The lax.scan carry at group boundaries is exactly what remat saves;
    sharding its sequence dim over "model" cuts saved-activation HBM by
    tp (enabling 4-8x fewer microbatches, which scales down the
    per-microbatch gradient reduce traffic by the same factor). Exit is
    a comm-free local slice; re-entry is a (B*S*D/tp)-operand
    all-gather — ~1/tp of the all-reduce it stands next to."""
    return constrain(x, batch_axes() or None, TP, None)


def bshd(x, head_axis=TP):
    """(batch, seq|heads, heads|seq, hd): pin batch + heads."""
    return constrain(x, batch_axes() or None, None, head_axis, None)
