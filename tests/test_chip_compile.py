"""AOT compiles of the served path for a TPU v5e chip, without the chip.

The TPU compiler ships with jaxlib's TPU support and compiles for a
*described* topology, so these tests catch what interpret mode cannot:
Pallas constructs Mosaic refuses to lower, blocks that break the (8, 128)
tiling, scalar memory (SMEM) overflows, and programs that do not fit the
chip's 16 GB of HBM. Nothing runs; a pass here is not a chip run.

The topology is described inside a module fixture (never at import), so
every xdist worker collects the same tests and only the worker that runs
this file loads the TPU library.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import engine as E
from repro.core import index as I
from repro.core import porth, spac
from repro.core.queries import LeafView

HBM_BYTES = 16e9          # one v5e chip
SMOKE_N = 4_000_000       # chip_smoke.py's bootstrap size
K = 10
Q = 256


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _view(rows, cols, dim, sharding):
    return LeafView(_sds((rows, cols, dim), jnp.int32, sharding),
                    _sds((rows, cols), bool, sharding),
                    _sds((rows,), bool, sharding),
                    _sds((rows, dim), jnp.int32, sharding),
                    _sds((rows, dim), jnp.int32, sharding))


# rows x 64 slots on either side of the engine's flat-scan budget (2^15)
@pytest.mark.parametrize("rows,route", [(511, "flat"),
                                        (513, "pallas-frontier")])
@pytest.mark.parametrize("dim", [2, 3])
def test_knn_kernels_compile_for_v5e(one_chip, rows, route, dim):
    """The planner picks ``route`` at this size, and that route's
    compiled Pallas kernel lowers to one Mosaic custom call."""
    cols = 64
    planned, _ = E.QueryEngine().plan_knn(rows, cols)
    assert planned == route, (rows * cols, planned)
    fn = E._knn_closure(Q, dim, "int32", K, route, "pallas")
    compiled = fn.lower(_view(rows, cols, dim, one_chip),
                        _sds((Q, dim), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _smoke_rows(kind):
    """Row capacity of ``kind`` sized for chip_smoke's trace: 1% insert
    batches, a quarter of each retired, 5 steps."""
    batch = SMOKE_N // 100
    max_live = SMOKE_N + 5 * (batch - batch // 4)
    return I.capacity_for(max_live, 32, I.get_backend(kind).cap_slack)


def test_frontier_kernel_fits_smem_at_smoke_size(one_chip):
    """At porth's smoke size (129,696 groups of 512 slots) the visit
    order no longer fits the 1 MiB SMEM in one launch; the kernel cuts
    it into launches and compiles."""
    rows = _smoke_rows("porth")
    fn = E._knn_closure(Q, 2, "int32", K, "pallas-frontier", "pallas")
    fn.lower(_view(rows, 64, 2, one_chip),
             _sds((Q, 2), jnp.int32, one_chip)).compile()


@pytest.mark.parametrize("kind", ["spac-h", "porth"])
def test_delete_fits_hbm_at_smoke_size(one_chip, kind):
    """A 10,000-point delete from a 4M-point tree sized for the smoke
    trace fits the chip: slot ranks are computed for the touched rows
    only, not the whole (R, C, C) comparison."""
    rows = _smoke_rows(kind)
    pts = jax.ShapeDtypeStruct((SMOKE_N, 2), jnp.int32)
    msk = jax.ShapeDtypeStruct((SMOKE_N,), bool)
    if kind == "porth":
        root = jax.ShapeDtypeStruct((2,), jnp.int32)
        tree = jax.eval_shape(
            lambda p, m, lo, hi: porth.build_impl(
                p, lo, hi, m, phi=32, lam=3, rounds=5, capacity_rows=rows),
            pts, msk, root, root)
    else:
        tree = jax.eval_shape(
            lambda p, m: spac.build_impl(p, m, phi=32, curve="hilbert",
                                         bits=16, coord_bits=20,
                                         capacity_rows=rows), pts, msk)
    tree = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), tree)
    m = SMOKE_N // 400
    fn = I._update_closure(kind, "delete", m, 2, "int32", (), False)
    mem = fn.lower(tree, _sds((m, 2), jnp.int32, one_chip),
                   _sds((m,), bool, one_chip)).compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, (mem.argument_size_in_bytes,
                               mem.output_size_in_bytes,
                               mem.temp_size_in_bytes)
