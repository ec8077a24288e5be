"""Per-kernel allclose vs the pure-jnp oracle, across shape/dtype sweeps.

Kernels execute in interpret mode (CPU container; TPU is the lowering
target — see DESIGN.md)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.bbox import ops as bbox_ops
from repro.kernels.flash_attn import ops as fa_ops
from repro.kernels.flash_attn.ref import attention_ref
from repro.kernels.knn import ops as knn_ops
from repro.kernels.morton import ops as morton_ops
from repro.kernels.sieve import ops as sieve_ops
from repro.kernels.sieve.ref import bucket_ids_ref


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,Hq,Hkv,Sq,Skv,d", [
    (1, 2, 2, 64, 64, 32),     # MHA square
    (2, 4, 2, 64, 64, 32),     # GQA
    (1, 4, 1, 32, 128, 16),    # MQA decode-ish (suffix queries)
    (1, 2, 2, 48, 80, 32),     # ragged (non-multiple of block)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(B, Hq, Hkv, Sq, Skv, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Hq, Sq, d), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, Skv, d), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, Skv, d), dtype)
    got = fa_ops.attention(q, k, v, causal=True, impl="interpret",
                           block_q=32, block_k=32)
    want = attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("window", [16, 64])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 2, 96, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 96, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 96, 32), jnp.float32)
    got = fa_ops.attention(q, k, v, causal=True, window=window,
                           impl="interpret", block_q=32, block_k=32)
    want = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


def test_flash_attention_non_causal():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 2, 64, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 64, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 64, 32), jnp.float32)
    got = fa_ops.attention(q, k, v, causal=False, impl="interpret",
                           block_q=32, block_k=32)
    want = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


# ---------------------------------------------------------------------------
# morton
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dim,bits,n", [(2, 15, 1000), (2, 16, 64),
                                        (3, 10, 513)])
def test_morton_kernel(dim, bits, n):
    rng = np.random.default_rng(0)
    pts = rng.integers(0, 1 << 20, size=(n, dim)).astype(np.int32)
    got = morton_ops.morton_encode(jnp.asarray(pts), bits=bits,
                                   coord_bits=20, impl="interpret")
    want = morton_ops.morton_encode(jnp.asarray(pts), bits=bits,
                                    coord_bits=20, impl="ref")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# sieve
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dim,lam,n,dtype", [
    (2, 3, 2048, jnp.int32), (2, 3, 1000, jnp.float32),
    (3, 2, 513, jnp.int32), (2, 2, 4096, jnp.float32)])
def test_sieve_histogram_kernel(dim, lam, n, dtype):
    rng = np.random.default_rng(1)
    if dtype == jnp.float32:
        pts = rng.random((n, dim)).astype(np.float32)
        lo = np.zeros((n, dim), np.float32)
        hi = np.ones((n, dim), np.float32)
    else:
        pts = rng.integers(0, 1 << 20, size=(n, dim)).astype(np.int32)
        lo = np.zeros((n, dim), np.int32)
        hi = np.full((n, dim), 1 << 20, np.int32)
    got = sieve_ops.sieve_histogram(jnp.asarray(pts), jnp.asarray(lo),
                                    jnp.asarray(hi), lam=lam, block_n=256,
                                    impl="interpret")
    want = sieve_ops.sieve_histogram(jnp.asarray(pts), jnp.asarray(lo),
                                     jnp.asarray(hi), lam=lam, block_n=256,
                                     impl="ref")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sieve_partition_is_stable_counting_sort():
    rng = np.random.default_rng(2)
    n = 3000
    pts = rng.integers(0, 1 << 20, size=(n, 2)).astype(np.int32)
    lo = jnp.zeros((n, 2), jnp.int32)
    hi = jnp.full((n, 2), 1 << 20, jnp.int32)
    dest, bucket, offsets = sieve_ops.sieve_partition(
        jnp.asarray(pts), lo, hi, lam=3, block_n=512, impl="ref")
    dest, bucket = np.asarray(dest), np.asarray(bucket)
    # dest is a permutation
    assert len(np.unique(dest)) == n
    # equal buckets keep input order (stability) and are contiguous
    out_bucket = np.empty(n, np.int32)
    out_src = np.empty(n, np.int64)
    out_bucket[dest] = bucket
    out_src[dest] = np.arange(n)
    assert (np.diff(out_bucket) >= 0).all()
    for b in np.unique(bucket):
        srcs = out_src[out_bucket == b]
        assert (np.diff(srcs) > 0).all()
    # offsets match bucket boundaries
    want_off = np.searchsorted(out_bucket, np.arange(64))
    np.testing.assert_array_equal(np.asarray(offsets), want_off)


def test_sieve_buckets_match_porth_convention():
    """The sieve kernel's comparison-based buckets equal Morton bits."""
    rng = np.random.default_rng(3)
    n = 512
    pts = rng.integers(0, 1 << 6, size=(n, 2)).astype(np.int32)
    lo = jnp.zeros((n, 2), jnp.int32)
    hi = jnp.full((n, 2), 1 << 6, jnp.int32)
    got = np.asarray(bucket_ids_ref(jnp.asarray(pts), lo, hi, lam=3))
    from repro.core import sfc
    want = np.asarray(sfc.morton_encode(jnp.asarray(pts).astype(jnp.uint32),
                                        6)) >> 6  # top 3 levels = 6 bits
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# knn
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("Q,N,dim,k", [(64, 500, 2, 8), (33, 1024, 3, 4),
                                       (128, 256, 2, 16)])
def test_knn_kernel(Q, N, dim, k):
    rng = np.random.default_rng(4)
    qs = rng.random((Q, dim)).astype(np.float32)
    ps = rng.random((N, dim)).astype(np.float32)
    ok = rng.random(N) > 0.1
    d_got, i_got = knn_ops.knn_bruteforce(
        jnp.asarray(qs), jnp.asarray(ps), jnp.asarray(ok), k=k,
        block_q=32, block_p=128, impl="pallas-interpret")
    d_want, i_want = knn_ops.knn_bruteforce(
        jnp.asarray(qs), jnp.asarray(ps), jnp.asarray(ok), k=k, impl="ref")
    np.testing.assert_allclose(np.asarray(d_got), np.asarray(d_want),
                               rtol=1e-4, atol=1e-5)


def test_knn_kernel_rejects_legacy_interpret_alias():
    """One canonical spelling across layers: "interpret" must fail loudly
    at the kernel boundary (the engine rejects it too)."""
    q = jnp.zeros((4, 2), jnp.float32)
    p = jnp.zeros((8, 2), jnp.float32)
    ok = jnp.ones(8, bool)
    with pytest.raises(ValueError, match="pallas-interpret"):
        knn_ops.knn_bruteforce_impl(q, p, ok, k=2, impl="interpret")
    with pytest.raises(ValueError, match="unknown knn kernel impl"):
        knn_ops.knn_bruteforce_impl(q, p, ok, k=2, impl="mxu")


# ---------------------------------------------------------------------------
# fused frontier knn
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("R,C,dim,Q,k,bq,bp,entries", [
    (37, 16, 2, 33, 8, 8, 64, None),      # ragged everything
    (64, 8, 3, 16, 4, 16, 128, None),     # 3-d, whole blocks
    (5, 4, 2, 7, 32, 8, 8, None),         # k > live points
    (96, 8, 2, 40, 6, 8, 16, 15),         # 3 steps per launch, 16 launches
])
def test_frontier_kernel_interpret_matches_ref(R, C, dim, Q, k, bq, bp,
                                               entries, monkeypatch):
    """Interpret-mode fused kernel is bit-identical to its jnp mirror:
    same prep, same tile expressions, same visit prefix — also when the
    scalar-prefetch order is cut into many launches (``entries``)."""
    from repro.kernels.frontier import kernel, knn_frontier_impl

    if entries is not None:
        monkeypatch.setattr(kernel, "PREFETCH_ENTRIES", entries)

    rng = np.random.default_rng(11)
    pts = jnp.asarray(rng.integers(0, 1 << 10, (R, C, dim)), jnp.int32)
    valid = jnp.asarray(rng.random((R, C)) > 0.2)
    active = jnp.asarray(rng.random(R) > 0.1)
    lo = jnp.where(valid[..., None], pts, jnp.int32(1 << 30)).min(axis=1)
    hi = jnp.where(valid[..., None], pts, jnp.int32(-1)).max(axis=1)
    q = jnp.asarray(rng.integers(0, 1 << 10, (Q, dim)), jnp.int32)

    args = (pts, valid, active, lo, hi, q)
    d_ref, i_ref = knn_frontier_impl(*args, k=k, impl="ref",
                                     block_q=bq, block_p=bp)
    d_int, i_int = knn_frontier_impl(*args, k=k, impl="pallas-interpret",
                                     block_q=bq, block_p=bp)
    np.testing.assert_array_equal(np.asarray(d_int), np.asarray(d_ref))
    np.testing.assert_array_equal(np.asarray(i_int), np.asarray(i_ref))


def test_frontier_kernel_rejects_legacy_interpret_alias():
    from repro.kernels.frontier import knn_frontier_impl

    z = jnp.zeros((4, 4, 2), jnp.int32)
    with pytest.raises(ValueError, match="pallas-interpret"):
        knn_frontier_impl(z, jnp.ones((4, 4), bool), jnp.ones(4, bool),
                          z[:, 0], z[:, 0], z[:, 0], k=2, impl="interpret")


# ---------------------------------------------------------------------------
# bbox
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("R,C,dim", [(100, 16, 2), (257, 64, 3)])
def test_bbox_kernel(R, C, dim):
    rng = np.random.default_rng(5)
    pts = rng.random((R, C, dim)).astype(np.float32)
    valid = rng.random((R, C)) > 0.3
    lo_g, hi_g = bbox_ops.row_bbox(jnp.asarray(pts), jnp.asarray(valid),
                                   block_r=64, impl="interpret")
    lo_w, hi_w = bbox_ops.row_bbox(jnp.asarray(pts), jnp.asarray(valid),
                                   impl="ref")
    np.testing.assert_allclose(np.asarray(lo_g), np.asarray(lo_w))
    np.testing.assert_allclose(np.asarray(hi_g), np.asarray(hi_w))
