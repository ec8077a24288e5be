"""Mixture-of-Experts with capacity-based sort dispatch (EP-friendly).

Two dispatch paths, numerically identical (tests/test_models.py):

* dense (`_moe_group`) — token-expert pairs ranked per expert; the
  first C survive; activations gathered into an (E, C, D) buffer and
  hit the expert matmuls as one batched einsum. Under pjit the
  cross-expert scatter/gather lowers to whatever GSPMD picks — on the
  production mesh it picks gather-all-reduces (measured: 27% of qwen3's
  train collective bytes, §Perf cell A).
* shard_map (`_moe_group_shard_map`, default when an ambient mesh with
  a "model" axis is set) — manual expert parallelism: each model rank
  owns E/tp experts, routes its replicated token block to *local*
  experts only (the sieve/bucket idea from the paper's sieve primitive:
  rank-within-bucket packing, fixed capacity), computes, and one psum
  over "model" combines the partial outputs. Comm per group = exactly
  one (Tg, D) all-reduce — no gathers, no scatters.

Tokens are processed in groups (cfg.moe_group) scanned sequentially so
the (E, C, D) buffer stays bounded (VMEM/HBM footprint knob for §Perf).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.sharding import constraints as cstr

from .layers import rms_norm

P = jax.sharding.PartitionSpec


def _route(xg, wr, K):
    """Router: returns (topw (Tg,K) normalized, topi (Tg,K) int32)."""
    logits = jnp.einsum("td,de->te", xg, wr).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, K)
    topw = topw / jnp.maximum(jnp.sum(topw, -1, keepdims=True), 1e-9)
    return topw.astype(xg.dtype), topi


def _rank_in_expert(flat_e, n_buckets):
    """Stable rank of each pair within its expert bucket (sieve-style)."""
    n = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True).astype(jnp.int32)
    inv = jnp.argsort(order).astype(jnp.int32)
    sorted_e = flat_e[order]
    idx = jnp.arange(n, dtype=jnp.int32)
    change = jnp.concatenate([jnp.ones((1,), bool),
                              sorted_e[1:] != sorted_e[:-1]])
    first = jax.lax.associative_scan(jnp.maximum,
                                     jnp.where(change, idx, 0))
    return (idx - first)[inv]


def _expert_ffn(xe, p):
    h1 = jnp.einsum("ecd,edf->ecf", xe, p["w1"])
    h3 = jnp.einsum("ecd,edf->ecf", xe, p["w3"])
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(h1) * h3, p["w2"])


def _moe_group(xg, p, cfg, moe):
    """Dense-dispatch path. xg: (Tg, D) -> (Tg, D)."""
    Tg, D = xg.shape
    E, K = moe.n_experts, moe.top_k
    C = max(1, int(Tg * K * moe.capacity_factor / E))

    topw, topi = _route(xg, p["wr"], K)
    flat_e = topi.reshape(-1)                                # (Tg*K,)
    rank = _rank_in_expert(flat_e, E)
    keep = rank < C
    slot = jnp.where(keep, flat_e * C + rank, E * C)         # E*C => drop
    idx = jnp.arange(Tg * K, dtype=jnp.int32)
    tok = idx // K
    xe = jnp.zeros((E * C, D), xg.dtype).at[slot].set(
        xg[tok], mode="drop").reshape(E, C, D)
    ye = _expert_ffn(xe, p).reshape(E * C, D)
    safe = jnp.minimum(slot, E * C - 1)
    yk = jnp.where(keep[:, None], ye[safe], 0).reshape(Tg, K, D)
    return jnp.einsum("tk,tkd->td", topw, yk)


def _moe_shard_map(h, p, cfg, moe, mesh):
    """Manual-EP path (full-manual shard_map over every mesh axis):
    tokens stay on their data rank, experts live on their model rank,
    the router runs on local tokens, local experts compute, and ONE
    psum over "model" combines partial outputs. h: (B, S, D) with B
    sharded over the DP axes; returns y (B, S, D) likewise."""
    E, K = moe.n_experts, moe.top_k
    axes = dict(zip(mesh.axis_names,
                    mesh.shape.values() if hasattr(mesh.shape, "values")
                    else mesh.shape))
    tp = axes["model"]
    El = E // tp
    dp = tuple(a for a in ("pod", "data") if a in axes)

    def local(h, wr, w1, w3, w2):
        Bl, S, D = h.shape
        hf = h.reshape(-1, D)
        T = hf.shape[0]
        Tg = min(cfg.moe_group, T)
        n_groups = (T + Tg - 1) // Tg
        hf = jnp.pad(hf, ((0, n_groups * Tg - T), (0, 0)))
        r = jax.lax.axis_index("model")

        def one(xg):
            C = max(1, int(Tg * K * moe.capacity_factor / E))
            topw, topi = _route(xg, wr, K)
            flat_e = topi.reshape(-1)
            mine = (flat_e // El) == r
            el = jnp.where(mine, flat_e % El, El)     # El => foreign
            rank = _rank_in_expert(jnp.where(mine, flat_e, E), E)
            keep = mine & (rank < C)
            slot = jnp.where(keep, el * C + rank, El * C)
            idx = jnp.arange(Tg * K, dtype=jnp.int32)
            xe = jnp.zeros((El * C + 1, D), xg.dtype).at[slot].set(
                xg[idx // K], mode="drop")[:-1].reshape(El, C, D)
            ye = _expert_ffn(xe, dict(w1=w1, w3=w3, w2=w2)
                             ).reshape(El * C, D)
            safe = jnp.minimum(slot, El * C - 1)
            yk = jnp.where(keep[:, None], ye[safe], 0).reshape(Tg, K, D)
            return jnp.einsum("tk,tkd->td", topw, yk)  # local experts

        y = jax.lax.map(one, hf.reshape(n_groups, Tg, D))
        y = y.reshape(-1, D)[:T].reshape(Bl, S, D)
        return jax.lax.psum(y, "model")               # ONLY collective

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(dp or None, None, None), P(), P("model"),
                                 P("model"), P("model")),
                       out_specs=P(dp or None, None, None), check_vma=False)
    return fn(h, p["wr"], p["w1"], p["w3"], p["w2"])


def moe_block(x, p, cfg):
    """x: (B, S, D), residual included."""
    B, S, D = x.shape
    moe = cfg.moe
    h = rms_norm(x, p["ln"], cfg.norm_eps)

    am = cstr._mesh()
    shape = dict(zip(am.axis_names,
                     am.shape.values() if hasattr(am.shape, "values")
                     else am.shape)) if am is not None else {}
    n_dp = 1
    for a in ("pod", "data"):
        n_dp *= shape.get(a, 1)
    # manual-EP pays one weight-reshard on shard_map entry; worth it
    # when many tokens amortize it (train/prefill), not for decode
    # (dense dispatch + GSPMD is near-free at B tokens/step).
    use_sm = (cfg.moe_shard_map and am is not None
              and "model" in shape
              and moe.n_experts % shape["model"] == 0
              and B % n_dp == 0
              and (B * S) // n_dp >= 512)
    if use_sm:
        y = _moe_shard_map(h, p, cfg, moe, am)
        return x + cstr.bsd(y)

    Tg = min(cfg.moe_group, B * S)
    hf = h.reshape(-1, D)
    T = hf.shape[0]
    n_groups = (T + Tg - 1) // Tg
    pad = n_groups * Tg - T
    hf = jnp.pad(hf, ((0, pad), (0, 0)))
    groups = hf.reshape(n_groups, Tg, D)
    y = jax.lax.map(lambda g: _moe_group(g, p, cfg, moe), groups)
    y = cstr.bsd(y.reshape(-1, D)[:T].reshape(B, S, D))
    return x + y


def init_moe(key, cfg, dtype):
    moe, D = cfg.moe, cfg.d_model
    E, F = moe.n_experts, moe.d_ff
    ks = jax.random.split(key, 4)
    return dict(
        ln=jnp.ones((D,), dtype),
        wr=jax.random.normal(ks[0], (D, E), dtype) * D ** -0.5,
        w1=jax.random.normal(ks[1], (E, D, F), dtype) * D ** -0.5,
        w3=jax.random.normal(ks[2], (E, D, F), dtype) * D ** -0.5,
        w2=jax.random.normal(ks[3], (E, F, D), dtype) * F ** -0.5,
    )
