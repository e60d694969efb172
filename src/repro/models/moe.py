"""Mixture-of-Experts FFN: shared experts + routed top-k.

Two interchangeable implementations:

* ``dense``  — oracle: loops over experts with exact (drop-free) top-k
  combine. Used by CPU tests and as the correctness reference.
* ``ep``     — production path: expert parallelism over the mesh's ``model``
  axis via ``shard_map`` with fixed-capacity dispatch — local scatter into
  per-destination buffers, ``all_to_all``, grouped expert matmul,
  ``all_to_all`` back, weighted combine (the DeepSeek-style EP pattern).
  Tokens are additionally sequence-sharded over the model axis when the
  sequence length divides it, which bounds the dispatch buffers.

Both return (y, aux_loss) where aux is the switch-style load-balance loss.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Params = Dict[str, Any]


def init_moe(key, cfg) -> Params:
    d = cfg.d_model
    E, ff = cfg.num_experts, cfg.moe_d_ff
    sh_ff = cfg.moe_d_ff * cfg.num_shared_experts
    ks = jax.random.split(key, 8)
    dt = jnp.dtype(cfg.dtype)
    s = lambda n: 1.0 / math.sqrt(n)
    p = {
        "router": jax.random.normal(ks[0], (d, E), jnp.float32) * s(d),
        "w1": jax.random.normal(ks[1], (E, d, ff), dt) * s(d),
        "w3": jax.random.normal(ks[2], (E, d, ff), dt) * s(d),
        "w2": jax.random.normal(ks[3], (E, ff, d), dt) * s(ff),
        "ln": jnp.zeros((d,), dt),
    }
    if cfg.num_shared_experts:
        p["sh_w1"] = jax.random.normal(ks[4], (d, sh_ff), dt) * s(d)
        p["sh_w3"] = jax.random.normal(ks[5], (d, sh_ff), dt) * s(d)
        p["sh_w2"] = jax.random.normal(ks[6], (sh_ff, d), dt) * s(sh_ff)
    return p


def _route(xt: jax.Array, router: jax.Array, k: int
           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """xt: [T, d] -> (gates [T,k], idx [T,k], aux scalar)."""
    logits = xt.astype(jnp.float32) @ router          # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # switch-style aux: E * sum_e f_e * P_e
    E = router.shape[1]
    f = jnp.mean(jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(1), axis=0)
    Pm = probs.mean(0)
    aux = E * jnp.sum(f * Pm)
    return gates.astype(xt.dtype), idx, aux


def _expert_ffn(h: jax.Array, w1, w3, w2) -> jax.Array:
    """h: [E, C, d] grouped through per-expert SwiGLU."""
    a = jnp.einsum("ecd,edf->ecf", h, w1)
    b = jnp.einsum("ecd,edf->ecf", h, w3)
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(a) * b, w2)


# --------------------------------------------------------------------------
# dense oracle
# --------------------------------------------------------------------------
def routed_dense(xt: jax.Array, p: Params, cfg) -> Tuple[jax.Array, jax.Array]:
    T, d = xt.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    gates, idx, aux = _route(xt, p["router"], k)

    def body(acc, e):
        w = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)     # [T]
        y = jax.nn.silu(xt @ p["w1"][e]) * (xt @ p["w3"][e]) @ p["w2"][e]
        return acc + y * w[:, None], None

    acc0 = jnp.zeros_like(xt)
    acc, _ = jax.lax.scan(body, acc0, jnp.arange(E))
    return acc, aux


# --------------------------------------------------------------------------
# expert-parallel shard_map path
# --------------------------------------------------------------------------
def routed_ep(x: jax.Array, p: Params, cfg, ctx) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, d] (globally sharded). EP over ctx.model_axis."""
    mesh = ctx.mesh
    M = ctx.model_axis_size
    E, k = cfg.num_experts, cfg.experts_per_token
    assert E % M == 0, (E, M)
    B, S, d = x.shape
    seq_shard = S % M == 0 and S >= M
    tok_spec = P(ctx.data_axes, ctx.model_axis if seq_shard else None, None)

    def local_fn(xl, router, w1, w3, w2):
        bl, sl, _ = xl.shape
        T = bl * sl
        xt = xl.reshape(T, d)
        gates, idx, aux = _route(xt, router, k)
        aux = jax.lax.pmean(aux, ctx.model_axis)
        cap = max(1, int(math.ceil(T * k / E * ctx.capacity_factor)))

        ids = idx.reshape(-1)                                  # [T*k]
        gts = gates.reshape(-1)
        onehot = jax.nn.one_hot(ids, E, dtype=jnp.int32)       # [T*k, E]
        pos = jnp.take_along_axis(jnp.cumsum(onehot, 0) - 1,
                                  ids[:, None], axis=1)[:, 0]  # [T*k]
        keep = pos < cap
        posc = jnp.minimum(pos, cap - 1)
        vals = jnp.repeat(xt, k, axis=0) * keep[:, None].astype(xt.dtype)
        buf = jnp.zeros((E, cap, d), xt.dtype).at[ids, posc].add(vals)

        # dispatch: [E, cap, d] -> [E/M, M*cap, d] rows for my local experts
        recv = jax.lax.all_to_all(buf, ctx.model_axis,
                                  split_axis=0, concat_axis=1, tiled=True)
        hidden = _expert_ffn(recv, w1, w3, w2)
        # return: [E/M, M*cap, d] -> [E, cap, d] rows of my tokens
        back = jax.lax.all_to_all(hidden, ctx.model_axis,
                                  split_axis=1, concat_axis=0, tiled=True)
        out_rows = back[ids, posc] * (keep.astype(xt.dtype) * gts)[:, None]
        y = out_rows.reshape(T, k, d).sum(axis=1)
        return y.reshape(bl, sl, d), aux

    y, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(tok_spec, P(None, None), P(ctx.model_axis, None, None),
                  P(ctx.model_axis, None, None), P(ctx.model_axis, None, None)),
        out_specs=(tok_spec, P()),
        check_vma=False,
    )(x, p["router"], p["w1"], p["w3"], p["w2"])
    return y, aux


# --------------------------------------------------------------------------
# full MoE block: shared experts + routed + residual
# --------------------------------------------------------------------------
def moe_block(p: Params, x: jax.Array, cfg, ctx) -> Tuple[jax.Array, jax.Array]:
    from repro.models.layers import rms_norm, swiglu
    B, S, d = x.shape
    xn = rms_norm(x, p["ln"], cfg.norm_eps)
    y = jnp.zeros_like(xn)
    if cfg.num_shared_experts:
        y = y + swiglu(xn, p["sh_w1"], p["sh_w3"], p["sh_w2"])
    if ctx.moe_impl == "ep" and ctx.mesh is not None:
        routed, aux = routed_ep(xn, p, cfg, ctx)
    else:
        routed, aux = routed_dense(xn.reshape(B * S, d), p, cfg)
        routed = routed.reshape(B, S, d)
    return x + y + routed, aux
