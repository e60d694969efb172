"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.jpeg import tables as T

IDCT64 = T.idct64_matrix().astype(np.float32)


def idct8x8(x: jax.Array) -> jax.Array:
    """x: [N, 64] f32 dequantized coefficient rows -> spatial rows."""
    return x @ jnp.asarray(IDCT64).T


def dequant_idct(x: jax.Array, q: jax.Array) -> jax.Array:
    """x: [N, 64] raw coefficients; q: [64] quant table row."""
    return (x * q[None, :]) @ jnp.asarray(IDCT64).T + 128.0


def decode_batch(x: jax.Array, qidx: jax.Array, qtab: jax.Array) -> jax.Array:
    """x: [N, 64] raw rows; qidx: [N] i32 table index; qtab: [T, 64]."""
    return (x * qtab[qidx]) @ jnp.asarray(IDCT64).T + 128.0


def ycbcr2rgb(y: jax.Array, cb: jax.Array, cr: jax.Array):
    r = y + 1.402 * (cr - 128.0)
    g = y - 0.344136 * (cb - 128.0) - 0.714136 * (cr - 128.0)
    b = y + 1.772 * (cb - 128.0)
    return r, g, b


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True) -> jax.Array:
    """Oracle for the flash kernel. q/k/v: [BH, S, D]."""
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / (q.shape[-1] ** 0.5)
    if causal:
        S = q.shape[1]
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
