"""Oracles for every Pallas kernel (allclose targets in tests): pure jnp,
and a float64 numpy RandomResizedCrop resample."""
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


def pil_bilinear_weights(n_out: int, n_in: int) -> np.ndarray:
    """[n_out, n_in] float64 weights of PIL's antialiased bilinear resize
    of ``n_in`` pixels onto ``n_out`` (``Resample.c``, ``precompute_coeffs``):
    scale ``n_in / n_out``, support ``max(scale, 1)``, the triangle filter
    over the taps PIL's window admits, normalised to sum to 1."""
    scale = n_in / n_out
    support = max(scale, 1.0)
    centre = (np.arange(n_out) + 0.5) * scale
    y = np.arange(n_in)[None, :]
    lo = np.maximum((centre - support + 0.5).astype(np.int64), 0)[:, None]
    hi = np.minimum((centre + support + 0.5).astype(np.int64), n_in)[:, None]
    w = np.maximum(1.0 - np.abs(y + 0.5 - centre[:, None]) / support, 0.0)
    w[(y < lo) | (y >= hi)] = 0.0
    return w / w.sum(axis=1, keepdims=True)


def rrc_resample(img: np.ndarray, box, size=(224, 224)) -> np.ndarray:
    """Oracle for ``rrc_resample``: [H, W, C] uint8 and one crop box (top,
    left, height, width, flip) -> [*size, C] uint8. Crops, resamples each
    axis with ``pil_bilinear_weights`` in float64, flips left-right if
    asked, rounds half up and clips."""
    top, left, h, w, flip = (int(v) for v in box)
    crop = img[top:top + h, left:left + w].astype(np.float64)
    out = np.einsum("ij,jkc->ikc", pil_bilinear_weights(size[0], h), crop)
    out = np.einsum("lk,ikc->ilc", pil_bilinear_weights(size[1], w), out)
    if flip:
        out = out[:, ::-1]
    return np.clip(np.floor(out + 0.5), 0, 255).astype(np.uint8)
