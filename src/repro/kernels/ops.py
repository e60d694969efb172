"""jit'd public wrappers around the Pallas kernels.

Handles padding to tile multiples and dtype casts. On a TPU the kernels
lower through Mosaic; on the CPU backend, where the tests run, they run in
Pallas interpret mode.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from repro.jpeg import tables as T
from repro.kernels.decode_batch import TILE_N as DB_TILE, decode_batch_pallas
from repro.kernels.dequant_idct import TILE_N as DQ_TILE, dequant_idct_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.idct8x8 import TILE_N, idct8x8_pallas
from repro.kernels.ycbcr2rgb import LANES, TILE_R, ycbcr2rgb_pallas

_IDCT64 = jnp.asarray(T.idct64_matrix().astype(np.float32))


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _pad_rows(x, mult):
    n = x.shape[0]
    pad = (-n) % mult
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
    return x, n


def idct8x8(x) -> jax.Array:
    """[N, 64] f32 dequantized coefficients -> [N, 64] spatial rows."""
    x = jnp.asarray(x, jnp.float32)
    xp, n = _pad_rows(x, TILE_N)
    out = idct8x8_pallas(xp, _IDCT64, interpret=_interpret())
    return out[:n]


def dequant_idct(x, q) -> jax.Array:
    """[N, 64] raw coefficients + [64] quant row -> level-shifted rows."""
    x = jnp.asarray(x, jnp.float32)
    q = jnp.asarray(q, jnp.float32).reshape(1, 64)
    xp, n = _pad_rows(x, DQ_TILE)
    out = dequant_idct_pallas(xp, q, _IDCT64, interpret=_interpret())
    return out[:n]


def decode_batch(x, qidx, qtables) -> jax.Array:
    """Batched fused dequant+IDCT: [N, 64] rows + [N] per-row table index
    + [T, 64] quant tables -> [N, 64] level-shifted rows (one launch for a
    whole micro-batch; rows from different images interleave freely)."""
    x = jnp.asarray(x, jnp.float32)
    qidx = jnp.asarray(qidx, jnp.int32).reshape(-1, 1)
    qtables = jnp.asarray(qtables, jnp.float32)
    if qtables.ndim != 2 or qtables.shape[1] != 64:
        qtables = qtables.reshape(-1, 64)
    xp, n = _pad_rows(x, DB_TILE)
    qip, _ = _pad_rows(qidx, DB_TILE)          # pad rows index table 0
    out = decode_batch_pallas(xp, qip, qtables, _IDCT64,
                              interpret=_interpret())
    return out[:n]


def flash_attention(q, k, v, *, causal: bool = True,
                    blk_q: int = 256) -> jax.Array:
    """[B, S, H, D] x [B, S, KV, D]^2 -> [B, S, H, D] fused attention.

    GQA handled by repeating KV heads; heads flattened into the grid batch.
    """
    import jax.numpy as jnp
    B, S, H, D = q.shape
    KV = k.shape[2]
    rep = H // KV
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    blk = blk_q
    while S % blk:
        blk //= 2
    out = flash_attention_pallas(qf, kf, vf, causal=causal,
                                 interpret=_interpret(), blk_q=max(blk, 1))
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def ycbcr2rgb(y, cb, cr) -> jax.Array:
    """[H, W] f32 planes -> [H, W, 3] f32 RGB."""
    y = jnp.asarray(y, jnp.float32)
    h, w = y.shape
    npix = h * w
    rows = -(-npix // LANES)

    def prep(p):
        flat = jnp.asarray(p, jnp.float32).reshape(-1)
        pad = rows * LANES - npix
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
        flat = flat.reshape(rows, LANES)
        flat, _ = _pad_rows(flat, TILE_R)
        return flat

    r, g, b = ycbcr2rgb_pallas(prep(y), prep(cb), prep(cr),
                               interpret=_interpret())

    def un(p):
        return p.reshape(-1)[:npix].reshape(h, w)

    return jnp.stack([un(r), un(g), un(b)], axis=-1)
