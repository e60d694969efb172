"""Pallas TPU kernel: fused dequantize + 8x8 IDCT + level shift.

One VMEM round-trip for the whole post-entropy block transform: coefficient
rows are scaled by the (VMEM-resident) quant table, hit the MXU through the
Kronecker IDCT matrix, and leave as level-shifted samples — the unfused jnp
pipeline writes the dequantized and IDCT'd intermediates back to HBM twice.
Samples are not clamped here: like every other engine, the colour
conversion sees the unclamped planes and only the RGB output is clamped.
Clamping each plane first moves pixels by up to 6 against ``numpy-ref`` on
ImageNet-sized images.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_N = 512


def _dequant_idct_kernel(x_ref, q_ref, m_ref, o_ref):
    deq = x_ref[...] * q_ref[...]            # (TILE_N,64) * (1,64) broadcast
    pix = jnp.dot(deq, m_ref[...].T, preferred_element_type=jnp.float32)
    o_ref[...] = pix + 128.0


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequant_idct_pallas(x: jax.Array, q: jax.Array, m: jax.Array, *,
                        interpret: bool = False) -> jax.Array:
    """x: [N, 64] f32 raw coefficients; q: [1, 64] quant row; m: [64, 64]."""
    n = x.shape[0]
    assert n % TILE_N == 0, n
    grid = (n // TILE_N,)
    return pl.pallas_call(
        _dequant_idct_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_N, 64), lambda i: (i, 0)),
            pl.BlockSpec((1, 64), lambda i: (0, 0)),
            pl.BlockSpec((64, 64), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_N, 64), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 64), jnp.float32),
        interpret=interpret,
    )(x, q, m)
