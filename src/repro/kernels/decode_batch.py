"""Pallas TPU kernel: batched fused dequant + 8x8 IDCT + level shift.

Generalizes ``dequant_idct.py`` from one quant row to a whole micro-batch:
the input is every block row of every batch member concatenated into
``[B*blocks, 64]``, plus a per-row index selecting which of the ``[T, 64]``
quant tables scales that row. The gather is expressed as a one-hot matmul
(``onehot(idx) @ qtables``) rather than a vector gather — the MXU-friendly
form that lowers cleanly through Mosaic; T is the batch's table count
(= micro-batch size), so the one-hot GEMM is a skinny ``[TILE_N, T]`` x
``[T, 64]`` — noise next to the ``[TILE_N, 64]`` x ``[64, 64]`` IDCT GEMM.

VMEM per grid step (TILE_N=512, T<=64): x 128 KiB + out 128 KiB + qidx
2 KiB + qtables <=16 KiB + IDCT matrix 16 KiB — same envelope as the
single-table kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_N = 512


def _decode_batch_kernel(x_ref, qi_ref, qt_ref, m_ref, o_ref):
    ids = qi_ref[...]                          # (TILE_N, 1) int32
    t = qt_ref.shape[0]
    tids = jax.lax.broadcasted_iota(jnp.int32, (1, t), 1)
    onehot = (ids == tids).astype(jnp.float32)            # (TILE_N, T)
    q = jnp.dot(onehot, qt_ref[...],
                preferred_element_type=jnp.float32)       # (TILE_N, 64)
    deq = x_ref[...] * q
    pix = jnp.dot(deq, m_ref[...].T, preferred_element_type=jnp.float32)
    o_ref[...] = pix + 128.0      # unclamped, as in dequant_idct.py


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_batch_pallas(x: jax.Array, qidx: jax.Array, qtab: jax.Array,
                        m: jax.Array, *, interpret: bool = False
                        ) -> jax.Array:
    """x: [N, 64] f32 raw coefficient rows (N multiple of TILE_N);
    qidx: [N, 1] i32 per-row quant-table index; qtab: [T, 64] quant rows;
    m: [64, 64] Kronecker IDCT matrix. -> [N, 64] level-shifted rows."""
    n = x.shape[0]
    t = qtab.shape[0]
    assert n % TILE_N == 0, n
    grid = (n // TILE_N,)
    return pl.pallas_call(
        _decode_batch_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_N, 64), lambda i: (i, 0)),
            pl.BlockSpec((TILE_N, 1), lambda i: (i, 0)),
            pl.BlockSpec((t, 64), lambda i: (0, 0)),
            pl.BlockSpec((64, 64), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_N, 64), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 64), jnp.float32),
        interpret=interpret,
    )(x, qidx, qtab, m)
