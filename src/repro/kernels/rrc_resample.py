"""Pallas TPU kernel: a batch of RandomResizedCrop resamples, one crop box
per image, as two matrix products per channel.

Each image gets ``out = Wy @ X @ Wx.T`` per channel, where ``X`` is the
whole padded plane and ``Wy`` ``[out_h, H_pad]``, ``Wx`` ``[out_w, W_pad]``
are PIL's antialiased bilinear weights for that image's crop, built in the
kernel from its box (``top, left, height, width, flip``, scalar-prefetched
into SMEM). The crop lives only in the weight values: taps outside the box
weigh 0, so the compiled program depends on (batch, padded size, output
size) alone and one program serves every crop.

PIL's filter, per axis, for a crop of ``n`` pixels onto ``m``: scale
``s = n / m``, support ``f = max(s, 1)``, output pixel ``i`` centred at
``c = (i + 0.5) * s`` in the crop, tap ``y`` (a crop pixel) weighted by the
triangle ``max(0, 1 - |y + 0.5 - c| / f)``, the taps normalised to sum to 1.
A flip reverses the output columns, so it is folded into ``Wx``'s rows.

Pixels travel as int8 holding ``value - 128`` (Mosaic casts int8, not
uint8, to float): each row of weights sums to 1, so the products of the
shifted planes are the shifted result, rounded half up and clipped to
[-128, 127]. Arrays are float32, the dots at DEFAULT precision.

VMEM per grid step at 512x512 padding onto 224x224: the int8 planes
768 KiB (twice, double-buffered), the two weight matrices 448 KiB each,
one 224x512 float32 intermediate, the int8 output 147 KiB (twice).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _axis_weights(n_out: int, n_in: int, start, length, flip) -> jax.Array:
    """[n_out, n_in] f32 weights resampling ``length`` pixels from
    ``start`` onto ``n_out``, reversed where ``flip`` is non-zero; int32
    scalars in, nothing read outside the crop."""
    f32 = jnp.float32
    i = lax.broadcasted_iota(jnp.int32, (n_out, n_in), 0)
    y = lax.broadcasted_iota(jnp.int32, (n_out, n_in), 1) - start
    i = jnp.where(flip != 0, (n_out - 1) - i, i).astype(f32)
    scale = jnp.full((1, 1), length, jnp.int32).astype(f32) * (1.0 / n_out)
    support = jnp.maximum(scale, 1.0)
    centre = (i + 0.5) * scale
    w = jnp.maximum(1.0 - jnp.abs(y.astype(f32) + 0.5 - centre) / support,
                    0.0)
    w = jnp.where((y >= 0) & (y < length), w, 0.0)
    return w / jnp.sum(w, axis=1, keepdims=True)


def _rrc_kernel(crop_ref, x_ref, o_ref):
    b = pl.program_id(0)
    top, left, h, w, flip = (crop_ref[b, k] for k in range(5))
    _, channels, hp, wp = x_ref.shape
    out_h, out_w = o_ref.shape[2], o_ref.shape[3]
    wy = _axis_weights(out_h, hp, top, h, 0)
    wx = _axis_weights(out_w, wp, left, w, flip)
    for c in range(channels):
        plane = x_ref[0, c].astype(jnp.float32)
        rows = jnp.dot(wy, plane, preferred_element_type=jnp.float32)
        out = lax.dot_general(rows, wx, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
        out = jnp.clip(jnp.floor(out + 0.5), -128.0, 127.0)
        o_ref[0, c] = out.astype(jnp.int32).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("out_hw", "interpret"))
def rrc_resample_pallas(x: jax.Array, crop: jax.Array, *,
                        out_hw: tuple, interpret: bool = False) -> jax.Array:
    """x: [B, C, H_pad, W_pad] int8 planes holding ``value - 128``;
    crop: [B, 5] int32 boxes -> [B, C, out_h, out_w] int8, shifted alike."""
    n, channels, hp, wp = x.shape
    out_h, out_w = out_hw
    return pl.pallas_call(
        _rrc_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[pl.BlockSpec((1, channels, hp, wp),
                                   lambda b, crop: (b, 0, 0, 0))],
            out_specs=pl.BlockSpec((1, channels, out_h, out_w),
                                   lambda b, crop: (b, 0, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((n, channels, out_h, out_w),
                                       jnp.int8),
        interpret=interpret,
        name="rrc_resample",
    )(crop, x)
