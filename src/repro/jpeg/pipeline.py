"""Post-entropy decode stages: dequant -> IDCT -> upsample -> color -> RGB.

Dual implementations: numpy (reference) and jnp (jit-able); the Pallas
kernels in repro.kernels implement the same stages with explicit VMEM
tiling. All decode paths share these building blocks.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.jpeg import tables as T
from repro.jpeg.parser import DecodeSpec
from repro.obs import trace

_IDCT64 = T.idct64_matrix().astype(np.float32)    # [64, 64] kron(C.T, C.T)


# ------------------------------------------------------------------ numpy
def idct_blocks_np(coefs: np.ndarray) -> np.ndarray:
    """[by, bx, 8, 8] dequantized -> spatial blocks (separable matrix IDCT)."""
    c = T.dct_matrix().astype(np.float64)
    return np.einsum("ik,...kl,jl->...ij", c.T, coefs.astype(np.float64), c.T)


def idct_blocks_np_fast(coefs: np.ndarray) -> np.ndarray:
    """Kronecker 64x64 single-GEMM IDCT (batched across blocks)."""
    by, bx = coefs.shape[:2]
    flat = coefs.reshape(-1, 64).astype(np.float32)
    return (flat @ _IDCT64.T).reshape(by, bx, 8, 8)


def idct_blocks_np_sparse(coefs: np.ndarray) -> np.ndarray:
    """DC-shortcut IDCT (beyond-paper live optimization, §Perf):

    At photographic quantization levels a large fraction of blocks carry
    only a DC coefficient; their IDCT is the constant DC/8. GEMM only the
    blocks with AC energy (libjpeg applies the same idea per-row)."""
    by, bx = coefs.shape[:2]
    flat = coefs.reshape(-1, 64).astype(np.float32)
    has_ac = np.any(flat[:, 1:] != 0.0, axis=1)
    out = np.empty_like(flat)
    out[:] = (flat[:, :1] / 8.0)               # DC-only blocks: constant
    if has_ac.any():
        out[has_ac] = flat[has_ac] @ _IDCT64.T
    return out.reshape(by, bx, 8, 8)


def assemble_plane_np(blocks: np.ndarray) -> np.ndarray:
    by, bx = blocks.shape[:2]
    return blocks.transpose(0, 2, 1, 3).reshape(by * 8, bx * 8)


def upsample_np(plane: np.ndarray, fh: int, fv: int) -> np.ndarray:
    if fh == 1 and fv == 1:
        return plane
    return np.repeat(np.repeat(plane, fv, axis=0), fh, axis=1)


def ycbcr_to_rgb_np(y, cb, cr) -> np.ndarray:
    r = y + 1.402 * (cr - 128.0)
    g = y - 0.344136 * (cb - 128.0) - 0.714136 * (cr - 128.0)
    b = y + 1.772 * (cb - 128.0)
    return np.stack([r, g, b], axis=-1)


def ycck_to_rgb_np(y, cb, cr, k) -> np.ndarray:
    inv = ycbcr_to_rgb_np(y, cb, cr)           # = 255 - CMY
    cmy = 255.0 - inv
    kk = k[..., None]
    rgb = (255.0 - np.clip(cmy, 0, 255)) * (255.0 - np.clip(kk, 0, 255)) \
        / 255.0
    return rgb


def finalize_np(rgb: np.ndarray, h: int, w: int) -> np.ndarray:
    return np.clip(np.round(rgb[:h, :w]), 0, 255).astype(np.uint8)


def assemble_image(spec: DecodeSpec, planes: Sequence[np.ndarray],
                   ycbcr_fn=None) -> np.ndarray:
    """The shared plane-assembly tail every host-side decode path ends
    with: upsample each component plane to the max sampling factor, crop
    to the common extent, dispatch the 1/3/4-component colorspace
    conversion (gray / YCbCr / Adobe-YCCK), finalize to RGB u8 [H, W, 3].

    ``planes`` are the per-component level-shifted spatial planes, one
    per ``spec.components`` entry, pre-upsample. ``ycbcr_fn`` overrides
    the 3-component conversion (the Pallas paths pass their fused kernel
    wrapper); 1- and 4-component handling is engine-independent.

    The ``jpeg.assemble`` stage span lives here (not at call sites) so
    every host-side path — numpy, fft, pallas — gets the same
    attribution for free.
    """
    with trace.span("jpeg.assemble"):
        hmax = max(c.h for c in spec.components)
        vmax = max(c.v for c in spec.components)
        planes = [upsample_np(p, hmax // c.h, vmax // c.v)
                  for p, c in zip(planes, spec.components)]
        hh = min(p.shape[0] for p in planes)
        ww = min(p.shape[1] for p in planes)
        planes = [p[:hh, :ww] for p in planes]
        if len(planes) == 1:
            rgb = np.repeat(planes[0][..., None], 3, axis=-1)
        elif len(planes) == 3:
            rgb = (ycbcr_fn or ycbcr_to_rgb_np)(*planes)
        else:
            rgb = ycck_to_rgb_np(*planes)
        return finalize_np(np.asarray(rgb, np.float64), spec.height,
                           spec.width)


# ------------------------------------------------------------------ jnp
def dequant_jnp(coefs, qtable):
    return coefs.astype(jnp.float32) * qtable.astype(jnp.float32)


def idct_blocks_jnp(deq):
    """[by,bx,8,8] -> spatial via Kronecker GEMM (MXU-friendly form)."""
    by, bx = deq.shape[:2]
    flat = deq.reshape(-1, 64)
    m = jnp.asarray(_IDCT64)
    return (flat @ m.T).reshape(by, bx, 8, 8)


def idct_blocks_jnp_separable(deq):
    c = jnp.asarray(T.dct_matrix().astype(np.float32))
    return jnp.einsum("ik,...kl,jl->...ij", c.T, deq, c.T)


def assemble_plane_jnp(blocks):
    by, bx = blocks.shape[:2]
    return blocks.transpose(0, 2, 1, 3).reshape(by * 8, bx * 8)


def upsample_jnp(plane, fh: int, fv: int):
    if fh == 1 and fv == 1:
        return plane
    return jnp.repeat(jnp.repeat(plane, fv, axis=0), fh, axis=1)


def ycbcr_to_rgb_jnp(y, cb, cr):
    r = y + 1.402 * (cr - 128.0)
    g = y - 0.344136 * (cb - 128.0) - 0.714136 * (cr - 128.0)
    b = y + 1.772 * (cb - 128.0)
    return jnp.stack([r, g, b], axis=-1)


def ycck_to_rgb_jnp(y, cb, cr, k):
    inv = ycbcr_to_rgb_jnp(y, cb, cr)
    cmy = 255.0 - inv
    kk = k[..., None]
    return (255.0 - jnp.clip(cmy, 0, 255)) * (255.0 - jnp.clip(kk, 0, 255)) \
        / 255.0


def finalize_jnp(rgb, h: int, w: int):
    return jnp.clip(jnp.round(rgb[:h, :w]), 0, 255).astype(jnp.uint8)


# -------------------------------------------------- whole-image transforms
def transform_np(spec: DecodeSpec, coef: Dict[int, np.ndarray],
                 fast_idct: bool = True, int_idct: bool = False,
                 sparse_idct: bool = False) -> np.ndarray:
    planes = []
    with trace.span("jpeg.dequant_idct"):
        for c in spec.components:
            q = spec.qtables[c.tq].astype(np.float64)
            deq = coef[c.cid] * q[None, None]
            if sparse_idct:
                blocks = idct_blocks_np_sparse(deq)
            elif int_idct:
                # libjpeg-islow-style scaled integer IDCT (13-bit fixed
                # point)
                m = np.round(_IDCT64 * (1 << 13)).astype(np.int64)
                flat = deq.reshape(-1, 64).astype(np.int64)
                blocks = ((flat @ m.T) >> 13).reshape(
                    deq.shape).astype(np.float64)
            elif fast_idct:
                blocks = idct_blocks_np_fast(deq)
            else:
                blocks = idct_blocks_np(deq)
            planes.append(assemble_plane_np(blocks) + 128.0)
    return assemble_image(spec, planes)


@partial(jax.jit, static_argnames=("n_comp", "factors", "h", "w",
                                   "separable"))
def _transform_jit(coefs, qtables, *, n_comp, factors, h, w, separable):
    planes = []
    for i in range(n_comp):
        deq = dequant_jnp(coefs[i], qtables[i])
        blocks = (idct_blocks_jnp_separable(deq) if separable
                  else idct_blocks_jnp(deq))
        plane = assemble_plane_jnp(blocks) + 128.0
        fh, fv = factors[i]
        planes.append(upsample_jnp(plane, fh, fv))
    hh = min(p.shape[0] for p in planes)
    ww = min(p.shape[1] for p in planes)
    planes = [p[:hh, :ww] for p in planes]
    if n_comp == 1:
        rgb = jnp.repeat(planes[0][..., None], 3, axis=-1)
    elif n_comp == 3:
        rgb = ycbcr_to_rgb_jnp(*planes)
    else:
        rgb = ycck_to_rgb_jnp(*planes)
    return finalize_jnp(rgb, h, w)


# -------------------------------------------------- batched transforms
def assemble_plane_batch_jnp(blocks):
    """[B, by, bx, 8, 8] -> [B, by*8, bx*8]."""
    b, by, bx = blocks.shape[:3]
    return blocks.transpose(0, 1, 3, 2, 4).reshape(b, by * 8, bx * 8)


def upsample_batch_jnp(plane, fh: int, fv: int):
    if fh == 1 and fv == 1:
        return plane
    return jnp.repeat(jnp.repeat(plane, fv, axis=1), fh, axis=2)


@partial(jax.jit, static_argnames=("n_comp", "factors", "separable"))
def _transform_batch_jit(coefs, qtables, *, n_comp, factors, separable):
    """One fused launch for a whole micro-batch.

    coefs[i]: [B, by_i, bx_i, 8, 8] f32 (zero-padded to the bucket grid);
    qtables[i]: [B, 8, 8] per-image quant tables. Returns the *uncropped*
    [B, Hpad, Wpad, 3] u8 batch — per-image crop happens host-side so the
    compile-cache key is the bucket grid, not each member's pixel dims.
    """
    planes = []
    for i in range(n_comp):
        deq = coefs[i] * qtables[i][:, None, None]
        b, by, bx = deq.shape[:3]
        if separable:
            c = jnp.asarray(T.dct_matrix().astype(np.float32))
            blocks = jnp.einsum("ik,...kl,jl->...ij", c.T, deq, c.T)
        else:
            m = jnp.asarray(_IDCT64)
            blocks = (deq.reshape(-1, 64) @ m.T).reshape(b, by, bx, 8, 8)
        plane = assemble_plane_batch_jnp(blocks) + 128.0
        fh, fv = factors[i]
        planes.append(upsample_batch_jnp(plane, fh, fv))
    hh = min(p.shape[1] for p in planes)
    ww = min(p.shape[2] for p in planes)
    planes = [p[:, :hh, :ww] for p in planes]
    if n_comp == 1:
        rgb = jnp.repeat(planes[0][..., None], 3, axis=-1)
    elif n_comp == 3:
        rgb = ycbcr_to_rgb_jnp(*planes)
    else:
        rgb = ycck_to_rgb_jnp(*planes)
    return jnp.clip(jnp.round(rgb), 0, 255).astype(jnp.uint8)


def batch_layout(specs: Sequence[DecodeSpec],
                 coefs: Sequence[Dict[int, np.ndarray]]):
    """Stack per-image coefficient grids into bucket-padded batch arrays.

    All specs must share component count and sampling structure (the
    bucket invariants). Grids inside a bucket may differ by up to the
    bucket granularity; smaller members are zero-padded — zero blocks
    IDCT to flat gray that the per-image crop discards.

    -> (stacked [B, by, bx, 8, 8] f32 per component,
        stacked [B, 8, 8] f32 qtables per component)
    """
    base = specs[0]
    n_comp = len(base.components)
    for s in specs[1:]:
        if len(s.components) != n_comp or \
                [(c.h, c.v) for c in s.components] != \
                [(c.h, c.v) for c in base.components]:
            raise ValueError("batch members must share sampling structure")
    stacked, qstacked = [], []
    for k in range(n_comp):
        grids = [coefs[b][specs[b].components[k].cid] for b in range(len(specs))]
        by = max(g.shape[0] for g in grids)
        bx = max(g.shape[1] for g in grids)
        out = np.zeros((len(specs), by, bx, 8, 8), np.float32)
        for b, g in enumerate(grids):
            out[b, :g.shape[0], :g.shape[1]] = g
        stacked.append(out)
        qstacked.append(np.stack(
            [s.qtables[s.components[k].tq].astype(np.float32)
             for s in specs]))
    return stacked, qstacked


def transform_batch(specs: Sequence[DecodeSpec],
                    coefs: Sequence[Dict[int, np.ndarray]],
                    separable: bool = False) -> List[np.ndarray]:
    """Decode a same-bucket batch with a single fused jitted transform.

    The per-image results are byte-identical to ``transform_jnp`` on each
    member: every stage is pointwise per image (the IDCT GEMM reduces
    over the fixed 64-wide axis), so batching only changes launch count.
    """
    stacked, qstacked = batch_layout(specs, coefs)
    hmax = max(c.h for c in specs[0].components)
    vmax = max(c.v for c in specs[0].components)
    factors = tuple((hmax // c.h, vmax // c.v) for c in specs[0].components)
    # one fused launch: dequant/IDCT/assemble are not separable stages
    # under jit, so the whole device transform is one span
    with trace.span("jpeg.transform", batch=len(specs)):
        out = _transform_batch_jit(
            tuple(jnp.asarray(s) for s in stacked),
            tuple(jnp.asarray(q) for q in qstacked),
            n_comp=len(stacked), factors=factors, separable=separable)
        out = np.asarray(out)
    return [out[b, :s.height, :s.width] for b, s in enumerate(specs)]


def transform_jnp(spec: DecodeSpec, coef: Dict[int, np.ndarray],
                  jit: bool = True, separable: bool = False) -> np.ndarray:
    hmax = max(c.h for c in spec.components)
    vmax = max(c.v for c in spec.components)
    coefs = tuple(jnp.asarray(coef[c.cid], jnp.float32)
                  for c in spec.components)
    qts = tuple(jnp.asarray(spec.qtables[c.tq], jnp.float32)
                for c in spec.components)
    factors = tuple((hmax // c.h, vmax // c.v) for c in spec.components)
    if jit:
        # fused jit launch: stages are not separable, one transform span
        with trace.span("jpeg.transform"):
            out = _transform_jit(coefs, qts, n_comp=len(coefs),
                                 factors=factors, h=spec.height,
                                 w=spec.width, separable=separable)
            return np.asarray(out)
    # unjitted: eager stage-by-stage dispatch (the "wrapper overhead" path)
    planes = []
    with trace.span("jpeg.dequant_idct"):
        for i in range(len(spec.components)):
            deq = dequant_jnp(coefs[i], qts[i])
            blocks = (idct_blocks_jnp_separable(deq) if separable
                      else idct_blocks_jnp(deq))
            plane = assemble_plane_jnp(blocks) + 128.0
            planes.append(upsample_jnp(plane, *factors[i]))
    with trace.span("jpeg.assemble"):
        hh = min(p.shape[0] for p in planes)
        ww = min(p.shape[1] for p in planes)
        planes = [p[:hh, :ww] for p in planes]
        if len(planes) == 1:
            rgb = jnp.repeat(planes[0][..., None], 3, axis=-1)
        elif len(planes) == 3:
            rgb = ycbcr_to_rgb_jnp(*planes)
        else:
            rgb = ycck_to_rgb_jnp(*planes)
        return np.asarray(finalize_jnp(rgb, spec.height, spec.width))
