"""The sixteen built-in decode paths, registered into ``repro.codecs``.

This module is now the *registration site* of the decode surface — the
capability/context API itself lives in ``repro.codecs`` (typed
``Capabilities``, the ``eligible(caps, context)`` resolver, decoder
sessions via ``open_decoder``, and the ``@register_decoder`` plugin
registry). ``DECODE_PATHS`` / ``get_path`` / ``list_paths`` remain below
as thin **deprecation shims** over the registry for one release; new
code should use ``repro.codecs`` directly (migration map in DESIGN.md
§6).

Every path is bytes -> RGB uint8 [H, W, 3] over the same codec substrate,
differing in transform engine (numpy / jnp / Pallas), fusion/jit level,
arithmetic (float vs fixed-point vs FFT), and robustness policy (strict
paths reject the rare Adobe-YCCK mode => skip accounting). Mirrors the
paper's evaluation surface:

  name            engine    notes                                   strict
  numpy-ref       numpy     separable float IDCT (oracle)           no
  numpy-fast      numpy     Kronecker 64x64 GEMM IDCT               no
  numpy-int       numpy     13-bit fixed-point IDCT (libjpeg-ish)   no
  numpy-sparse    numpy     DC-shortcut sparse IDCT (beyond-paper)  no
  jnp-basic       jnp       eager per-stage dispatch                no
  jnp-jit         jnp       jit, separable IDCT                     no
  jnp-fused       jnp       jit, single fused transform             no
  jnp-batched     jnp       fused + reused compilation cache        no
  jnp-batch       jnp       true batched: one fused launch / bucket no
  fft-idct        numpy     IDCT via FFT (scipy-free, skimage-ish)  no
  pallas-idct     pallas    IDCT kernel (interpret on CPU)          no
  pallas-fused    pallas    fused dequant+IDCT+color kernels        no
  pallas-batch    pallas    batched kernel, per-row qtable gather   no
  strict-turbo    jnp       jnp-fused + strict policy               yes
  strict-fast     numpy     numpy-fast + strict policy              yes
  strict-pallas   pallas    pallas-idct + strict policy             yes

Capabilities: paths with a ``batch_fn`` (``jnp-fused``/``jnp-batched``/
``jnp-batch`` and ``pallas-fused``/``pallas-batch``) register
``batchable=True`` — a micro-batch runs ONE fused transform launch per
same-structure group (entropy decode stays serial on the host, being
bit-serial by nature). ``fork_safe`` follows the DESIGN.md §6 rule: only
pure-numpy paths survive forked process-pool workers (the analogue of
the paper's "PyVips is not loader-eligible under this forked harness");
the ``eligible`` resolver in ``repro.codecs`` is the only place that
rule is enforced. Restart-interval (DRI/RSTn) JPEGs are handled by the
shared entropy decoder, so every path inherits them.
"""
from __future__ import annotations

import dataclasses
import warnings
from collections.abc import Mapping
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.codecs import (Capabilities, DecoderSpec, ExecContext, as_spec,
                          eligible, get_decoder, decoder_names,
                          list_decoders, register_decoder)
from repro.jpeg import huffman, pipeline
from repro.jpeg import parser as P
from repro.jpeg.parser import UnsupportedJpeg
from repro.obs import trace

__all__ = ["DECODE_PATHS", "DecodePath", "get_path", "list_paths",
           "UnsupportedJpeg"]


def _entropy(data: bytes, strict: bool):
    with trace.span("jpeg.parse"):
        spec = P.parse(data)
        if strict:
            P.check_strict(spec)
    # huffman.decode_coefficients emits the jpeg.entropy span itself
    # (it carries the serial/parallel mode + fallback args)
    coef = huffman.decode_coefficients(spec)
    return spec, coef


def _entropy_batch(datas: List[bytes], strict: bool) -> List:
    """Host-side serial entropy decode; per-item exceptions captured."""
    items: List = []
    for d in datas:
        try:
            items.append(_entropy(d, strict))
        except Exception as e:
            items.append(e)
    return items


def _structure_groups(items: List) -> Dict[tuple, List[int]]:
    """Index groups sharing component count + sampling structure (the
    invariants a stacked [B, ...] transform needs)."""
    groups: Dict[tuple, List[int]] = {}
    for i, it in enumerate(items):
        if isinstance(it, BaseException):
            continue
        spec = it[0]
        key = (len(spec.components),
               tuple((c.h, c.v) for c in spec.components))
        groups.setdefault(key, []).append(i)
    return groups


# ------------------------------------------------------------ numpy family
def _numpy_ref(data: bytes) -> np.ndarray:
    spec, coef = _entropy(data, False)
    return pipeline.transform_np(spec, coef, fast_idct=False)


def _numpy_fast(data: bytes, strict: bool = False) -> np.ndarray:
    spec, coef = _entropy(data, strict)
    return pipeline.transform_np(spec, coef, fast_idct=True)


def _numpy_int(data: bytes) -> np.ndarray:
    spec, coef = _entropy(data, False)
    return pipeline.transform_np(spec, coef, int_idct=True)


def _numpy_sparse(data: bytes) -> np.ndarray:
    spec, coef = _entropy(data, False)
    return pipeline.transform_np(spec, coef, sparse_idct=True)


def _fft_idct(data: bytes) -> np.ndarray:
    spec, coef = _entropy(data, False)
    # IDCT-II via FFT (type-III DCT through complex FFT), scipy-free
    import numpy.fft as fft

    def idct1(x, axis):
        n = x.shape[axis]
        k = np.arange(n).reshape([-1 if i == axis % x.ndim else 1
                                  for i in range(x.ndim)])
        w = np.exp(1j * np.pi * k / (2 * n))
        xw = x * w * np.sqrt(2 * n)
        xw0 = np.take(x, [0], axis=axis) * (np.sqrt(n) - np.sqrt(2 * n))
        xw = xw + xw0 * (k == 0)
        full = fft.ifft(xw, n=n, axis=axis)
        v = np.real(full)
        idx = np.empty(n, dtype=np.int64)
        idx[::2] = np.arange((n + 1) // 2)
        idx[1::2] = np.arange(n - 1, n // 2 - 1, -1)
        return np.take(v, idx, axis=axis)

    planes = []
    with trace.span("jpeg.dequant_idct"):
        for c in spec.components:
            q = spec.qtables[c.tq].astype(np.float64)
            deq = coef[c.cid] * q[None, None]
            blocks = idct1(idct1(deq, axis=2), axis=3)
            planes.append(pipeline.assemble_plane_np(blocks) + 128.0)
    return pipeline.assemble_image(spec, planes)


# ------------------------------------------------------------ jnp family
def _jnp_basic(data: bytes) -> np.ndarray:
    spec, coef = _entropy(data, False)
    return pipeline.transform_jnp(spec, coef, jit=False)


def _jnp_jit(data: bytes) -> np.ndarray:
    spec, coef = _entropy(data, False)
    return pipeline.transform_jnp(spec, coef, jit=True, separable=True)


def _jnp_fused(data: bytes, strict: bool = False) -> np.ndarray:
    spec, coef = _entropy(data, strict)
    return pipeline.transform_jnp(spec, coef, jit=True, separable=False)


def _jnp_decode_batch(datas: List[bytes], strict: bool = False) -> List:
    """True batched decode: serial host entropy, then ONE fused jitted
    transform per same-structure group (see pipeline.transform_batch)."""
    items = _entropy_batch(datas, strict)
    out = list(items)                  # exceptions stay in place
    for idxs in _structure_groups(items).values():
        specs = [items[i][0] for i in idxs]
        coefs = [items[i][1] for i in idxs]
        try:
            imgs = pipeline.transform_batch(specs, coefs)
        except Exception as e:         # a bad group fails only its members
            imgs = [e] * len(idxs)
        for i, img in zip(idxs, imgs):
            out[i] = img
    return out


def _one_of_batch(batch_fn) -> Callable[[bytes], np.ndarray]:
    """Single-image front for a batched implementation (B=1 batch)."""
    def fn(data: bytes) -> np.ndarray:
        res = batch_fn([data])[0]
        if isinstance(res, BaseException):
            raise res
        return res
    return fn


# ------------------------------------------------------------ pallas family
def _ycbcr_kernel(y, cb, cr) -> np.ndarray:
    from repro.kernels import ops
    # the colour round trip: upload three planes, kernel, copy back
    with trace.span("jpeg.color"):
        return np.asarray(ops.ycbcr2rgb(y, cb, cr))


def _pallas_idct(data: bytes, strict: bool = False) -> np.ndarray:
    from repro.kernels import ops
    spec, coef = _entropy(data, strict)
    planes = []
    with trace.span("jpeg.dequant_idct"):
        for c in spec.components:
            q = spec.qtables[c.tq].astype(np.float32)
            deq = (coef[c.cid] * q[None, None]).astype(np.float32)
            by, bx = deq.shape[:2]
            blocks = ops.idct8x8(deq.reshape(-1, 64)).reshape(by, bx, 8, 8)
            planes.append(
                pipeline.assemble_plane_np(np.asarray(blocks)) + 128.0)
    return pipeline.assemble_image(spec, planes)


def _pallas_fused(data: bytes) -> np.ndarray:
    from repro.kernels import ops
    spec, coef = _entropy(data, False)
    planes = []
    with trace.span("jpeg.dequant_idct"):
        for c in spec.components:
            q = spec.qtables[c.tq].astype(np.float32)
            by, bx = coef[c.cid].shape[:2]
            blocks = ops.dequant_idct(
                coef[c.cid].reshape(-1, 64).astype(np.float32),
                q.reshape(64))
            planes.append(pipeline.assemble_plane_np(
                np.asarray(blocks).reshape(by, bx, 8, 8)))
    return pipeline.assemble_image(spec, planes, ycbcr_fn=_ycbcr_kernel)


def _pallas_transform_group(specs, coefs) -> List[np.ndarray]:
    """One batched-kernel launch for a whole same-structure group: every
    block row of every (image, component) pair is concatenated into one
    [sum(blocks), 64] array with a per-row quant-table index — the
    per-row gather is what lets rows of different images (and different
    quality levels) share a single launch."""
    from repro.kernels import ops
    rows, ridx, qtabs, spans = [], [], [], []
    for spec, coef in zip(specs, coefs):
        for c in spec.components:
            grid = coef[c.cid]
            by, bx = grid.shape[:2]
            r = grid.reshape(-1, 64).astype(np.float32)
            ridx.append(np.full(len(r), len(qtabs), np.int32))
            qtabs.append(spec.qtables[c.tq].astype(np.float32).reshape(64))
            spans.append((len(r), by, bx))
            rows.append(r)
    rows, ridx, qtabs = (np.concatenate(rows), np.concatenate(ridx),
                         np.stack(qtabs))
    # the device transform, launch to pixels on the host
    with trace.span("jpeg.transform", batch=len(specs), rows=len(rows)):
        pix = np.asarray(ops.decode_batch(rows, ridx, qtabs))
    imgs, pos, si = [], 0, 0
    for spec in specs:
        planes = []
        for _ in spec.components:
            nr, by, bx = spans[si]
            si += 1
            blocks = pix[pos:pos + nr].reshape(by, bx, 8, 8)
            pos += nr
            planes.append(pipeline.assemble_plane_np(blocks))
        imgs.append(pipeline.assemble_image(spec, planes,
                                            ycbcr_fn=_ycbcr_kernel))
    return imgs


def _pallas_decode_batch(datas: List[bytes], strict: bool = False) -> List:
    items = _entropy_batch(datas, strict)
    out = list(items)
    for idxs in _structure_groups(items).values():
        specs = [items[i][0] for i in idxs]
        coefs = [items[i][1] for i in idxs]
        try:
            imgs = _pallas_transform_group(specs, coefs)
        except Exception as e:
            imgs = [e] * len(idxs)
        for i, img in zip(idxs, imgs):
            out[i] = img
    return out


# ------------------------------------------------------------ registration
def _register(name, fn, *, engine="numpy", strict=False, batch_fn=None,
              description=""):
    # every built-in path funnels entropy decode through huffman, so all
    # of them honor the interval-parallel entropy_workers knob AND
    # inherit progressive (SOF2) decode — except the strict paths, whose
    # policy refuses progressive before entropy decode (check_strict)
    register_decoder(
        name, fn,
        caps=Capabilities(engine=engine, strict=strict,
                          fork_safe=(engine == "numpy"),
                          batchable=batch_fn is not None,
                          parallel_entropy=True,
                          progressive=not strict),
        batch_fn=batch_fn, description=description)


_register("numpy-ref", _numpy_ref, engine="numpy",
          description="separable float IDCT, reference oracle")
_register("numpy-fast", lambda d: _numpy_fast(d, False), engine="numpy",
          description="Kronecker 64x64 GEMM IDCT")
_register("numpy-int", _numpy_int, engine="numpy",
          description="13-bit fixed-point IDCT")
_register("jnp-basic", _jnp_basic, engine="jnp",
          description="eager per-stage jnp dispatch")
_register("jnp-jit", _jnp_jit, engine="jnp",
          description="jit, separable IDCT")
_register("jnp-fused", lambda d: _jnp_fused(d, False), engine="jnp",
          batch_fn=_jnp_decode_batch,
          description="jit, fused whole-image transform")
_register("jnp-batched", lambda d: _jnp_fused(d, False), engine="jnp",
          batch_fn=_jnp_decode_batch,
          description="fused + warm compile cache (bucketed shapes)")
_register("jnp-batch", _one_of_batch(_jnp_decode_batch), engine="jnp",
          batch_fn=_jnp_decode_batch,
          description="true batched: one fused launch per bucket")
_register("fft-idct", _fft_idct, engine="numpy",
          description="IDCT via FFT (skimage-style)")
_register("pallas-idct", lambda d: _pallas_idct(d, False), engine="pallas",
          description="Pallas IDCT kernel (interpret on CPU; MXU on TPU)")
_register("pallas-fused", _pallas_fused, engine="pallas",
          batch_fn=_pallas_decode_batch,
          description="fused Pallas dequant+IDCT + color kernels")
_register("pallas-batch", _one_of_batch(_pallas_decode_batch),
          engine="pallas", batch_fn=_pallas_decode_batch,
          description="batched Pallas kernel, per-row qtable gather")
_register("strict-turbo", lambda d: _jnp_fused(d, True), engine="jnp",
          strict=True,
          description="jnp-fused + strict JPEG-mode policy")
_register("strict-fast", lambda d: _numpy_fast(d, True), engine="numpy",
          strict=True,
          description="numpy-fast + strict JPEG-mode policy")
_register("strict-pallas", lambda d: _pallas_idct(d, True), engine="pallas",
          strict=True,
          description="pallas-idct + strict JPEG-mode policy")
# 14th path — beyond-paper optimization (EXPERIMENTS.md §Perf): DC-shortcut
# IDCT, GEMM only blocks with AC energy.
_register("numpy-sparse", _numpy_sparse, engine="numpy",
          description="DC-shortcut sparse IDCT (beyond-paper)")


# ------------------------------------------------- deprecation shims (v1)
# DECODE_PATHS / get_path / list_paths were the pre-codecs front door.
# They remain for one release as live read-only views over the registry:
# a decoder registered via repro.codecs shows up here too, and these
# never diverge from the registry. New code: repro.codecs (DESIGN.md §6).
@dataclasses.dataclass(frozen=True)
class DecodePath:
    """Deprecated adapter over ``repro.codecs.DecoderSpec`` (same duck
    type: ``decode``/``decode_batch`` raw conventions plus the legacy
    ``process_eligible`` flag). Constructible directly for ad-hoc test
    decoders; ``repro.codecs.as_spec`` lifts it into the new API."""

    name: str
    fn: Callable[[bytes], np.ndarray]
    strict: bool = False
    process_eligible: bool = True     # legacy alias of caps.fork_safe
    engine: str = "numpy"             # numpy | jnp | pallas
    description: str = ""
    batch_fn: Optional[Callable[[List[bytes]], List]] = None
    parallel_entropy: bool = False    # ad-hoc shims stay serial-only
    progressive: bool = False         # ad-hoc shims are baseline-only

    @property
    def caps(self) -> Capabilities:
        return Capabilities(engine=self.engine, strict=self.strict,
                            fork_safe=self.process_eligible,
                            batchable=self.batch_fn is not None,
                            parallel_entropy=self.parallel_entropy,
                            progressive=self.progressive)

    def decode(self, data: bytes) -> np.ndarray:
        return self.fn(data)

    def decode_batch(self, datas: List[bytes]) -> List:
        """Index-aligned arrays-or-exceptions — the registration-level
        batch convention, delegated to the registry's one implementation
        so the shim can never diverge from it."""
        return as_spec(self).decode_batch(datas)


_PATH_CACHE: Dict[str, Tuple[DecoderSpec, DecodePath]] = {}


def _path_of(spec: DecoderSpec) -> DecodePath:
    cached = _PATH_CACHE.get(spec.name)
    if cached is not None and cached[0] is spec:
        return cached[1]
    path = DecodePath(name=spec.name, fn=spec.fn, strict=spec.caps.strict,
                      process_eligible=spec.caps.fork_safe,
                      engine=spec.caps.engine,
                      description=spec.description, batch_fn=spec.batch_fn,
                      parallel_entropy=spec.caps.parallel_entropy,
                      progressive=spec.caps.progressive)
    _PATH_CACHE[spec.name] = (spec, path)
    return path


class _DecodePathsView(Mapping):
    """Live read-only mapping over the codecs registry (deprecated)."""

    def __getitem__(self, name: str) -> DecodePath:
        return _path_of(get_decoder(name))

    def __iter__(self):
        return iter(decoder_names())

    def __len__(self) -> int:
        return len(decoder_names())

    def __repr__(self) -> str:
        return f"DECODE_PATHS<deprecated view of {len(self)} decoders>"


DECODE_PATHS: Mapping = _DecodePathsView()


def get_path(name: str) -> DecodePath:
    """Deprecated: use ``repro.codecs.get_decoder`` (or ``open_decoder``
    for a context-checked session)."""
    warnings.warn("jpeg.paths.get_path() is deprecated; use "
                  "repro.codecs.get_decoder()/open_decoder()",
                  DeprecationWarning, stacklevel=2)
    return DECODE_PATHS[name]


def list_paths(process_eligible: Optional[bool] = None,
               strict: Optional[bool] = None) -> List[DecodePath]:
    """Deprecated: use ``repro.codecs.list_decoders`` — eligibility is a
    (capabilities, context) question answered by the ``eligible``
    resolver, e.g. ``list_decoders(context=ExecContext.PROCESS_POOL)``.
    """
    warnings.warn("jpeg.paths.list_paths() is deprecated; use "
                  "repro.codecs.list_decoders()",
                  DeprecationWarning, stacklevel=2)
    out = []
    for spec in list_decoders():
        if process_eligible is not None and \
                bool(eligible(spec.caps, ExecContext.PROCESS_POOL)) \
                != process_eligible:
            continue
        if strict is not None and spec.caps.strict != strict:
            continue
        out.append(_path_of(spec))
    return out
