"""Per-sample RandomResizedCrop and horizontal flip for training batches.

The host draws each sample's crop box and flip at collate and places the
decoded image top-left in a padded uint8 batch; ``resample`` then turns the
batch, once it is on the device, into every box resampled onto the loader's
``target_hw`` in one Pallas launch (``kernels.ops.rrc_resample``).

Parameters follow torchvision's ``RandomResizedCrop.get_params`` and
``RandomHorizontalFlip``, drawn from ``np.random.default_rng([seed, epoch,
index])`` so that a sample's crop depends on nothing but its key: a
restored loader replays it, and a reference can recompute it. The draws,
in order (a reference must copy this order):

1. up to 10 attempts, each ``area_share = rng.uniform(scale[0], scale[1])``
   then ``log_ratio = rng.uniform(log(ratio[0]), log(ratio[1]))``; with
   ``target = height * width * area_share`` and ``r = exp(log_ratio)``,
   ``w = round(sqrt(target * r))`` and ``h = round(sqrt(target / r))``
   (Python's ``round``); if ``0 < w <= width`` and ``0 < h <= height`` the
   attempt succeeds and draws ``top = rng.integers(0, height - h + 1)``
   then ``left = rng.integers(0, width - w + 1)``;
2. if none succeeds, the centre-crop fallback, which draws nothing: the
   whole image, or its centre clamped to the ratio range;
3. one draw for the flip: ``rng.random() < flip``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence, Tuple

import numpy as np

from repro.obs import trace


@dataclasses.dataclass(frozen=True)
class RandomResizedCrop:
    """torchvision's ``RandomResizedCrop`` (bilinear, antialiased) and
    ``RandomHorizontalFlip(flip)``; the output size is the loader's
    ``target_hw``. ``pad_hw`` is the padded image size of a batch: every
    decoded image must fit in it."""
    scale: Tuple[float, float] = (0.08, 1.0)
    ratio: Tuple[float, float] = (3 / 4, 4 / 3)
    flip: float = 0.5
    seed: int = 0
    pad_hw: Tuple[int, int] = (512, 512)


def crop_box(rng: np.random.Generator, height: int, width: int,
             scale: Sequence[float], ratio: Sequence[float]):
    """torchvision's ``get_params`` -> (top, left, h, w, fell_back)."""
    area = height * width
    log_lo, log_hi = math.log(ratio[0]), math.log(ratio[1])
    for _ in range(10):
        target = area * rng.uniform(scale[0], scale[1])
        r = math.exp(rng.uniform(log_lo, log_hi))
        w = int(round(math.sqrt(target * r)))
        h = int(round(math.sqrt(target / r)))
        if 0 < w <= width and 0 < h <= height:
            top = int(rng.integers(0, height - h + 1))
            left = int(rng.integers(0, width - w + 1))
            return top, left, h, w, False
    in_ratio = width / height
    if in_ratio < min(ratio):
        w, h = width, int(round(width / min(ratio)))
    elif in_ratio > max(ratio):
        h, w = height, int(round(height * max(ratio)))
    else:
        h, w = height, width
    return (height - h) // 2, (width - w) // 2, h, w, True


def sample_params(spec: RandomResizedCrop, epoch: int, index: int,
                  height: int, width: int):
    """(top, left, h, w, flip, fell_back) of dataset item ``index`` in
    ``epoch``."""
    rng = np.random.default_rng([spec.seed, epoch, index])
    top, left, h, w, fell_back = crop_box(rng, height, width, spec.scale,
                                          spec.ratio)
    flip = bool(rng.random() < spec.flip)
    return top, left, h, w, flip, fell_back


def collate(spec: RandomResizedCrop, size: Tuple[int, int], epoch: int,
            indices: Sequence[int], images: Sequence[np.ndarray],
            labels) -> dict:
    """The batch for the device: ``image`` [B, *pad_hw, 3] uint8 with each
    image top-left, ``label`` [B] int32, ``crop`` [B, 5] int32 (top, left,
    height, width, flip). ``size`` is the output size, against which the
    span counts the crops that ``resample`` enlarges."""
    ph, pw = spec.pad_hw
    with trace.span("augment.collate", images=len(images)) as sp:
        batch = np.zeros((len(images), ph, pw, 3), np.uint8)
        crop = np.zeros((len(images), 5), np.int32)
        fallback = upscaled = 0
        for k, (i, img) in enumerate(zip(indices, images)):
            h, w = img.shape[:2]
            if h > ph or w > pw:
                raise ValueError(f"a {h}x{w} image (index {i}) does not fit "
                                 f"the augmentation's pad_hw {spec.pad_hw}")
            batch[k, :h, :w] = img
            top, left, ch, cw, flip, fell_back = sample_params(
                spec, epoch, i, h, w)
            crop[k] = top, left, ch, cw, flip
            fallback += fell_back
            upscaled += (ch < size[0]) or (cw < size[1])
        sp.set(fallback=fallback, upscaled=upscaled)
    return {"image": batch, "label": np.asarray(labels, np.int32),
            "crop": crop}


def resample(batch: dict, size: Tuple[int, int]) -> dict:
    """The device half, for a batch ``collate`` made and ``jax.device_put``
    placed: its crop boxes resampled onto ``size`` in one launch,
    dispatched asynchronously, the padded images dropped."""
    from repro.kernels import ops
    with trace.span("augment.resample"):
        image = ops.rrc_resample(batch["image"], batch["crop"],
                                 size=tuple(size))
    return dict(batch, image=image)
