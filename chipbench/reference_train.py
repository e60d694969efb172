"""Plain reference for the training pipeline's crop, resample and flip,
independent of the program: it imports nothing from ``repro``.

It copies the semantics the program documents (``repro.data.augment``,
``DESIGN.md``): torchvision's ``RandomResizedCrop.get_params`` and
``RandomHorizontalFlip`` drawn from ``np.random.default_rng([seed, epoch,
index])`` in torchvision's order, then PIL's antialiased bilinear resize of
the crop in float64, the flip, rounding half up and a clip to [0, 255].
Images are decoded with ``reference.decode``.

Two controls, each the reference with one part made worse, read not
correct against it: ``precision="int8"`` decodes with an int8 IDCT, and
``antialias=False`` resamples with plain two-tap bilinear weights (support
1 at every scale), which is what a resize without PIL's antialias does.
"""
from __future__ import annotations

import math

import numpy as np

from chipbench import reference as R


def crop_box(rng, height: int, width: int, scale, ratio):
    """torchvision's ``get_params``: up to 10 attempts, each an area share
    then a log-ratio, and on success the top then the left offset; else
    the centre crop clamped to the ratio range -> (top, left, h, w)."""
    area = height * width
    log_lo, log_hi = math.log(ratio[0]), math.log(ratio[1])
    for _ in range(10):
        target = area * rng.uniform(scale[0], scale[1])
        r = math.exp(rng.uniform(log_lo, log_hi))
        w = int(round(math.sqrt(target * r)))
        h = int(round(math.sqrt(target / r)))
        if 0 < w <= width and 0 < h <= height:
            return (int(rng.integers(0, height - h + 1)),
                    int(rng.integers(0, width - w + 1)), h, w)
    if width / height < min(ratio):
        w, h = width, int(round(width / min(ratio)))
    elif width / height > max(ratio):
        h, w = height, int(round(height * max(ratio)))
    else:
        h, w = height, width
    return (height - h) // 2, (width - w) // 2, h, w


def params(aug: dict, seed: int, epoch: int, index: int, height: int,
           width: int):
    """(top, left, h, w, flip) of one item; ``aug`` holds ``scale``,
    ``ratio`` and ``flip`` as the configuration gives them."""
    rng = np.random.default_rng([seed, epoch, index])
    box = crop_box(rng, height, width, aug["scale"], aug["ratio"])
    return (*box, int(rng.random() < aug["flip"]))


def weights(n_out: int, n_in: int, antialias: bool = True) -> np.ndarray:
    """[n_out, n_in] weights of a bilinear resize of ``n_in`` samples onto
    ``n_out``: PIL's triangle over support ``max(scale, 1)`` and the window
    it admits, or with ``antialias=False`` support 1 at every scale."""
    scale = n_in / n_out
    support = max(scale, 1.0) if antialias else 1.0
    out = np.zeros((n_out, n_in))
    for i in range(n_out):
        c = (i + 0.5) * scale
        lo = max(int(c - support + 0.5), 0)
        hi = min(int(c + support + 0.5), n_in)
        y = np.arange(lo, hi)
        out[i, lo:hi] = np.maximum(1.0 - np.abs(y + 0.5 - c) / support, 0.0)
        out[i] /= out[i].sum()
    return out


def resample(img: np.ndarray, box, size, antialias: bool = True):
    """Crop ``box`` (top, left, h, w, flip) of ``img`` onto ``size``."""
    top, left, h, w, flip = box
    crop = img[top:top + h, left:left + w].astype(np.float64)
    out = np.einsum("ij,jkc->ikc", weights(size[0], h, antialias), crop)
    out = np.einsum("lk,ikc->ilc", weights(size[1], w, antialias), out)
    if flip:
        out = out[:, ::-1]
    return np.clip(np.floor(out + 0.5), 0, 255).astype(np.uint8)


def expected(data: bytes, aug: dict, seed: int, epoch: int, index: int,
             size, precision: str = "f64", antialias: bool = True):
    """-> (the item's crop box, what the pipeline should deliver)."""
    img = R.decode(data, precision=precision)
    box = params(aug, seed, epoch, index, *img.shape[:2])
    return box, resample(img, box, size, antialias)
