"""The benchmark's own image pool: ImageNet-val-shaped JPEGs from a seed.

The shape follows the ILSVRC2012 validation split: five common sizes
(375x500, 500x375, 333x500, 500x333, 500x500), qualities 60-95, 70%
4:2:0 and 30% 4:4:4 chroma sampling, and one Adobe-YCCK image at the
index 19876/50000 scales to. Baseline images are written by Pillow's
libjpeg, the family of encoders that wrote ImageNet's files; the YCCK
image is the fixed file ``data/ycck.jpg``.

Which size, sampling and quality each index gets is drawn from the
configuration's ``layout_seed``, so every run seed decodes the same set of
headers in the same order and the program compiles the same shapes; the
pixels, and so the entropy-coded data, are drawn from the run seed.

``stand_ins`` gives, for the same layout, flat images with the same
headers: decoding them takes the program through every shape a pool
decode does, at a fraction of the entropy-decode cost. Set-up warms up
on them.
"""
from __future__ import annotations

import io
import os
from concurrent.futures import ThreadPoolExecutor
from typing import List, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
YCCK_FILE = os.path.join(HERE, "data", "ycck.jpg")
# encoding threads: Pillow and the numpy synthesis release the GIL
THREADS = 4


def layout(cfg: dict) -> List[Tuple[int, int, str, int]]:
    """(height, width, sampling, quality) of each pool index; the rare
    index is ``(h, w, "ycck", 0)`` with the fixed file's size."""
    n = cfg["pool_images"]
    rng = np.random.default_rng([cfg["layout_seed"]])
    sizes = [tuple(s) for s in cfg["sizes"]]
    size_i = rng.integers(len(sizes), size=n)
    quality = rng.choice(cfg["qualities"], size=n)
    s444 = rng.random(n) < cfg["share_444"]
    out = [(sizes[si][0], sizes[si][1], "444" if f else "420", int(q))
           for si, q, f in zip(size_i, quality, s444)]
    out[rare_index(cfg)] = (*ycck_size(), "ycck", 0)
    return out


def rare_index(cfg: dict) -> int:
    num, den = cfg["rare_index_of"]
    return int(num / den * cfg["pool_images"])


def ycck_size() -> Tuple[int, int]:
    from PIL import Image
    with Image.open(YCCK_FILE) as im:
        return im.height, im.width


def synth(rng: np.random.Generator, h: int, w: int) -> np.ndarray:
    """Band-limited waves plus texture noise: photographic coefficient
    sparsity, in float32."""
    yy = np.arange(h, dtype=np.float32)[:, None]
    xx = np.arange(w, dtype=np.float32)[None, :]
    img = np.full((h, w, 3), 128.0, np.float32)
    for _ in range(4):
        fy, fx = rng.uniform(0.01, 0.2, size=2)
        ph, amp = rng.uniform(0, 6.28), rng.uniform(20, 70)
        wave = np.sin(yy * np.float32(fy) + xx * np.float32(fx)
                      + np.float32(ph)) * np.float32(amp)
        img += wave[..., None] * rng.uniform(0.3, 1.0, size=3).astype(
            np.float32)
    img += rng.standard_normal((h, w, 3), dtype=np.float32) * np.float32(
        rng.uniform(2, 10))
    return np.clip(img, 0, 255).astype(np.uint8)


def encode(img: np.ndarray, sampling: str, quality: int) -> bytes:
    from PIL import Image
    buf = io.BytesIO()
    Image.fromarray(img).save(buf, format="JPEG", quality=quality,
                              subsampling={"420": 2, "444": 0}[sampling])
    return buf.getvalue()


def build(cfg: dict, seed: int) -> List[bytes]:
    """The pool for ``seed``: the same seed gives the same bytes."""
    lay = layout(cfg)
    with open(YCCK_FILE, "rb") as f:
        ycck = f.read()

    def one(i):
        h, w, sampling, q = lay[i]
        if sampling == "ycck":
            return ycck
        return encode(synth(np.random.default_rng([seed, i]), h, w),
                      sampling, q)

    with ThreadPoolExecutor(THREADS) as ex:
        return list(ex.map(one, range(len(lay))))


def stand_ins(cfg: dict, copies: int = 1) -> List[List[bytes]]:
    """Per pool index, ``copies`` distinct flat images with its header
    (size, sampling, quality); the rare index gets the YCCK file."""
    lay = layout(cfg)
    with open(YCCK_FILE, "rb") as f:
        ycck = f.read()
    made = {}
    out = []
    for h, w, sampling, q in lay:
        if sampling == "ycck":
            out.append([ycck] * copies)
            continue
        key = (h, w, sampling, q)
        if key not in made:
            made[key] = [encode(np.full((h, w, 3), 96 + 8 * c, np.uint8),
                                sampling, q) for c in range(copies)]
        out.append(made[key])
    return out
