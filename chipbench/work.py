"""The least work a baseline JPEG decode asks of the device, from headers.

Counted from what each image's own header says it holds, never from how
the program lays the work out: no padded rows, no tiles, no one-hot
products. A later change to the kernels leaves these counts as they are.

Per coded 8x8 block (every data unit the entropy stream carries, which
includes the blocks that pad the image to whole MCUs):

* operations: 64 multiplies to dequantise, a separable 8x8 inverse DCT as
  two passes of 8x8 by 8x8 matrix products (2 * 8 * 8 * 8 multiply-adds,
  2,048 operations), and 64 adds for the level shift: 2,176;
* bytes: the 64 coefficients read once as 16-bit integers, which is what
  baseline JPEG can hold: 128. The samples it makes need not leave the
  chip before the colour pass, so they are not counted.

Per output pixel:

* operations: YCbCr to RGB, two subtracts for the chroma offsets, four
  multiplies and four adds: 10 (upsampling is a copy and is not counted);
* bytes: three 8-bit channels written once: 3.

A four-component (YCCK) image costs more per pixel in the program; it is
counted like a three-component one, which keeps the count a lower bound.
"""
from __future__ import annotations

OPS_PER_BLOCK = 2176
BYTES_PER_BLOCK = 128
OPS_PER_PIXEL = 10
BYTES_PER_PIXEL = 3


def image_work(blocks: int, pixels: int):
    """-> (operations, bytes) of one decoded image."""
    return (blocks * OPS_PER_BLOCK + pixels * OPS_PER_PIXEL,
            blocks * BYTES_PER_BLOCK + pixels * BYTES_PER_PIXEL)


def header_work(data: bytes):
    """(operations, bytes) of one JPEG, read from its header alone."""
    from chipbench.reference import mcu_grid, parse
    f = parse(data)
    rows, cols, _, _ = mcu_grid(f)
    blocks = sum(rows * v * cols * h for _, h, v, _ in f.comps)
    return image_work(blocks, f.height * f.width)


def total_work(datas):
    """(operations, bytes) of decoding every JPEG in ``datas``."""
    ops = nbytes = 0
    for d in datas:
        o, b = header_work(d)
        ops += o
        nbytes += b
    return ops, nbytes


def least_seconds(ops: float, nbytes: float, peak: dict):
    """The least time the chip could take, and which bound sets it."""
    t_ops = ops / peak["ops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
