"""The least work a RandomResizedCrop resample asks of the device, from the
crop boxes alone.

Counted from each delivered crop box, never from how the program lays the
work out: the padding around an image, the taps that weigh 0 and the
planes outside the crop are not counted. A separable resize of an h x w
crop onto s x s takes, per channel, one pass along one axis (s * h * w
multiply-adds) and one along the other on what the first left (s * s *
min(h, w) multiply-adds, doing the shorter axis last):

* operations: 2 * 3 * s * (h * w + s * min(h, w));
* bytes: the crop's three 8-bit channels read once, and the output's
  written once: 3 * (h * w + s * s).
"""
from __future__ import annotations


def crop_work(h: int, w: int, size: int):
    """-> (operations, bytes) of resampling one h x w crop onto size^2."""
    return (2 * 3 * size * (h * w + size * min(h, w)),
            3 * (h * w + size * size))


def total_work(boxes, size: int):
    """(operations, bytes) of every (top, left, h, w, flip) box."""
    ops = nbytes = 0
    for _, _, h, w, _ in boxes:
        o, b = crop_work(int(h), int(w), size)
        ops += o
        nbytes += b
    return ops, nbytes
