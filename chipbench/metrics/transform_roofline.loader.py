"""The least time the window's decode work (``work.py``, from each image's
header) takes at the chip's peaks, as a share of device busy time."""
from chipbench.reduce import roofline_pct


def read(record):
    return roofline_pct(record)
