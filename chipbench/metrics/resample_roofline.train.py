"""The least time of the window's resample work (``resample_work.py``,
from the crop boxes of every batch resampled in the window) at the chip's
peaks, as a share of the device time of every ``rrc_resample`` op in the
traced window (``resample_device_s``); none where the trace has no such
op."""
from chipbench.work import least_seconds


def read(record):
    kernel_s = record.get("resample_device_s")
    if not kernel_s or not record.get("peaks") \
            or not record.get("resample_bytes"):
        return None
    t, _ = least_seconds(record["resample_ops"], record["resample_bytes"],
                         record["peaks"])
    return 100.0 * t / kernel_s
