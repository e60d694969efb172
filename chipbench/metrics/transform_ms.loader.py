"""The batched Pallas launch (``jpeg.transform`` spans: the kernel call
through the copy of its pixels to the host) per image; none where the
program has no such span."""
from chipbench.reduce import span_ms_per


def read(record):
    if "jpeg.transform" not in (record.get("spans") or {}):
        return None
    return span_ms_per(record, ("jpeg.transform",), "jpeg.entropy")
