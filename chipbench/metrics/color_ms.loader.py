"""The colour round trip (``jpeg.color`` spans: three planes up, the
``ycbcr2rgb`` kernel, the pixels back) per image; none where the program
has no such span."""
from chipbench.reduce import span_ms_per


def read(record):
    if "jpeg.color" not in (record.get("spans") or {}):
        return None
    return span_ms_per(record, ("jpeg.color",), "jpeg.entropy")
