"""Host assembly of device results (``jpeg.assemble`` spans) per image."""
from chipbench.reduce import span_ms_per


def read(record):
    return span_ms_per(record, ("jpeg.assemble",), "jpeg.assemble")
