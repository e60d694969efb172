"""Host parse and entropy decode (``jpeg.parse`` + ``jpeg.entropy`` spans)
per image, in the traced window; in a thread pool it includes the time a
thread waits for the interpreter lock."""
from chipbench.reduce import span_ms_per


def read(record):
    return span_ms_per(record, ("jpeg.parse", "jpeg.entropy"), "jpeg.entropy")
