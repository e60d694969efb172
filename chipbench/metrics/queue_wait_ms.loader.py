"""Time the consumer waits on the loader's worker pool
(``loader.queue_wait`` spans), per chunk; only a pooled loader has it."""
from chipbench.reduce import span_ms_per


def read(record):
    return span_ms_per(record, ("loader.queue_wait",), "loader.queue_wait")
