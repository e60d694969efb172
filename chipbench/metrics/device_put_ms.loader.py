"""``prefetch_to_device``'s ``jax.device_put`` (``prefetch.device_put``
spans) per batch. The call is asynchronous: this is the hand-off and
staging, not the transfer's completion."""
from chipbench.reduce import span_ms_per


def read(record):
    return span_ms_per(record, ("prefetch.device_put",),
                       "prefetch.device_put")
