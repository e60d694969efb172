"""JAX's persistent compilation cache at a place the caller can predict.

The cache key includes the directory, so a directory that moves between
runs never hits. Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
the examples) call ``use_compile_cache()`` once before they compile; no
library module calls it while it is imported.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache; this file is src/repro/common/ under the checkout
DEFAULT_DIR = str(Path(os.path.abspath(__file__)).parents[3] / ".jax_cache")


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and the directory is left alone. Otherwise the cache goes to
    ``DEFAULT_DIR``, a fixed path inside the checkout. Either way every
    compile is cached: the decode programs compile in well under the
    default one-second threshold, which would keep them all out.
    """
    import jax
    path = os.environ.get(ENV_VAR)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
