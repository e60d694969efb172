"""Target-hardware constants for roofline analysis + host fingerprinting.

The decode kernels run on a TPU v5e (and on the CPU backend in the tests).
These constants feed the three-term roofline (compute / memory / collective)
derived from the compiled dry-run artifacts. Sources: public TPU v5e specs.

``host_fingerprint()`` is the bench harness's machine identity: every emitted
record set carries it so results are only ever compared across commits on the
same (or an explicitly acknowledged different) host — the paper's core point
is that the platform is part of the claim.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import os
import platform as _platform
import sys


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_bf16_flops: float      # FLOP/s per chip
    hbm_bandwidth: float        # bytes/s per chip
    ici_link_bandwidth: float   # bytes/s per link (one direction)
    ici_links_per_chip: int     # 2D torus on v5e
    hbm_bytes: int              # HBM capacity per chip
    vmem_bytes: int             # VMEM per core (v5e has 1 core/chip)


TPU_V5E = ChipSpec(
    name="tpu_v5e",
    peak_bf16_flops=197e12,
    hbm_bandwidth=819e9,
    ici_link_bandwidth=50e9,
    ici_links_per_chip=4,
    hbm_bytes=16 * 1024**3,
    vmem_bytes=128 * 1024**2,
)

# MXU native tile: 128x128 systolic array; VPU lanes (8, 128).
MXU_DIM = 128
VPU_LANES = 128
VPU_SUBLANES = 8


def _cpu_model() -> str:
    """Best-effort CPU model name (``platform.processor()`` is often empty
    on Linux; /proc/cpuinfo has the marketing string)."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.lower().startswith("model name"):
                    return line.split(":", 1)[1].strip()
    except OSError:
        pass
    return _platform.processor() or "unknown"


@functools.lru_cache(maxsize=1)
def _host_info() -> tuple:
    import numpy as np
    try:
        import jax
        jax_version = jax.__version__
    # absence of jax IS the datum: records say "none" on bench hosts
    # repro: ignore[except-swallow] -- probe failure means no accelerator
    except Exception:
        jax_version = "none"
    info = {
        "cpu_model": _cpu_model(),
        "cpus": os.cpu_count(),
        "machine": _platform.machine(),
        "system": _platform.system(),
        "python": sys.version.split()[0],
        "numpy": np.__version__,
        "jax": jax_version,
    }
    key = "|".join(f"{k}={info[k]}" for k in sorted(info))
    info["fingerprint"] = hashlib.sha256(key.encode()).hexdigest()[:12]
    info["hostname"] = _platform.node()
    return tuple(info.items())


def host_fingerprint() -> dict:
    """Stable identity of the machine a benchmark ran on.

    ``fingerprint`` hashes only the fields that change benchmark meaning
    (CPU model, core count, arch, python/jax/numpy versions) — not
    hostname or time — so two runs on identical hosts compare cleanly.
    Computed once per process (a sweep saves ~140 record files, each
    stamped with it); callers get a fresh copy.
    """
    return dict(_host_info())


def roofline_terms(
    flops_per_chip: float,
    hbm_bytes_per_chip: float,
    collective_bytes_per_chip: float,
    chip: ChipSpec = TPU_V5E,
) -> dict:
    """Three-term roofline in seconds-per-step, per chip.

    ``cost_analysis()`` reports per-device (post-SPMD-partitioning) FLOPs
    and bytes, so all inputs here are per-chip quantities. The collective
    term models each chip pushing its collective payload through its ICI links
    (all links usable in a 2D torus; we use a single-link bound as the
    conservative default, matching the prompt's ~50 GB/s/link figure).
    """
    compute_s = flops_per_chip / chip.peak_bf16_flops
    memory_s = hbm_bytes_per_chip / chip.hbm_bandwidth
    collective_s = collective_bytes_per_chip / chip.ici_link_bandwidth
    terms = {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
    }
    dominant = max(terms, key=lambda k: terms[k])
    bound = max(terms.values())
    total = max(bound, 1e-30)
    terms["dominant"] = dominant
    terms["bound_s"] = bound
    # Roofline fraction: useful-compute time over the binding resource time.
    terms["roofline_fraction"] = compute_s / total
    return terms
