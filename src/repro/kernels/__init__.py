# Pallas TPU kernels for the paper's compute hot-spot: the post-entropy
# JPEG block transform (dequant + 8x8 IDCT + color conversion), expressed
# MXU/VPU-natively (see DESIGN.md hardware-adaptation notes). ops.py holds
# the jit'd wrappers (Mosaic on the TPU, interpret mode in the CPU tests),
# ref.py the pure jnp oracles used by the per-kernel allclose sweeps.
from repro.kernels import ops, ref
