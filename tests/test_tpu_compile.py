"""Ahead-of-time compiles of the decode kernels for a described TPU v5e.

Interpret mode runs the Pallas kernels as plain XLA programs, so it cannot
see what Mosaic refuses: unaligned block shapes, too much VMEM, an index
block it cannot tile. These tests compile each kernel of the decode path,
and the fused batched transform, for one chip of a described ``v5e:2x2``
topology at real micro-batch sizes. Nothing runs; a compile that passes
here is not a chip run.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and a test worker that describes it at import would change what the other
workers collect.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.jpeg.pipeline import _transform_batch_jit
from repro.kernels.decode_batch import TILE_N as DB_TILE
from repro.kernels.decode_batch import decode_batch_pallas
from repro.kernels.dequant_idct import TILE_N as DQ_TILE
from repro.kernels.dequant_idct import dequant_idct_pallas
from repro.kernels.idct8x8 import TILE_N as IDCT_TILE
from repro.kernels.idct8x8 import idct8x8_pallas
from repro.kernels.ycbcr2rgb import LANES, TILE_R, ycbcr2rgb_pallas

# 4:2:0 micro-batch of 16 ImageNet-val-sized 375x500 images: 24x32 MCUs of
# 16x16 pixels, so the Y grid is 48x64 blocks and each chroma grid 24x32
BATCH = 16
Y_GRID = (48, 64)
C_GRID = (24, 32)
ROWS = BATCH * (Y_GRID[0] * Y_GRID[1] + 2 * C_GRID[0] * C_GRID[1])


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _rows(n, tile):
    return -(-n // tile) * tile


def _kernel_cases():
    """(name, pallas fn, argument shapes/dtypes) at real sizes."""
    f32, i32 = jnp.float32, jnp.int32
    n_db = _rows(ROWS, DB_TILE)
    # one 375x500 4:2:0 image's Y plane as 128-lane colour rows
    color_rows = _rows(-(-375 * 500 // LANES), TILE_R)
    return {
        "idct8x8": (idct8x8_pallas,
                    [((_rows(8192, IDCT_TILE), 64), f32), ((64, 64), f32)]),
        "dequant_idct": (dequant_idct_pallas,
                         [((_rows(8192, DQ_TILE), 64), f32),
                          ((1, 64), f32), ((64, 64), f32)]),
        # T = quant tables in the launch: one per (image, component) pair
        # of a 16-image micro-batch, and a 32-image one
        "decode_batch_t48": (decode_batch_pallas,
                             [((n_db, 64), f32), ((n_db, 1), i32),
                              ((3 * BATCH, 64), f32), ((64, 64), f32)]),
        "decode_batch_t96": (decode_batch_pallas,
                             [((n_db, 64), f32), ((n_db, 1), i32),
                              ((6 * BATCH, 64), f32), ((64, 64), f32)]),
        "ycbcr2rgb": (ycbcr2rgb_pallas,
                      [((color_rows, LANES), f32)] * 3),
    }


@pytest.mark.parametrize("name", sorted(_kernel_cases()))
def test_kernel_compiles_through_mosaic(one_chip, name):
    fn, shapes = _kernel_cases()[name]
    args = [_sds(s, d, one_chip) for s, d in shapes]
    compiled = fn.lower(*args, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


def test_transform_batch_compiles_for_420_microbatch(one_chip):
    f32 = jnp.float32
    coefs = (_sds((BATCH, *Y_GRID, 8, 8), f32, one_chip),
             _sds((BATCH, *C_GRID, 8, 8), f32, one_chip),
             _sds((BATCH, *C_GRID, 8, 8), f32, one_chip))
    qtabs = tuple(_sds((BATCH, 8, 8), f32, one_chip) for _ in range(3))
    compiled = _transform_batch_jit.lower(
        coefs, qtabs, n_comp=3, factors=((1, 1), (2, 2), (2, 2)),
        separable=False).compile()
    out = compiled.out_info
    assert out.shape == (BATCH, Y_GRID[0] * 8, Y_GRID[1] * 8, 3)
    assert out.dtype == np.uint8
    mem = compiled.memory_analysis()
    # the program and its temporaries fit one 16 GB v5e with room to spare
    assert mem.temp_size_in_bytes < 1 << 30, mem
