"""Per-kernel allclose sweeps: Pallas (interpret=True) vs ref.py oracles."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.jpeg import tables as T


@pytest.mark.parametrize("n", [64, 512, 1024, 1500])
@pytest.mark.parametrize("scale", [1.0, 100.0])
def test_idct8x8_matches_ref(n, scale):
    rng = np.random.RandomState(n)
    x = (rng.randn(n, 64) * scale).astype(np.float32)
    out = np.asarray(ops.idct8x8(x))
    want = np.asarray(ref.idct8x8(jnp.asarray(x)))
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-3)


def test_idct8x8_matches_separable_numpy():
    """Kronecker GEMM == separable C^T X C (the mathematical identity the
    MXU formulation rests on)."""
    rng = np.random.RandomState(0)
    blocks = rng.randn(37, 8, 8).astype(np.float32) * 50
    c = T.dct_matrix()
    want = np.einsum("ik,nkl,jl->nij", c.T, blocks.astype(np.float64), c.T)
    got = np.asarray(ops.idct8x8(blocks.reshape(-1, 64))).reshape(-1, 8, 8)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-2)


@pytest.mark.parametrize("n", [64, 512, 777])
@pytest.mark.parametrize("qscale", [1, 16, 99])
def test_dequant_idct_matches_ref(n, qscale):
    rng = np.random.RandomState(n + qscale)
    x = rng.randint(-200, 200, size=(n, 64)).astype(np.float32)
    q = np.clip(rng.randint(1, qscale + 1, size=64), 1, 255).astype(
        np.float32)
    out = np.asarray(ops.dequant_idct(x, q))
    want = np.asarray(ref.dequant_idct(jnp.asarray(x), jnp.asarray(q)))
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-3)
    # planes stay unclamped: only the RGB output is clamped
    assert out.min() < 0.0 and out.max() > 255.0


@pytest.mark.parametrize("n", [64, 512, 777])
@pytest.mark.parametrize("ntab", [1, 3, 24])
def test_decode_batch_matches_ref(n, ntab):
    """Batched kernel with per-row quant-table gather vs the jnp oracle
    (covers non-tile-multiple row counts and 1..many tables)."""
    rng = np.random.RandomState(n * 31 + ntab)
    x = rng.randint(-200, 200, size=(n, 64)).astype(np.float32)
    qt = np.clip(rng.randint(1, 99, size=(ntab, 64)), 1, 255).astype(
        np.float32)
    qi = rng.randint(0, ntab, size=n).astype(np.int32)
    out = np.asarray(ops.decode_batch(x, qi, qt))
    want = np.asarray(ref.decode_batch(jnp.asarray(x), jnp.asarray(qi),
                                       jnp.asarray(qt)))
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-3)
    # planes stay unclamped: only the RGB output is clamped
    assert out.min() < 0.0 and out.max() > 255.0


def test_decode_batch_single_table_matches_dequant_idct():
    """With one table the batched kernel degenerates to dequant_idct."""
    rng = np.random.RandomState(9)
    x = rng.randint(-200, 200, size=(640, 64)).astype(np.float32)
    q = rng.randint(1, 64, size=64).astype(np.float32)
    a = np.asarray(ops.decode_batch(x, np.zeros(640, np.int32), q[None]))
    b = np.asarray(ops.dequant_idct(x, q))
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-4)


@pytest.mark.parametrize("hw", [(8, 128), (64, 64), (100, 130), (17, 23)])
def test_ycbcr2rgb_matches_ref(hw):
    h, w = hw
    rng = np.random.RandomState(h * w)
    y = rng.uniform(0, 255, (h, w)).astype(np.float32)
    cb = rng.uniform(0, 255, (h, w)).astype(np.float32)
    cr = rng.uniform(0, 255, (h, w)).astype(np.float32)
    out = np.asarray(ops.ycbcr2rgb(y, cb, cr))
    r, g, b = ref.ycbcr2rgb(jnp.asarray(y), jnp.asarray(cb), jnp.asarray(cr))
    want = np.stack([np.asarray(r), np.asarray(g), np.asarray(b)], axis=-1)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-3)


def test_idct_roundtrip_with_fdct():
    """FDCT (encoder) then kernel IDCT recovers the original block."""
    rng = np.random.RandomState(3)
    blocks = rng.uniform(-128, 127, (16, 8, 8))
    c = T.dct_matrix()
    coefs = np.einsum("ki,nij,lj->nkl", c, blocks, c)
    got = np.asarray(ops.idct8x8(
        coefs.reshape(-1, 64).astype(np.float32))).reshape(-1, 8, 8)
    np.testing.assert_allclose(got, blocks, atol=5e-3)


@pytest.mark.parametrize("shape", [(2, 64, 4, 16), (1, 128, 8, 32),
                                   (2, 96, 4, 16)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(shape, dtype, causal):
    import jax
    B, S, H, D = shape
    KV = H // 2
    rng = np.random.RandomState(S)
    q = rng.randn(B, S, H, D).astype(dtype) * 0.5
    k = rng.randn(B, S, KV, D).astype(dtype) * 0.5
    v = rng.randn(B, S, KV, D).astype(dtype) * 0.5
    out = np.asarray(ops.flash_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal),
        np.float32)
    # oracle with repeated KV
    kk = jnp.repeat(jnp.asarray(k), 2, axis=2)
    vv = jnp.repeat(jnp.asarray(v), 2, axis=2)
    qf = jnp.asarray(q).transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = kk.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    vf = vv.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    want = np.asarray(ref.flash_attention(qf, kf, vf, causal=causal),
                      np.float32).reshape(B, H, S, D).transpose(0, 2, 1, 3)
    tol = 2e-2 if dtype == "bfloat16" else 2e-5
    np.testing.assert_allclose(out, want, rtol=tol, atol=tol)
