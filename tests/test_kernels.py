"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def _rand(shape, dtype):
    x = RNG.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype)


# ------------------------------------------------------------ flash attention

FLASH_CASES = [
    # (B, T, S, H, Hkv, D, causal, window, bq, bk)
    (1, 128, 128, 2, 2, 64, True, 0, 128, 128),
    (2, 256, 256, 4, 2, 64, True, 0, 128, 64),
    (1, 128, 128, 4, 1, 128, True, 64, 64, 64),
    (1, 256, 256, 2, 2, 32, False, 0, 128, 128),
    (2, 128, 128, 8, 4, 64, True, 32, 64, 32),
    (1, 512, 512, 2, 1, 64, True, 128, 128, 128),
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(case, dtype):
    B, T, S, H, Hkv, D, causal, window, bq, bk = case
    q = _rand((B, T, H, D), dtype)
    k = _rand((B, S, Hkv, D), dtype)
    v = _rand((B, S, Hkv, D), dtype)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=bq, block_k=bk)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_matches_model_chunked_attention():
    """Kernel ≡ the GSPMD chunked-attention path used in the dry-run."""
    from repro.models.attention import chunked_attention
    q = _rand((2, 128, 4, 64), jnp.float32)
    k = _rand((2, 128, 2, 64), jnp.float32)
    v = _rand((2, 128, 2, 64), jnp.float32)
    a = ops.flash_attention(q, k, v, causal=True)
    b = chunked_attention(q, k, v, causal=True, kv_chunk=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)


# -------------------------------------------------------------- fedavg reduce

@pytest.mark.parametrize("K,N,bn,bk", [(5, 1000, 256, 2), (16, 4096, 2048, 8),
                                       (3, 7, 2048, 8), (64, 513, 128, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fedavg_reduce_matches_ref(K, N, bn, bk, dtype):
    u = _rand((K, N), dtype)
    w = jnp.asarray(RNG.uniform(0.1, 5.0, (K,)), jnp.float32)
    got = ops.fedavg_reduce(u, w, block_n=bn, block_k=bk)
    want = ref.fedavg_reduce_ref(u, w)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 12), st.integers(1, 300), st.integers(0, 100))
def test_fedavg_reduce_property(K, N, seed):
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.01, 10.0, (K,)), jnp.float32)
    got = ops.fedavg_reduce(u, w)
    want = ref.fedavg_reduce_ref(u, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------------- quantize

@pytest.mark.parametrize("N,block", [(1024, 256), (256 * 192, 256),
                                     (512, 128), (4096, 512)])
def test_quantize_roundtrip(N, block):
    x = _rand((N,), jnp.float32)
    q, s = ops.quantize(x, block=block, rows_per_tile=1)
    qr, sr = ref.quantize_ref(x, block=block)
    assert bool((np.asarray(q) == np.asarray(qr)).all())
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    d = ops.dequantize(q, s, block=block, rows_per_tile=1)
    # int8 symmetric: relative reconstruction error bounded by 1/127 per block
    err = np.abs(np.asarray(d) - np.asarray(x))
    per_block_max = np.abs(np.asarray(x)).reshape(-1, block).max(1)
    assert (err.reshape(-1, block).max(1) <= per_block_max / 127.0 + 1e-6).all()


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 8), st.integers(0, 1000))
def test_quantize_property_blocks(nblocks, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(nblocks * 256) * rng.uniform(0.01, 100),
                    jnp.float32)
    q, s = ops.quantize(x, block=256, rows_per_tile=1)
    qr, sr = ref.quantize_ref(x, block=256)
    assert bool((np.asarray(q) == np.asarray(qr)).all())
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
