"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in the Pallas
interpreter (``interpret=True``; the TPU compile of these kernels is
checked in test_tpu_compile.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention, matmul, rmsnorm
from repro.kernels import ref

KEY = jax.random.PRNGKey(7)


@pytest.mark.parametrize("mkn", [(128, 128, 128), (256, 512, 384),
                                 (64, 96, 32), (8, 8, 8), (512, 128, 256),
                                 # partial edge blocks in M and N
                                 (300, 640, 400),
                                 # K with no multiple of 128 dividing it
                                 (264, 576, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_allclose(mkn, dtype):
    M, K, N = mkn
    k1, k2 = jax.random.split(KEY)
    a = jax.random.normal(k1, (M, K), dtype)
    b = jax.random.normal(k2, (K, N), dtype)
    got = np.asarray(matmul(a, b, interpret=True), np.float32)
    want = np.asarray(ref.matmul_ref(a, b), np.float32)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * 8)


@pytest.mark.parametrize("shape", [(4, 64, 128), (3, 37, 96), (1, 1, 8),
                                   (2, 200, 256),
                                   (3, 100, 576)])    # partial last block
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_allclose(shape, dtype):
    k1, k2 = jax.random.split(KEY)
    x = jax.random.normal(k1, shape, dtype)
    w = (jax.random.normal(k2, shape[-1:]) * 0.1).astype(dtype)
    got = np.asarray(rmsnorm(x, w, interpret=True), np.float32)
    want = np.asarray(ref.rmsnorm_ref(x, w), np.float32)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.parametrize(
    "dims", [(2, 128, 128, 4, 2, 64),     # square causal GQA
             (1, 64, 256, 8, 8, 32),      # suffix queries (Sq < Skv)
             (2, 256, 256, 6, 2, 64),     # multi-tile both ways
             (1, 96, 96, 3, 1, 16),       # MQA, non-128 sizes
             (1, 300, 300, 2, 1, 32)])    # partial q block, whole KV
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_allclose(dims, dtype):
    B, Sq, Skv, H, KVH, d = dims
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (B, Sq, H, d), dtype)
    k = jax.random.normal(k2, (B, Skv, KVH, d), dtype)
    v = jax.random.normal(k3, (B, Skv, KVH, d), dtype)
    got = np.asarray(flash_attention(q, k, v, causal=True, interpret=True),
                     np.float32)
    want = np.asarray(ref.flash_attention_ref(q, k, v, causal=True),
                      np.float32)
    tol = 4e-2 if dtype == jnp.bfloat16 else 3e-4
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


def test_flash_attention_matches_model_reference_path():
    """The kernel and the model's chunked_attention agree (same math)."""
    from repro.models.layers import chunked_attention
    B, S, H, KVH, d = 2, 64, 4, 2, 32
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (B, S, H, d))
    k = jax.random.normal(k2, (B, S, KVH, d))
    v = jax.random.normal(k3, (B, S, KVH, d))
    pos = jnp.arange(S)
    a = chunked_attention(q, k, v, q_positions=pos, kv_positions=pos,
                          causal=True, kv_chunk=16)
    b = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-4,
                               atol=3e-4)


@pytest.mark.parametrize("op", ["matmul", "rmsnorm", "flash_attention"])
def test_kernels_refuse_silent_interpret(op):
    """Off the TPU, a kernel call without interpret=True raises instead of
    quietly running in the interpreter."""
    if jax.default_backend() == "tpu":
        pytest.skip("the TPU compiles the kernels")
    x = jnp.ones((8, 128), jnp.float32)
    q = jnp.ones((1, 8, 1, 128), jnp.float32)
    call = {"matmul": lambda: matmul(x, x.T),
            "rmsnorm": lambda: rmsnorm(x, x[0]),
            "flash_attention": lambda: flash_attention(q, q, q)}[op]
    with pytest.raises(RuntimeError, match="interpret=True"):
        call()
