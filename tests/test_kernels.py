"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in the Pallas
interpreter (``interpret=True``; the TPU compile of these kernels is
checked in test_tpu_compile.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention, matmul, rmsnorm
from repro.kernels import ref
from repro.kernels.tiles import FlashTiles

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
    "dims", [(2, 128, 4, 2, 64),      # square causal GQA
             (1, 64, 8, 8, 32),       # MHA, one partial tile
             (2, 256, 6, 2, 64),      # two tiles
             (1, 96, 3, 1, 16),       # MQA, non-128 sizes
             (1, 300, 2, 1, 32)])     # ragged length
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_allclose(dims, dtype):
    B, S, H, KVH, d = dims
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (B, S, H, d), dtype)
    k = jax.random.normal(k2, (B, S, KVH, d), dtype)
    v = jax.random.normal(k3, (B, S, KVH, d), dtype)
    got = flash_attention(q, k, v, interpret=True)
    assert got.dtype == dtype
    want = np.asarray(ref.flash_attention_ref(q, k, v, causal=True),
                      np.float32)
    tol = 4e-2 if dtype == jnp.bfloat16 else 3e-4
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=tol,
                               atol=tol)


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
    b = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-4,
                               atol=3e-4)


def _rel_gap(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


# (B, S, H, KVH, d, tiles): tiles None are the shape's own
# (tiles.flash_tiles); the others make several tiles of unequal sizes, so
# the causal skips and clamped index maps of every kernel are crossed.
GRAD_CASES = {
    "g3-d64-one-tile": (2, 128, 6, 2, 64, None),
    "g4-d64-tiles": (1, 512, 4, 1, 64,
                     FlashTiles(fwd=(128, 256), dkv=(256, 128),
                                dq=(128, 128))),
    "g3-d64-ragged": (1, 300, 3, 1, 64, None),
    "g4-d128-tiles": (1, 384, 8, 2, 128,
                      FlashTiles(fwd=(128, 128), dkv=(128, 384),
                                 dq=(384, 128))),
    "g4-d128-ragged": (1, 200, 4, 1, 128, None),
}


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("case", sorted(GRAD_CASES))
def test_flash_attention_grads(case, remat):
    """Output and dq, dk, dv (through ``jax.grad``, under ``jax.checkpoint``
    as the train step's ``remat full`` runs it) against the f32 oracle and
    the model's chunked_attention."""
    from repro.models.layers import chunked_attention
    B, S, H, KVH, d, tiles = GRAD_CASES[case]
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(S + d), 4)
    q = jax.random.normal(k1, (B, S, H, d))
    k = jax.random.normal(k2, (B, S, KVH, d))
    v = jax.random.normal(k3, (B, S, KVH, d))
    w = jax.random.normal(k4, (B, S, H, d))
    pos = jnp.arange(S)
    paths = {
        "flash": lambda q, k, v: flash_attention(q, k, v, tiles=tiles,
                                                 interpret=True),
        "oracle": lambda q, k, v: ref.flash_attention_ref(q, k, v),
        "chunked": lambda q, k, v: chunked_attention(
            q, k, v, q_positions=pos, kv_positions=pos, kv_chunk=64)}
    out = {}
    for name, attn in paths.items():
        loss = lambda q, k, v, attn=attn: jnp.sum(attn(q, k, v) * w)
        if remat and name == "flash":
            loss = jax.checkpoint(loss)
        out[name] = (attn(q, k, v),) + jax.grad(loss, (0, 1, 2))(q, k, v)
    for want in ("oracle", "chunked"):
        gaps = [_rel_gap(a, b) for a, b in zip(out["flash"], out[want])]
        assert max(gaps) < 3e-4, (want, gaps)


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
