"""Per-arch smoke tests (reduced configs) + prefill/decode consistency."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, supported_shapes
from repro.models.model import Model, ModelKnobs

KNOBS = ModelKnobs(kv_chunk=16, ssm_chunk=8)


def make_batch(cfg, B=2, S=32, key=None):
    key = key if key is not None else jax.random.PRNGKey(0)
    tshape = (B, S, cfg.n_codebooks) if cfg.n_codebooks else (B, S)
    batch = {"tokens": jax.random.randint(key, tshape, 0, cfg.vocab),
             "labels": jax.random.randint(key, tshape, 0, cfg.vocab)}
    if cfg.n_patches:
        batch["patches"] = jax.random.normal(
            key, (B, cfg.n_patches, cfg.d_model)) * 0.1
    return batch


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_smoke_forward_loss_decode(arch):
    cfg = get_config(arch, reduced=True)
    model = Model(cfg, KNOBS)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg)
    loss = jax.jit(model.loss)(params, batch)
    assert np.isfinite(float(loss))
    logits = model.forward(params, batch)
    assert logits.shape[-1] == cfg.vocab
    assert np.all(np.isfinite(np.asarray(logits)))
    # one decode step from an empty cache
    cache = model.init_cache(2, 64)
    tok = batch["tokens"][:, :1]
    lg, cache2 = jax.jit(model.decode_step)(params, cache, jnp.int32(0),
                                            {"tokens": tok})
    assert np.all(np.isfinite(np.asarray(lg)))


@pytest.mark.parametrize("arch", ["smollm-135m", "deepseek-v2-236b",
                                  "jamba-v0.1-52b", "xlstm-125m",
                                  "musicgen-large"])
def test_prefill_decode_matches_forward(arch):
    """Teacher-forced decode after prefill must reproduce the full-sequence
    forward logits — validates every cache layout (KV, latent, conv, ssm,
    mlstm, slstm) and the decode attention masks."""
    cfg = get_config(arch, reduced=True)
    if cfg.moe is not None:
        # capacity drops legitimately differ between prompt lengths; kill
        # drops so the cache-consistency comparison is exact
        from dataclasses import replace as drep
        cfg = drep(cfg, moe=drep(cfg.moe, capacity_factor=64.0))
    model = Model(cfg, KNOBS)
    params = model.init(jax.random.PRNGKey(1))
    B, S, S_pre = 2, 16, 8
    batch = make_batch(cfg, B, S, jax.random.PRNGKey(2))
    full_logits = np.asarray(model.forward(params, batch))
    if cfg.n_patches:   # decode positions offset by the patch prefix
        pytest.skip("vlm decode covered via smoke (patch prefix offsets)")

    toks = batch["tokens"]
    lg, cache, t0 = jax.jit(lambda p, b: model.prefill(p, b, S))(
        params, {"tokens": toks[:, :S_pre]})
    np.testing.assert_allclose(np.asarray(lg),
                               full_logits[:, S_pre - 1], rtol=2e-2,
                               atol=2e-3)
    step = jax.jit(model.decode_step)
    for t in range(S_pre, S):
        lg, cache = step(params, cache, jnp.int32(t),
                         {"tokens": toks[:, t:t + 1]})
        np.testing.assert_allclose(np.asarray(lg), full_logits[:, t],
                                   rtol=2e-2, atol=2e-3)


def test_vlm_prefill_decode_matches_forward():
    """internvl2: decode after a (patches + text) prefill reproduces the
    full-sequence forward logits — validates the patch-prefix position
    offsets through the cache."""
    cfg = get_config("internvl2-2b", reduced=True)
    model = Model(cfg, KNOBS)
    params = model.init(jax.random.PRNGKey(1))
    B, S_text, S_pre = 2, 12, 6
    key = jax.random.PRNGKey(2)
    toks = jax.random.randint(key, (B, S_text), 0, cfg.vocab)
    patches = jax.random.normal(key, (B, cfg.n_patches, cfg.d_model)) * 0.1
    full_logits = np.asarray(model.forward(
        params, {"tokens": toks, "patches": patches}))
    P_ = cfg.n_patches
    s_max = P_ + S_text + 4
    lg, cache, t0 = model.prefill(
        params, {"tokens": toks[:, :S_pre], "patches": patches}, s_max)
    np.testing.assert_allclose(np.asarray(lg),
                               full_logits[:, P_ + S_pre - 1],
                               rtol=2e-2, atol=2e-3)
    step = jax.jit(model.decode_step)
    for i in range(S_pre, S_text):
        t = P_ + i                      # absolute position in the cache
        lg, cache = step(params, cache, jnp.int32(t),
                         {"tokens": toks[:, i:i + 1]})
        np.testing.assert_allclose(np.asarray(lg), full_logits[:, P_ + i],
                                   rtol=2e-2, atol=2e-3)


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_grads_finite(arch):
    cfg = get_config(arch, reduced=True)
    model = Model(cfg, KNOBS)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg)
    g = jax.jit(jax.grad(model.loss))(params, batch)
    gnorm = float(jnp.sqrt(sum(jnp.sum(l.astype(jnp.float32) ** 2)
                               for l in jax.tree.leaves(g))))
    assert np.isfinite(gnorm) and gnorm > 0


def test_long_500k_skip_policy():
    runnable = {a: supported_shapes(get_config(a)) for a in ARCHS}
    assert "long_500k" in runnable["xlstm-125m"]
    assert "long_500k" in runnable["jamba-v0.1-52b"]
    assert "long_500k" not in runnable["yi-34b"]
    total = sum(len(v) for v in runnable.values())
    assert total == 32          # 10*3 + 2 runnable cells


def test_mlstm_chunkwise_matches_recurrent():
    """The chunkwise-parallel mLSTM (EXPERIMENTS.md §Perf H2-k) is an exact
    reformulation: outputs AND carried state match the recurrent oracle."""
    from repro.models import ssm as S
    cfg = get_config("xlstm-125m", reduced=True)
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 9)
    D, di, nh = cfg.d_model, cfg.d_inner, cfg.n_heads
    p = {"ln": jnp.zeros(D),
         "up": jax.random.normal(ks[0], (D, 2 * di)) * 0.05,
         "conv_w": jax.random.normal(ks[1], (cfg.d_conv, di)) * 0.1,
         "wq": jax.random.normal(ks[2], (di, di)) * 0.05,
         "wk": jax.random.normal(ks[3], (di, di)) * 0.05,
         "wv": jax.random.normal(ks[4], (di, di)) * 0.05,
         "wif": jax.random.normal(ks[5], (di, 2 * nh)) * 0.5,
         "b_if": jax.random.normal(ks[6], (2 * nh,)) * 0.5,
         "down": jax.random.normal(ks[7], (di, D)) * 0.05}
    x = jax.random.normal(ks[8], (2, 48, D))
    y_r, (_, st_r) = S.mlstm_block(p, x, cfg, chunk=16, mode="recurrent")
    y_c, (_, st_c) = S.mlstm_block(p, x, cfg, chunk=16, mode="chunkwise")
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_r),
                               rtol=1e-4, atol=1e-5)
    for a, b in zip(st_r, st_c):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-3, atol=1e-4)


def test_remat_matches_no_remat():
    cfg = get_config("smollm-135m", reduced=True)
    batch = make_batch(cfg)
    p = Model(cfg, KNOBS).init(jax.random.PRNGKey(0))
    l1 = Model(cfg, KNOBS).loss(p, batch)
    from dataclasses import replace
    l2 = Model(cfg, replace(KNOBS, remat="none")).loss(p, batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)


@pytest.mark.parametrize("sq,skv,chunk,causal", [
    (300, 300, 32, True),       # prompt length no chunk divides
    (20, 300, 32, True),        # suffix queries against a ragged KV
    (37, 100, 64, False),       # one full chunk + a shifted partial one
    (64, 64, 16, True)])        # divisible: the unshifted path
def test_chunked_attention_ragged_matches_naive(sq, skv, chunk, causal):
    from repro.kernels.ref import flash_attention_ref
    from repro.models.layers import chunked_attention
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(k1, (2, sq, 4, 16))
    k = jax.random.normal(k2, (2, skv, 2, 16))
    v = jax.random.normal(k3, (2, skv, 2, 16))
    got = chunked_attention(q, k, v, q_positions=jnp.arange(skv - sq, skv),
                            kv_positions=jnp.arange(skv), causal=causal,
                            kv_chunk=chunk)
    want = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("arch", ["smollm-135m", "deepseek-v2-236b"])
def test_ragged_kv_chunk_forward_matches_single_chunk(arch):
    """GQA and MLA (lazy per-chunk K/V) at a length no kv_chunk divides
    give the logits of one whole-sequence chunk."""
    cfg = get_config(arch, reduced=True)
    if cfg.moe is not None:
        from dataclasses import replace as drep
        cfg = drep(cfg, moe=drep(cfg.moe, capacity_factor=64.0))
    batch = make_batch(cfg, 2, 40, jax.random.PRNGKey(4))
    params = Model(cfg, KNOBS).init(jax.random.PRNGKey(5))
    ragged = Model(cfg, ModelKnobs(kv_chunk=16, ssm_chunk=8))
    whole = Model(cfg, ModelKnobs(kv_chunk=64, ssm_chunk=8))
    np.testing.assert_allclose(np.asarray(ragged.forward(params, batch)),
                               np.asarray(whole.forward(params, batch)),
                               rtol=2e-4, atol=2e-4)
