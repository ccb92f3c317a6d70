"""The benchmark's FLOP counts (bench/harness/flops.py) against the
program's jaxpr cost model (launch/jaxpr_cost.py), at a small size and
remat "none", where the two must agree on matrix-product FLOPs.  The
jaxpr model counts the full square of attention scores, so attention is
compared with ``causal=False``."""

import dataclasses
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import benchtiny  # noqa: E402,F401  (puts bench/ and src/ on the path)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from harness import flops as F  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.launch.jaxpr_cost import cost_of  # noqa: E402
from repro.models import layers as ML  # noqa: E402
from repro.models.model import Model, ModelKnobs, init_params  # noqa: E402

B, S = 2, 32


@pytest.fixture(scope="module")
def cfg():
    # heads of d_model / n_heads, as the published configurations have
    return dataclasses.replace(get_config("smollm-135m", reduced=True),
                               d_head=0)


def _block(cfg, prefix):
    p = init_params(cfg, jax.random.PRNGKey(0))["pos0"]
    return {k[len(prefix):]: v[0] for k, v in p.items()
            if k.startswith(prefix)}


def test_dense_fb_matches_jaxpr(cfg):
    ffn = _block(cfg, "ffn_")
    x = jnp.ones((B, S, cfg.d_model))
    f = jax.grad(lambda p, xx: jnp.sum(ML.ffn_block(p, xx, cfg) ** 2))
    want = cost_of(f, ffn, x).dot_flops
    got, _ = F.dense_fb(B, S, cfg.d_model, cfg.d_ff)
    assert got == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("kv_chunk", [S, S // 4])
def test_attn_fb_matches_jaxpr(cfg, kv_chunk):
    mix = _block(cfg, "mix_")
    x = jnp.ones((B, S, cfg.d_model))

    def fwd(p, xx):
        h, _ = ML.attn_block(p, xx, cfg, positions=jnp.arange(S),
                             kv_chunk=kv_chunk)
        return jnp.sum(h * h)
    want = cost_of(jax.grad(fwd), mix, x).dot_flops
    got, _ = F.attn_fb(B, S, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                       cfg.head_dim, causal=False)
    assert got == pytest.approx(want, rel=1e-9)
    causal, _ = F.attn_fb(B, S, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                          cfg.head_dim)
    assert causal < got


def test_train_flops_match_jaxpr(cfg):
    model = Model(cfg, ModelKnobs(kv_chunk=S, remat="none"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((B, S), jnp.int32),
             "labels": jnp.zeros((B, S), jnp.int32)}
    want = cost_of(jax.grad(model.loss), params, batch).dot_flops
    c = {"hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
         "num_attention_heads": cfg.n_heads,
         "num_key_value_heads": cfg.n_kv_heads,
         "num_hidden_layers": cfg.n_layers, "vocab_size": cfg.vocab}
    got = F.train_flops_per_token(c, S, causal=False) * B * S
    assert got == pytest.approx(want, rel=1e-9)
