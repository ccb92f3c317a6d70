"""Compile the main path's kernels and the full-width decode step for a
described TPU v5e, with no chip attached.

Nothing runs: this checks what the chip's compiler refuses (block shapes
not aligned to the (8, 128) tiling, too much fast memory, a program that
does not fit the device), which interpret-mode tests cannot see.  The
topology is described inside a fixture, never at import: only one process
at a time may load the TPU library, and every test worker imports this
file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.matmul import matmul_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.models.model import Model, ModelKnobs


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# smollm-135m widths: d_model 576, d_ff 1536, 9 heads (3 KV) of 64
@pytest.mark.parametrize("mkn", [(4096, 576, 1536),    # FFN up: K 576 whole
                                 (4096, 1536, 576),    # FFN down
                                 (300, 576, 1536)])    # partial M block
def test_matmul_compiles(one_chip, mkn):
    M, K, N = mkn
    c = _compile(matmul_pallas, _sds(one_chip, (M, K)),
                 _sds(one_chip, (K, N)))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("shape", [(3, 100, 576),      # 300 rows: partial
                                   (8, 2048, 576)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_compiles(one_chip, shape, dtype):
    c = _compile(rmsnorm_pallas, _sds(one_chip, shape, dtype),
                 _sds(one_chip, shape[-1:], dtype))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("sq", [2048, 1], ids=["prefill", "decode"])
def test_flash_attention_compiles(one_chip, sq):
    q = _sds(one_chip, (1, 9, sq, 64))
    kv = _sds(one_chip, (1, 3, 2048, 64))
    c = _compile(flash_attention_pallas, q, kv, kv)
    assert "tpu_custom_call" in c.as_text()


def test_smollm_decode_step_compiles(one_chip):
    """The engine's decode step at published widths, batch 8, s_max 2048,
    fits one chip."""
    model = Model(get_config("smollm-135m"), ModelKnobs(kv_chunk=32))
    on_chip = lambda t: jax.tree.map(                       # noqa: E731
        lambda a: _sds(one_chip, a.shape, a.dtype), t)
    params = on_chip(model.param_shapes())
    cache = on_chip(jax.eval_shape(lambda: model.init_cache(8, 2048)))
    t = _sds(one_chip, (8,), jnp.int32)
    tok = {"tokens": _sds(one_chip, (8, 1), jnp.int32)}
    c = _compile(model.decode_step, params, cache, t, tok)
    mem = c.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < 16 * 2**30, used
