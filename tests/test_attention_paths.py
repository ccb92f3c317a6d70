"""Which attention path the model traces: the Pallas flash kernel for
full-sequence causal self-attention on the TPU with no sharding rules
active, ``chunked_attention`` everywhere else (the CPU, latent attention's
lazily expanded keys, sharded programs).  ``layers.ATTENTION_CALLS``
counts the calls of each path as they are traced.

Off the chip the TPU is simulated by reporting it as the backend while
the step is traced (``jax.make_jaxpr``), which lowers nothing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.configs import get_config
from repro.models import layers as L
from repro.models.model import Model, ModelKnobs
from repro.parallel.sharding import axis_rules, make_rules

KNOBS = ModelKnobs(kv_chunk=16, ssm_chunk=8, moe_dispatch="dense")


def _traced_loss(arch, rules=None):
    """The loss and its gradient traced once; returns the jaxpr's text and
    the attention calls counted while tracing."""
    model = Model(get_config(arch, reduced=True), KNOBS)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 40), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens}
    before = dict(L.ATTENTION_CALLS)
    with axis_rules(rules):
        jaxpr = jax.make_jaxpr(jax.grad(model.loss))(params, batch)
    return str(jaxpr), {k: L.ATTENTION_CALLS[k] - before[k]
                        for k in before}


def _one_device_rules():
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    return make_rules("cp").with_mesh(mesh)


@pytest.mark.parametrize("backend,arch,rules,path", [
    ("tpu", "smollm-135m", None, "flash"),
    ("cpu", "smollm-135m", None, "chunked"),
    ("tpu", "smollm-135m", "one-device", "chunked"),
    ("tpu", "deepseek-v2-236b", None, "chunked"),
], ids=["tpu", "cpu", "tpu-rules", "tpu-mla"])
def test_attention_path_dispatch(monkeypatch, backend, arch, rules, path):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    text, calls = _traced_loss(
        arch, _one_device_rules() if rules == "one-device" else None)
    other = {"flash": "chunked", "chunked": "flash"}[path]
    assert calls[path] > 0 and calls[other] == 0, calls
    kernels = ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")
    if path == "flash":
        assert all(k in text for k in kernels)
    else:
        assert not any(k in text for k in kernels)


def test_flash_applies_reads_backend_and_rules(monkeypatch):
    assert not L.flash_applies()            # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert L.flash_applies()
    with axis_rules(make_rules("cp")):      # rules with no mesh: inactive
        assert L.flash_applies()
    with axis_rules(_one_device_rules()):
        assert not L.flash_applies()
