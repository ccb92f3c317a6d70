"""chip_smoke.py's phases at reduced size on the CPU, kernels in the Pallas
interpreter (``interpret=True``, passed explicitly); the engine on a prompt
length no KV chunk divides; jaxdist on a (2, 1, 2) mesh of 4 of the 8
virtual devices; and where the persistent compilation cache lands.
"""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch import compile_cache
from repro.models.model import Model, ModelKnobs
from repro.serve.engine import Engine, Request, ServeConfig

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke(tmp_path_factory):
    """The chip_smoke module.  The drivers it calls turn the persistent
    compilation cache on: keep it in a temporary directory, and off again
    for the rest of this process."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    with pytest.MonkeyPatch.context() as mp:
        mp.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        mp.setattr(compile_cache, "DEFAULT_DIR",
                   tmp_path_factory.mktemp("jax_cache"))
        yield mod
    jax.config.update("jax_compilation_cache_dir", was[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", was[1])
    cc.reset_cache()


def test_phase_train(smoke):
    out = smoke.phase_train(reduced=True, batch=2, seq=32, steps=2)
    assert len(out["losses"]) == 2 and out["compile_s"] > 0


def test_phase_serve(smoke):
    # prompts of 20-45 tokens against the driver's kv_chunk of 32
    out = smoke.phase_serve(reduced=True, batch=2, s_max=64, requests=3,
                            prompt_len=(20, 45), max_new=4)
    assert out["warm_tokens"] == 3 * 4


def test_phase_tune(smoke):
    out = smoke.phase_tune(reduced=True, batch=2, seq=16, max_configs=2)
    assert out["executed"] > 0 and out["speedup"] > 0


def test_phase_kernels_interpret(smoke):
    cfg = get_config(smoke.ARCH, reduced=True)
    cases = smoke.kernel_cases(d_model=cfg.d_model, d_ff=cfg.d_ff,
                               n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                               head_dim=cfg.head_dim, tokens=300, seq=200)
    out = smoke.phase_kernels(cases, interpret=True)
    assert set(out) == {label for label, _, _ in cases}


def test_phase_jaxdist_on_four_of_eight_devices(smoke):
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    out = smoke.phase_jaxdist(m=64, n=64, tall=512, panel=16, block=16)
    assert out["mesh"] == {"x": 2, "y": 1, "z": 2}


def test_engine_serves_prompt_no_kv_chunk_divides():
    """A 300-token prompt with kv_chunk 32: the engine's first token is
    the argmax of a plain full-sequence forward in one KV chunk."""
    cfg = get_config("smollm-135m", reduced=True)
    model = Model(cfg, ModelKnobs(kv_chunk=32))
    params = model.init(jax.random.PRNGKey(0))
    prompt = np.random.default_rng(0).integers(0, cfg.vocab, 300) \
        .astype(np.int32)
    eng = Engine(model, params, ServeConfig(batch_size=1, s_max=320,
                                            max_new_tokens=3))
    eng.submit(Request(0, prompt))
    toks = eng.run()[0].tokens
    ref = Model(cfg, ModelKnobs(kv_chunk=1024))
    logits = ref.forward(params, {"tokens": prompt[None]})
    assert len(toks) == 3
    assert toks[0] == int(np.argmax(np.asarray(logits[0, -1])))


def _cache_dir_after_compile(env):
    code = ("import jax\n"
            "from repro.launch.compile_cache import enable_compilation_cache"
            "\nprint(enable_compilation_cache())\n"
            "jax.jit(lambda x: x * 3 + 1)(2.0).block_until_ready()\n")
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True, timeout=120)
    return Path(out.stdout.strip().splitlines()[-1])


def test_compile_cache_follows_env_else_repo_root(tmp_path):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    assert _cache_dir_after_compile(env) == tmp_path
    assert any(tmp_path.iterdir()), "no cache entry written"
    del env["JAX_COMPILATION_CACHE_DIR"]
    code = ("from repro.launch.compile_cache import enable_compilation_cache"
            "\nprint(enable_compilation_cache())\n")
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True, timeout=120)
    assert Path(out.stdout.strip()) == ROOT / ".jax_cache"


def test_chip_smoke_refuses_without_a_tpu():
    """Off the chip it exits non-zero and prints no result line."""
    if jax.default_backend() == "tpu":
        pytest.skip("a TPU is present")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
