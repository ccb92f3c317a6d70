"""The serving KV cache is written in place: the decode step writes one row
per slot and layer into a donated cache (the one-hot blend is kept only
where the rules shard ``kv_seq``), and admission writes the request's cache
into its slot with one jitted, donated function compiled once."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.configs import get_config
from repro.models.model import Model, ModelKnobs
from repro.parallel.sharding import axis_rules, make_rules
from repro.serve.engine import Engine, Request, ServeConfig

KNOBS = ModelKnobs(kv_chunk=16, ssm_chunk=8, moe_dispatch="dense")
B, S_MAX = 3, 32


def _seq_sharded_rules():
    """Rules that shard ``kv_seq`` (over 'model'), on a one-device mesh."""
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    return make_rules("cp").with_mesh(mesh)


def _model(arch):
    model = Model(get_config(arch, reduced=True), KNOBS)
    return model, model.init(jax.random.PRNGKey(0))


def _filled_cache(model, key, batch=B):
    """A cache holding values at every position, so stale rows at and
    past each slot's position would show in the logits if read."""
    leaves, tree = jax.tree.flatten(model.init_cache(batch, S_MAX))
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(tree, [
        jax.random.normal(k, x.shape, x.dtype) * 0.5
        for k, x in zip(keys, leaves)])


def _kv_leaves(model, cache):
    return [leaf for kind, c in zip(model.cfg.pattern, cache)
            if kind in ("attn", "mla") for leaf in c]


def _blend_step(model, rules):
    def step(params, cache, t, batch):
        with axis_rules(rules):
            return model.decode_step(params, cache, t, batch)
    return jax.jit(step)


@pytest.mark.parametrize("donate", [False, True])
@pytest.mark.parametrize("arch", ["smollm-135m", "deepseek-v2-236b"])
def test_in_place_decode_matches_blend(arch, donate):
    """Ragged per-slot positions over several steps, on an attention and
    an MLA arch: the in-place write and the blend give the same logits
    and equal caches."""
    model, params = _model(arch)
    rules = _seq_sharded_rules()
    assert model.kv_write_in_place(B, S_MAX)
    with axis_rules(rules):
        assert not model.kv_write_in_place(B, S_MAX)
    in_place = jax.jit(model.decode_step,
                       donate_argnums=(1,) if donate else ())
    blend = _blend_step(model, rules)
    cache_a = _filled_cache(model, jax.random.PRNGKey(1))
    cache_b = jax.tree.map(jnp.copy, cache_a)
    t = np.array([0, 5, 17], np.int32)
    for step in range(4):
        tok = {"tokens": jax.random.randint(jax.random.PRNGKey(10 + step),
                                            (B, 1), 0, model.cfg.vocab)}
        lg_b, cache_b = blend(params, cache_b, jnp.asarray(t), tok)
        arg = jax.tree.map(jnp.copy, cache_a) if donate else cache_a
        lg_a, cache_a = in_place(params, arg, jnp.asarray(t), tok)
        np.testing.assert_allclose(np.asarray(lg_a), np.asarray(lg_b),
                                   rtol=1e-5, atol=1e-5)
        for a, b in zip(jax.tree.leaves(cache_a), jax.tree.leaves(cache_b)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        t = t + np.array([1, 2, 1], np.int32)


def _big_elementwise(jaxpr, n):
    """Elementwise mul, add or select_n equations, sub-jaxprs (scan
    bodies) included, whose output has at least ``n`` elements."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("mul", "add", "select_n") and any(
                np.prod(v.aval.shape) >= n for v in eqn.outvars):
            found.append(eqn.primitive.name)
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                if hasattr(sub, "jaxpr") and hasattr(sub, "consts"):
                    found += _big_elementwise(sub.jaxpr, n)
                elif hasattr(sub, "eqns"):
                    found += _big_elementwise(sub, n)
    return found


def _decode_args(model, params):
    cache = model.init_cache(B, S_MAX)
    t = jnp.array([1, 4, 9], jnp.int32)
    return params, cache, t, {"tokens": jnp.zeros((B, 1), jnp.int32)}


def test_unsharded_decode_writes_rows_in_place():
    """No elementwise op the size of a layer's cache (the blend's
    signature), and the compiled step aliases every cache leaf to its
    output."""
    model, params = _model("smollm-135m")
    args = _decode_args(model, params)
    layer = int(np.prod(_kv_leaves(model, args[1])[0].shape[1:]))
    jaxpr = jax.make_jaxpr(model.decode_step)(*args).jaxpr
    assert _big_elementwise(jaxpr, layer) == []
    compiled = jax.jit(model.decode_step,
                       donate_argnums=(1,)).lower(*args).compile()
    aliased = compiled.as_text().split("input_output_alias={")[1]
    n_leaves = len(jax.tree.leaves(args[1]))
    assert aliased.split("}, entry")[0].count("alias") == n_leaves


def test_seq_sharded_decode_keeps_the_blend():
    """Under rules that shard ``kv_seq`` the decode blends the row into
    each layer's cache, and the engine counts its steps as blends."""
    model, params = _model("smollm-135m")
    rules = _seq_sharded_rules()
    args = _decode_args(model, params)
    layer = int(np.prod(_kv_leaves(model, args[1])[0].shape[1:]))

    def step(*a):
        with axis_rules(rules):
            return model.decode_step(*a)
    assert _big_elementwise(jax.make_jaxpr(step)(*args).jaxpr, layer)

    sc = ServeConfig(batch_size=2, s_max=S_MAX, max_new_tokens=4)
    eng = Engine(model, params, sc, rules=rules)
    eng.submit(Request(0, np.arange(5, dtype=np.int32)))
    eng.run()
    assert eng.counters["decode_blend"] == 3
    assert eng.counters["decode_in_place"] == 0
    plain = Engine(model, params, sc)
    plain.submit(Request(0, np.arange(5, dtype=np.int32)))
    plain.run()
    assert plain.counters == {"decode_in_place": 3, "decode_blend": 0,
                              "splice_in_place": 1}
    assert plain.results[0].tokens == eng.results[0].tokens


def test_splice_writes_one_slot_and_compiles_once():
    """Admission into slot k leaves slot k equal to the prefill's cache and
    every other slot as it was; three slots share one compile."""
    model, params = _model("smollm-135m")
    eng = Engine(model, params, ServeConfig(batch_size=4, s_max=S_MAX,
                                            max_new_tokens=8))
    eng.cache = _filled_cache(model, jax.random.PRNGKey(3), batch=4)
    compiled = []
    for k, n in [(2, 5), (0, 7), (3, 4)]:
        eng.active[:] = True
        eng.active[k] = False
        before = [np.asarray(x) for x in jax.tree.leaves(eng.cache)]
        prompt = np.arange(1, n + 1, dtype=np.int32)
        eng.submit(Request(k, prompt))
        eng._admit()
        compiled.append(eng._splice._cache_size())
        _, one, _ = eng._prefill_cache[n](
            params, {"tokens": jnp.asarray(prompt[None])},
            jnp.asarray([n - 1], jnp.int32))
        for old, new, got in zip(before, jax.tree.leaves(eng.cache),
                                 jax.tree.leaves(one)):
            new = np.asarray(new)
            np.testing.assert_array_equal(new[:, k], np.asarray(got)[:, 0])
            others = [s for s in range(4) if s != k]
            np.testing.assert_array_equal(new[:, others], old[:, others])
    assert eng.counters["splice_in_place"] == 3
    assert compiled[0] == compiled[-1]     # the compile cache did not grow
