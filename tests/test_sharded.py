"""Multi-device (8 virtual) integration: sharding rules, MoE EP dispatch,
compressed collectives, jaxdist algorithms, sharded train step."""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.launch.mesh import make_host_mesh
from repro.models import moe as MM
from repro.models.model import Model, ModelKnobs
from repro.parallel.sharding import axis_rules, make_rules
from repro.train.optim import AdamWConfig, adamw_init
from repro.train.step import TrainConfig, make_train_step

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")


def test_rules_spec_dedup_and_fallback():
    mesh = make_host_mesh(model=4)        # (2, 4) data, model
    rules = make_rules("cp").with_mesh(mesh)
    # seq gets model; vocab (also model) must be dropped in the same spec
    s = rules.spec("batch", "seq", "vocab", dims=(4, 8, 12))
    assert s[1] == "model" and (len(s) < 3 or s[2] is None)
    # divisibility fallback: batch=1 cannot shard
    s2 = rules.spec("batch", None, dims=(1, 8))
    assert len(s2) == 0 or s2[0] is None
    # 'pod' axis silently dropped on a pod-less mesh
    assert all(ax in ("data", "model")
               for ax in (rules.mesh_axes("batch") or ()))


def test_rules_spec_properties():
    """Property test: for any logical-axes assignment and dims, the spec
    (a) never uses a mesh axis twice, (b) only shards divisible dims."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    mesh = make_host_mesh(model=4)        # (2, 4) data, model
    sizes = {"data": 2, "model": 4}
    logicals = ["batch", "seq", "ffn", "vocab", "embed", "tokens",
                "fsdp_embed", "expert", None]

    @given(st.lists(st.sampled_from(logicals), min_size=1, max_size=4),
           st.lists(st.integers(min_value=1, max_value=64), min_size=4,
                    max_size=4),
           st.sampled_from(["cp", "tp", "dp"]))
    @settings(max_examples=150, deadline=None)
    def check(axes, dims, variant):
        rules = make_rules(variant).with_mesh(mesh)
        spec = rules.spec(*axes, dims=dims[:len(axes)])
        used = []
        for i, entry in enumerate(spec):
            if entry is None:
                continue
            es = (entry,) if isinstance(entry, str) else tuple(entry)
            prod = 1
            for ax in es:
                assert ax not in used, (spec, axes)
                used.append(ax)
                prod *= sizes[ax]
            assert dims[i] % prod == 0, (spec, axes, dims)

    check()


def test_moe_dispatch_equivalence_all_regimes():
    cfg = get_config("phi3.5-moe", reduced=True)
    cfg = replace(cfg, moe=replace(cfg.moe, capacity_factor=8.0))
    mesh = make_host_mesh(model=4)
    key = jax.random.PRNGKey(0)
    D, E, F = cfg.d_model, cfg.moe.n_experts, cfg.moe.d_ff_expert
    ks = jax.random.split(key, 5)
    p = {"ln": jnp.zeros(D),
         "router": jax.random.normal(ks[0], (D, E)) * 0.1,
         "w_gate": jax.random.normal(ks[1], (E, D, F)) * 0.05,
         "w_up": jax.random.normal(ks[2], (E, D, F)) * 0.05,
         "w_down": jax.random.normal(ks[3], (E, F, D)) * 0.05}
    x = jax.random.normal(ks[4], (8, 16, D))
    y_ref = jax.jit(lambda p, x: MM.moe_ffn(p, x, cfg, dispatch="sort"))(p, x)
    for variant in ("cp", "tp", "dp"):
        rules = make_rules(variant).with_mesh(mesh)
        with axis_rules(rules):
            y = jax.jit(
                lambda p, x: MM.moe_ffn(p, x, cfg, dispatch="a2a"))(p, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=2e-5, atol=2e-5)


def test_sharded_train_step_matches_unsharded():
    """One optimizer step on the mesh == the single-device step."""
    cfg = get_config("smollm-135m", reduced=True)
    knobs = ModelKnobs(kv_chunk=16, ssm_chunk=8)
    model = Model(cfg, knobs)
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    B, S = 4, 32
    key = jax.random.PRNGKey(1)
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab),
             "labels": jax.random.randint(key, (B, S), 0, cfg.vocab)}
    tc = TrainConfig(grad_accum=2,
                     optimizer=AdamWConfig(lr=1e-3, warmup=1))
    ref_step = jax.jit(make_train_step(model, None, tc))
    p_ref, o_ref, m_ref = ref_step(params, opt, batch)

    mesh = make_host_mesh(model=4)
    rules = make_rules("cp").with_mesh(mesh)
    sh_step = jax.jit(make_train_step(model, rules, tc))
    p_sh, o_sh, m_sh = sh_step(params, opt, batch)
    np.testing.assert_allclose(float(m_sh["loss"]), float(m_ref["loss"]),
                               rtol=1e-5)
    l_ref = jax.tree.leaves(p_ref)
    l_sh = jax.tree.leaves(p_sh)
    for a, b in zip(l_ref, l_sh):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-4, atol=5e-5)


def test_grad_accum_invariance():
    """ga=1 and ga=4 produce the same update on the same global batch."""
    cfg = get_config("smollm-135m", reduced=True)
    model = Model(cfg, ModelKnobs(kv_chunk=16, ssm_chunk=8))
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    key = jax.random.PRNGKey(2)
    batch = {"tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab),
             "labels": jax.random.randint(key, (8, 32), 0, cfg.vocab)}
    outs = {}
    for ga in (1, 4):
        tc = TrainConfig(grad_accum=ga,
                         optimizer=AdamWConfig(lr=1e-3, warmup=1))
        step = jax.jit(make_train_step(model, None, tc))
        p, _, m = step(params, opt, batch)
        outs[ga] = (p, float(m["loss"]))
    np.testing.assert_allclose(outs[1][1], outs[4][1], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(outs[1][0]),
                    jax.tree.leaves(outs[4][0])):
        # microbatched mean reassociates float reductions: loose tolerance
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=1e-4)


def test_pipeline_parallel_matches_reference():
    """GPipe-style pipeline over 'pod': loss and grads match the plain
    model (exact schedule equivalence through ppermute transposes)."""
    from repro.parallel.pipeline import pipeline_loss
    cfg = get_config("smollm-135m", reduced=True)
    model = Model(cfg, ModelKnobs(kv_chunk=16, ssm_chunk=8))
    params = model.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    batch = {"tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab),
             "labels": jax.random.randint(key, (8, 32), 0, cfg.vocab)}
    ref = float(jax.jit(model.loss)(params, batch))
    mesh = make_host_mesh(model=2, pod=2)
    rules = make_rules("cp").with_mesh(mesh)
    got = float(jax.jit(
        lambda p, b: pipeline_loss(model, rules, p, b, n_mb=4))(
            params, batch))
    np.testing.assert_allclose(got, ref, rtol=2e-3)
    g = jax.jit(jax.grad(
        lambda p, b: pipeline_loss(model, rules, p, b, n_mb=4)))(
            params, batch)
    g_ref = jax.jit(jax.grad(model.loss))(params, batch)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-3, atol=1e-4)


def test_int8_ring_allreduce():
    from repro.parallel.compression import ring_allreduce_int8
    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    x = np.random.default_rng(0).standard_normal((8, 777)) \
        .astype(np.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P("data")))
    out = np.asarray(jax.jit(
        lambda a: ring_allreduce_int8(a, mesh, "data"))(xs))
    ref = x.sum(0)
    scale = np.abs(ref).max()
    for r in range(8):
        assert np.abs(out[r] - ref).max() / scale < 0.05


def test_error_feedback_reduces_bias():
    """With error feedback, compressed grad sums converge to the true sum
    over repeated steps (residual reinjection)."""
    from repro.parallel.compression import ErrorFeedback
    rng = np.random.default_rng(1)
    g_true = jnp.asarray(rng.standard_normal(4096).astype(np.float32))
    resid = ErrorFeedback.init(g_true)
    acc_c = np.zeros(4096)
    for i in range(20):
        c, resid = ErrorFeedback.apply(g_true, resid)
        acc_c += np.asarray(c)
    err = np.abs(acc_c - 20 * np.asarray(g_true)).max()
    assert err < 0.05 * np.abs(20 * np.asarray(g_true)).max()


def test_jaxdist_algorithms():
    from repro.jaxdist import make_3d_mesh, matmul_3d, tsqr
    mesh = make_3d_mesh((2, 2, 2))
    rng = np.random.default_rng(0)
    A = rng.standard_normal((32, 64)).astype(np.float32)
    B = rng.standard_normal((64, 16)).astype(np.float32)
    a = jax.device_put(A, NamedSharding(mesh, P("x", "z")))
    b = jax.device_put(B, NamedSharding(mesh, P("z", "y")))
    C = np.asarray(jax.jit(lambda a, b: matmul_3d(a, b, mesh))(a, b))
    np.testing.assert_allclose(C, A @ B, rtol=1e-4, atol=1e-4)

    Am = rng.standard_normal((64, 8)).astype(np.float32)
    am = jax.device_put(Am, NamedSharding(mesh, P("x", None)))
    Q, R = jax.jit(lambda a: tsqr(a, mesh, "x"))(am)
    np.testing.assert_allclose(np.asarray(Q) @ np.asarray(R), Am,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(Q).T @ np.asarray(Q),
                               np.eye(8), atol=1e-4)


def test_jaxdist_cholesky3d():
    from repro.jaxdist import cholesky_3d, make_3d_mesh
    mesh = make_3d_mesh((2, 2, 2))
    rng = np.random.default_rng(0)
    n = 32
    M = rng.standard_normal((n, n)).astype(np.float32)
    SPD = M @ M.T + n * np.eye(n, dtype=np.float32)
    aa = jax.device_put(SPD, NamedSharding(mesh, P("x", "y")))
    L, Linv = jax.jit(lambda a: cholesky_3d(a, mesh, block=8))(aa)
    np.testing.assert_allclose(np.asarray(L) @ np.asarray(L).T, SPD,
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(L) @ np.asarray(Linv),
                               np.eye(n), atol=2e-3)
