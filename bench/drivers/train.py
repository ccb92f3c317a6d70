"""The jitted train step, as ``launch/train.py`` builds it, fed by the
synthetic host data stream.

Traffic keys: ``batch``, ``seq``, ``kv_chunk``, ``remat``, ``grad_accum``,
``optimizer`` (every AdamW setting of this job, handed to the program and
to the reference alike), ``trace_seconds``.  Set-up makes the weights on the device from the seed
in one jitted call, compiles the step, and drives that same step through
the job's first three steps (``CHECKED``), keeping what the comparison
needs: each step's loss, the first gradient as the optimizer holds it,
and each leaf's change over the three.  The window goes on from there,
one step in flight, reading each step's loss as the next is dispatched.

Correctness: the plain reference runs the same three steps from the
same weights and batches, in float32 at ``highest`` precision, and each
number is the worst gap between the program and the reference.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from harness import data as D
from harness import flops as F

CHECKED = 3


class State:
    pass


def _leaf_norms(tree):
    import jax
    import jax.numpy as jnp
    return [float(jnp.linalg.norm(x)) for x in jax.tree.leaves(tree)]


def build(cell, seed):
    """The model, step and data stream of this job (no weights yet)."""
    import jax
    from harness.spec import arch_config
    from repro.configs.base import Shape
    from repro.models.model import Model, ModelKnobs
    from repro.train.data import DataConfig, batch_iterator
    from repro.train.optim import AdamWConfig
    from repro.train.step import TrainConfig, make_train_step

    t = cell.traffic
    cfg = arch_config(cell.config)
    model = Model(cfg, ModelKnobs(kv_chunk=t["kv_chunk"], remat=t["remat"]))
    tc = TrainConfig(grad_accum=t["grad_accum"],
                     optimizer=AdamWConfig(**t["optimizer"]))
    step = jax.jit(make_train_step(model, None, tc), donate_argnums=(0, 1))
    shape = Shape("bench", t["seq"], t["batch"], "train")
    data = batch_iterator(cfg, shape, DataConfig(seed=D.seed32(seed, 31)))
    return model, step, data


def setup(cell, rec, seed):
    import jax
    import jax.numpy as jnp
    from repro.train.optim import adamw_init

    st = State()
    st.cell, st.rec, st.seed = cell, rec, seed
    st.model, st.step, st.data = build(cell, seed)
    params = D.lm_params(st.model, seed)
    opt = jax.jit(adamw_init)(params)
    p0 = [np.asarray(x) for x in jax.tree.leaves(params)]
    st.batches, st.losses = [], []
    b1 = cell.traffic["optimizer"].get("b1", 0.9)
    for i in range(CHECKED):
        hb = next(st.data)
        st.batches.append(hb)
        params, opt, met = st.step(params, opt,
                                   {k: jnp.asarray(v) for k, v in hb.items()})
        st.losses.append(float(met["loss"]))
        if i == 0:
            st.first_grad = [n / (1 - b1) for n in _leaf_norms(opt["m"])]
    st.change = [float(np.linalg.norm(np.asarray(x) - a))
                 for x, a in zip(jax.tree.leaves(params), p0)]
    del p0
    st.params, st.opt = params, opt
    return st


def window(st, seconds):
    import jax.numpy as jnp

    t = st.cell.traffic
    rec = st.rec
    losses, pending = [], None
    t0 = time.perf_counter()
    steps = 0
    while True:
        with rec.span("batch fetch"):
            batch = {k: jnp.asarray(v) for k, v in next(st.data).items()}
        with rec.span("train step"):
            st.params, st.opt, met = st.step(st.params, st.opt, batch)
        steps += 1
        if pending is not None:
            losses.append(float(pending))
        pending = met["loss"]
        if time.perf_counter() - t0 >= seconds:
            break
    losses.append(float(pending))
    elapsed = time.perf_counter() - t0
    tokens = steps * t["batch"] * t["seq"]
    rec.counters["steps"] += steps
    rec.counters["tokens"] += tokens
    rec.counters["flops"] += tokens * F.train_flops_per_token(
        st.cell.config, t["seq"])
    bad = sum(1 for x in losses if not np.isfinite(x))
    return {"metrics": {"train_tokens_per_s": tokens / elapsed},
            "attempted": steps, "failed": bad, "elapsed_s": elapsed,
            "info": {"loss_last": losses[-1]}}


def release(st):
    st.params = st.opt = None
    gc.collect()


def reference_run(st, dtype, *, batches=None):
    """The reference's three steps: losses, first gradient's leaf norms,
    each leaf's change."""
    import jax
    import jax.numpy as jnp
    ref = st.cell.reference()
    params = D.lm_params(st.model, st.seed)
    losses, first, after = ref.train_steps(
        st.cell.traffic["optimizer"], params, batches or st.batches,
        st.cell.config, dtype=dtype)
    change = [float(jnp.linalg.norm(a - b.astype(jnp.float32)))
              for a, b in zip(jax.tree.leaves(after),
                              jax.tree.leaves(params))]
    return losses, _leaf_norms(first), change


def gaps(prog, ref):
    """The numbers compared: worst relative gaps of the first gradient's
    norm and of the change's norm, by leaf.  Leaves whose reference
    gradient is under a thousandth of the median leaf's move by round-off
    alone and are left out.  The loss gap is read too, and not compared:
    the control does not separate it (PERF.md)."""
    pl, pg, pc = prog
    rl, rg, rc = ref
    rg, pg, rc, pc = map(np.asarray, (rg, pg, rc, pc))
    keep = rg >= 1e-3 * np.median(rg)

    def worst(p, r):
        p, r = p[keep], r[keep]
        return float(np.max(np.abs(p - r) / np.maximum(r, np.median(r))))
    return ({"grad_norm_gap": worst(pg, rg),
             "change_norm_gap": worst(pc, rc)},
            {"loss_gap": float(max(abs(a - b) / abs(b)
                                   for a, b in zip(pl, rl)))})


def check(st, produce=None):
    import jax.numpy as jnp
    prog = produce(st) if produce else (st.losses, st.first_grad, st.change)
    if getattr(st, "ref_seed", None) != st.seed:
        st.ref, st.ref_seed = reference_run(st, jnp.float32), st.seed
    compared, st.check_info = gaps(prog, st.ref)
    return compared


def control(st):
    """The reference with fp8 products, put in the program's place."""
    return lambda s: reference_run(s, s.cell.reference().FP8)


def half_batch(st):
    """A planted fault: half of each batch left out, the mean taken over
    the rest (the reference, in float32, in the program's place)."""
    import jax.numpy as jnp
    halves = [{k: v[: v.shape[0] // 2] for k, v in b.items()}
              for b in st.batches]
    return lambda s: reference_run(s, jnp.float32, batches=halves)


FAULTS = {"half_batch": half_batch}
