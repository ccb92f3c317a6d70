"""Pipeline parallelism over the 'pod' axis (GPipe-style, selectable).

The layer stack (n_periods of scan-stacked params) is split into
``n_stages = |pod|`` contiguous stages; microbatches flow through stages
with boundary activations moved by ``ppermute``.  The schedule is the
classic (n_mb + n_stages - 1)-tick loop: stage s works on microbatch
(t - s) at tick t; the bubble fraction is (n_stages-1)/(n_mb+n_stages-1).

Implementation: ``shard_map`` manual over 'pod' only — 'data'/'model' stay
automatic, so the regular sharded layer code (logical-axis constraints on
the auto axes) runs unchanged inside each stage.  Backward flows through
the scan + ppermute transposes (reverse permutation) — no custom AD.

Embedding runs on stage 0, final-norm + head + loss on the last stage;
the scalar loss is broadcast back over 'pod'.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models import layers as ML
from repro.models.model import Model
from repro.parallel.sharding import ShardingRules, axis_rules


def _split_stages(stacked, n_stages: int):
    """(P_, ...) stacked period params -> (n_stages, P_/n_stages, ...)."""
    def one(a):
        p = a.shape[0]
        assert p % n_stages == 0, (p, n_stages)
        return a.reshape((n_stages, p // n_stages) + a.shape[1:])
    return jax.tree.map(one, stacked)


def pipeline_loss(model: Model, rules: ShardingRules, params, batch, *,
                  n_mb: int = 4):
    """Cross-entropy loss with the layer stack pipelined over 'pod'.

    Equivalent (exactly) to model.loss when the pattern period divides
    evenly into |pod| stages; requires n_periods % |pod| == 0 and
    global_batch % n_mb == 0.
    """
    mesh = rules.mesh
    assert mesh is not None and "pod" in mesh.axis_names
    n_stages = mesh.shape["pod"]
    cfg = model.cfg
    P_ = cfg.n_periods
    assert P_ % n_stages == 0
    # inside the manual-'pod' region, constraints may only reference the
    # automatic axes: strip 'pod' from every rule entry
    table = {}
    for k, v in rules.table.items():
        axes = (v,) if isinstance(v, str) else tuple(v or ())
        axes = tuple(a for a in axes if a != "pod")
        table[k] = axes if axes else None
    rules = ShardingRules(rules.name + "-pipe", table, mesh)

    stage_stacks = [_split_stages(params[f"pos{i}"], n_stages)
                    for i in range(cfg.period)]
    other = {"embed": params["embed"], "final": params["final"]}
    if "head" in params:
        other["head"] = params["head"]

    def split_mb(x):
        b = x.shape[0]
        return x.reshape((n_mb, b // n_mb) + x.shape[1:])

    mbs = jax.tree.map(split_mb, batch)

    # manual over 'pod'; everything else automatic
    auto = frozenset(a for a in mesh.axis_names if a != "pod")
    n_ticks = n_mb + n_stages - 1

    def body(stage_params_in, other_p, mbs_local):
        s = lax.axis_index("pod")
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        # local stage slice: (1, P_/n_stages, ...) -> (P_/n_stages, ...)
        stage_params = jax.tree.map(lambda a: a[0], stage_params_in)

        def embed_mb(t):
            """Stage 0's input for tick t (dummy past the last mb)."""
            idx = jnp.clip(t, 0, n_mb - 1)
            mb = jax.tree.map(lambda a: a[idx], mbs_local)
            with axis_rules(rules):
                return model._embed(other_p, mb)

        def stage_fn(x):
            with axis_rules(rules):
                body_fn = model._period_body_fwd(
                    jnp.arange(x.shape[1]), False)
                x, _ = lax.scan(body_fn, x, stage_params)
            return x

        def loss_mb(x, t):
            idx = jnp.clip(t - (n_stages - 1), 0, n_mb - 1)
            labels = jax.tree.map(lambda a: a[idx], mbs_local)["labels"]
            with axis_rules(rules):
                h = ML.rms_norm(x, other_p["final"]["ln"], cfg.norm_eps)
                logits = model._head(other_p, h)
                if cfg.n_patches:
                    logits = logits[:, -labels.shape[1]:]
                lse = jax.scipy.special.logsumexp(logits, axis=-1)
                oh = jax.nn.one_hot(labels, cfg.vocab, dtype=logits.dtype)
                ce = jnp.mean(lse - jnp.sum(logits * oh, axis=-1))
            return ce

        x0 = embed_mb(jnp.int32(0))

        def tick(carry, t):
            buf, loss_acc = carry
            # stage 0 injects microbatch t; others consume the buffer
            inj = embed_mb(t)
            x_in = jnp.where(s == 0, inj, buf)
            x_out = stage_fn(x_in)
            # last stage computes loss for valid ticks
            valid = (t >= n_stages - 1) & (t - (n_stages - 1) < n_mb)
            ce = loss_mb(x_out, t)
            loss_acc = loss_acc + jnp.where(
                (s == n_stages - 1) & valid, ce, 0.0)
            buf = lax.ppermute(x_out, "pod", perm)
            return (buf, loss_acc), None

        (buf, loss_acc), _ = lax.scan(
            tick, (jnp.zeros_like(x0), jnp.zeros((), jnp.float32)),
            jnp.arange(n_ticks))
        # the loss lives on the last stage: share it with everyone
        return lax.psum(loss_acc, "pod") / n_mb

    in_specs = (
        jax.tree.map(lambda a: P("pod"), stage_stacks),
        jax.tree.map(lambda a: P(), other),
        jax.tree.map(lambda a: P(), mbs),
    )
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=P(), check_vma=False,
                       axis_names={"pod"})
    return fn(stage_stacks, other, mbs)
