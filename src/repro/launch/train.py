"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \\
        --reduced --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Runs the full stack: config -> model -> sharded train step (when a mesh is
requested) -> synthetic data pipeline -> checkpoint/restart.  Auto-resumes
from the latest checkpoint in --ckpt-dir (fault tolerance: kill it at any
step and rerun the same command).  ``main`` returns the final params, the
per-step losses and the host-clock init, compile and step times.
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SHAPES, get_config
from repro.configs.base import Shape
from repro.launch.compile_cache import enable_compilation_cache
from repro.models.model import Model, ModelKnobs
from repro.parallel.sharding import make_rules
from repro.train import checkpoint as ckpt
from repro.train.data import DataConfig, batch_iterator, make_global_batch
from repro.train.optim import AdamWConfig, adamw_init
from repro.train.step import (TrainConfig, batch_shardings, make_train_step,
                              param_shardings, opt_shardings)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--model-axis", type=int, default=0,
                    help="use a (data, model) host mesh with this model size")
    ap.add_argument("--variant", default="cp")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compilation_cache()
    t_start = time.perf_counter()

    cfg = get_config(args.arch, reduced=args.reduced)
    shape = Shape("cli", args.seq, args.batch, "train")
    knobs = ModelKnobs(kv_chunk=min(64, args.seq),
                       ssm_chunk=min(32, args.seq))
    model = Model(cfg, knobs)
    rules = None
    if args.model_axis:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(model=args.model_axis)
        rules = make_rules(args.variant).with_mesh(mesh)

    tc = TrainConfig(grad_accum=args.grad_accum,
                     optimizer=AdamWConfig(lr=args.lr, warmup=10,
                                           decay_steps=args.steps))
    step_fn = make_train_step(model, rules, tc)
    key = jax.random.PRNGKey(args.seed)

    start = 0
    if args.ckpt_dir and (latest := ckpt.latest_step(args.ckpt_dir)) is not None:
        params_like = jax.eval_shape(model.init, key)
        like = {"params": params_like,
                "opt": jax.eval_shape(adamw_init, params_like)}
        sh = None
        if rules is not None:
            ps = param_shardings(model, rules)
            sh = {"params": ps, "opt": opt_shardings(model, rules)}
        tree, man = ckpt.restore(args.ckpt_dir, latest, like, shardings=sh)
        params, opt_state = tree["params"], tree["opt"]
        start = man["step"]
        print(f"resumed from step {start}")
    else:
        params = model.init(key)
        opt_state = adamw_init(params)
        if rules is not None:
            from repro.train.step import shard_params
            params = shard_params(model, params, rules)

    step_fn = jax.jit(step_fn, donate_argnums=(0, 1))

    it = batch_iterator(cfg, shape, DataConfig(seed=args.seed),
                        start_step=start)
    init_s = time.perf_counter() - t_start
    losses, step_s, compile_s = [], [], 0.0
    for i in range(start, args.steps):
        host_batch = next(it)
        batch = {k: jnp.asarray(v) for k, v in host_batch.items()}
        if i == start:      # compile once, outside the step timings
            t0 = time.perf_counter()
            step_fn = step_fn.lower(params, opt_state, batch).compile()
            compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        losses.append(float(metrics["loss"]))      # waits for the step
        step_s.append(time.perf_counter() - t0)
        if (i + 1) % args.log_every == 0 or i == start:
            print(f"step {i + 1:5d}  loss {losses[-1]:.4f}  "
                  f"gnorm {float(metrics['grad_norm']):.3f}")
        if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
            ckpt.save(args.ckpt_dir, i + 1,
                      {"params": params, "opt": opt_state}, keep=3)
    # the first step also moves data and warms up: steady excludes it
    steady = step_s[1:] or step_s
    steady_s = sum(steady) / len(steady) if steady else float("nan")
    print(f"done: {args.steps} steps; host-clock timing, not a benchmark: "
          f"init {init_s:.2f} s, compile {compile_s:.2f} s, first step "
          f"{step_s[0] if step_s else float('nan'):.3f} s, steady "
          f"{steady_s * 1e3:.1f} ms/step")
    return {"params": params, "losses": losses, "init_s": init_s,
            "compile_s": compile_s, "step_s": step_s, "steady_s": steady_s}


if __name__ == "__main__":
    main()
