"""End-to-end serving driver.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \\
        --reduced --requests 16 --max-new 24

Spins up the slot-based engine on a (reduced) model with random weights and
replays a batch of synthetic prompts, reporting aggregate decode throughput.
``main`` returns the engine, which holds the results, and the requests.

With ``--daemon``, instead drives simulated traffic through the always-on
tuning daemon (``repro.serve.tuner.run_daemon_demo``): shape misses open
background studies, later shapes warm-start from the fleet store, and an
injected kernel-cost shift exercises the drift -> re-tune path without
serving ever stopping.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compilation_cache
from repro.models.model import Model, ModelKnobs
from repro.serve.engine import Engine, Request, ServeConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--s-max", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prompt-len", type=int, nargs=2, default=(4, 31),
                    metavar=("LO", "HI"),
                    help="prompt lengths are drawn from --seed in [LO, HI]")
    ap.add_argument("--daemon", action="store_true",
                    help="run the always-on tuning daemon demo instead")
    ap.add_argument("--rounds", type=int, default=4,
                    help="steady-state serving rounds (daemon demo)")
    ap.add_argument("--bank", default=None,
                    help="save the fleet statistics bank here (daemon demo)")
    ap.add_argument("--checkpoint", default=None,
                    help="daemon checkpoint path (daemon demo)")
    args = ap.parse_args(argv)

    if args.daemon:
        return _daemon_demo(args)
    enable_compilation_cache()

    cfg = get_config(args.arch, reduced=args.reduced)
    model = Model(cfg, ModelKnobs(kv_chunk=32, ssm_chunk=16))
    params = model.init(jax.random.PRNGKey(args.seed))
    eng = Engine(model, params, ServeConfig(
        batch_size=args.batch, s_max=args.s_max,
        max_new_tokens=args.max_new, temperature=args.temperature,
        seed=args.seed))
    rng = np.random.default_rng(args.seed)
    reqs = []
    for uid in range(args.requests):
        n = int(rng.integers(args.prompt_len[0], args.prompt_len[1] + 1))
        shape = (n, cfg.n_codebooks) if cfg.n_codebooks else (n,)
        reqs.append(Request(uid, rng.integers(0, cfg.vocab, size=shape)
                            .astype(np.int32)))
        eng.submit(reqs[-1])
    t0 = time.perf_counter()
    steps = 0
    while eng.queue or eng.active.any():
        eng.step()
        steps += 1
    dt = time.perf_counter() - t0
    toks = sum(len(r.tokens) for r in eng.results.values())
    print(f"{args.requests} requests, {toks} tokens, {steps} engine steps; "
          f"host-clock timing, not a benchmark: {dt:.2f} s with compiles "
          f"({toks / dt:.1f} tok/s)")
    for uid in sorted(eng.results)[:4]:
        print(f"  req {uid}: {eng.results[uid].tokens[:12]} ...")
    return eng, reqs


def _daemon_demo(args) -> dict:
    from repro.serve.tuner import run_daemon_demo

    summary = run_daemon_demo(
        args.arch, rounds=args.rounds, checkpoint=args.checkpoint,
        bank_path=args.bank, log=print)
    r = summary["ratios"]
    print(f"hit ratio {r['hit_ratio']:.2f}, warm-start ratio "
          f"{r['warm_start_ratio']:.2f}, drift detected: "
          f"{summary['drift_detected']}, re-tunes: {summary['retunes']}, "
          f"served while re-tuning: {summary['served_while_retuning']}")
    for key, info in summary["second_tuned_serves"].items():
        print(f"  2nd tuned serve {key}: {info}")
    return summary


if __name__ == "__main__":
    main()
