"""Bring-up smoke: the main path once, end to end, on the TPU.

    python chip_smoke.py                # one chip: train, serve, tune, kernels
    python chip_smoke.py --four-chips   # jaxdist on a (2, 1, 2) 4-chip mesh

With no option the phases run in one process, at smollm-135m's published
widths (30 layers, d_model 576, vocab 49152) with random weights from
``--seed``, through the entry points a user calls:

- train    ``repro.launch.train.main``: batch 8 x seq 2048, 5 steps; every
           loss finite, the first near ln(vocab);
- serve    ``repro.launch.serve.main`` (the slot engine): batch 8, s_max
           2048, 16 greedy requests of 100-1000 prompt tokens and 32 new
           tokens each; every request gets its 32 tokens, and one
           request's first token is the argmax of a plain full-sequence
           ``Model.forward``;
- tune     an ``AutotuneSession`` over ``LMStudy(...).search_space(4)``
           with ``WallClockBackend``: eager, tolerance 0.3, 3 trials,
           batch 4 x seq 1024;
- kernels  the Pallas matmul, rmsnorm and causal flash attention (a full
           and a ragged length) compiled for the chip at smollm widths,
           each allclose to ``kernels/ref.py``.

``--four-chips`` runs only the paper's own distributed workload:
``jaxdist`` matmul_3d, tsqr and cholesky_3d on a (2, 1, 2) mesh, so the
'z' reduction crosses chips, each compared with the single-device ``jnp``
result.

The script needs a TPU and never falls back to the CPU.  It starts no
other process.  A line with a time on it is a smoke timing (one host-clock
reading), not a benchmark.  The last line, printed only when every phase
passed, is one JSON object naming the device; any failure exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
ARCH = "smollm-135m"


def _log(msg: str) -> None:
    print(msg, flush=True)


def _timing(phase: str, **secs: float) -> None:
    parts = ", ".join(f"{k} {v:.4f} s" for k, v in secs.items())
    _log(f"[smoke timing, not a benchmark] {phase}: {parts}")


# ----------------------------------------------------------------- phases

def phase_train(*, reduced: bool = False, batch: int = 8, seq: int = 2048,
                steps: int = 5, seed: int = 0) -> dict:
    from repro.launch import train

    argv = ["--arch", ARCH, "--batch", str(batch), "--seq", str(seq),
            "--steps", str(steps), "--seed", str(seed), "--log-every", "1"]
    out = train.main(argv + (["--reduced"] if reduced else []))
    losses = out["losses"]
    assert len(losses) == steps, losses
    assert all(math.isfinite(x) for x in losses), losses
    from repro.configs import get_config
    ln_v = math.log(get_config(ARCH, reduced=reduced).vocab)
    # random init: the first loss is that of a near-uniform prediction
    assert abs(losses[0] - ln_v) < 1.0, (losses[0], ln_v)
    _timing("train", init=out["init_s"], compile=out["compile_s"],
            first_step=out["step_s"][0], steady_per_step=out["steady_s"])
    return {k: out[k] for k in ("losses", "init_s", "compile_s", "step_s",
                                "steady_s")}


def phase_serve(*, reduced: bool = False, batch: int = 8, s_max: int = 2048,
                requests: int = 16, prompt_len=(100, 1000), max_new: int = 32,
                seed: int = 0) -> dict:
    import jax
    import numpy as np

    from repro.launch import serve
    from repro.models.model import Model, ModelKnobs
    from repro.serve.engine import Request

    argv = ["--arch", ARCH, "--batch", str(batch), "--s-max", str(s_max),
            "--requests", str(requests), "--max-new", str(max_new),
            "--prompt-len", str(prompt_len[0]), str(prompt_len[1]),
            "--temperature", "0", "--seed", str(seed)]
    t0 = time.perf_counter()
    eng, reqs = serve.main(argv + (["--reduced"] if reduced else []))
    cold_s = time.perf_counter() - t0
    for r in reqs:
        got = len(eng.results[r.uid].tokens)
        assert got == max_new, (r.uid, len(r.tokens), got)

    # reference: the plain full-sequence forward, in one KV chunk
    r = max(reqs, key=lambda q: len(q.tokens))
    ref = Model(eng.cfg, ModelKnobs(kv_chunk=s_max))
    logits = jax.jit(ref.forward)(eng.params, {"tokens": r.tokens[None]})
    want = int(np.argmax(np.asarray(logits[0, -1])))
    got = eng.results[r.uid].tokens[0]
    assert got == want, (r.uid, len(r.tokens), got, want)

    # the same prompts again: every prefill and the decode step compiled
    for q in reqs:
        eng.submit(Request(q.uid + requests, q.tokens))
    t0 = time.perf_counter()
    eng.run()
    warm_s = time.perf_counter() - t0
    toks = sum(len(eng.results[q.uid + requests].tokens) for q in reqs)
    _log(f"serve: {len(reqs)} requests, prompts "
         f"{min(len(q.tokens) for q in reqs)}-"
         f"{max(len(q.tokens) for q in reqs)} tokens, {max_new} new each; "
         f"request {r.uid} ({len(r.tokens)} tokens) first token {got} = "
         f"reference argmax")
    _timing("serve", first_pass_with_compiles=cold_s, warm_pass=warm_s)
    return {"cold_s": cold_s, "warm_s": warm_s, "warm_tokens": toks}


def phase_tune(*, reduced: bool = False, batch: int = 4, seq: int = 1024,
               max_configs: int = 4, seed: int = 0) -> dict:
    from repro.api import AutotuneSession, WallClockBackend
    from repro.tune.lm_study import LMStudy

    study = LMStudy(ARCH, reduced=reduced, batch=batch, seq=seq, seed=seed)
    session = AutotuneSession(study.search_space(max_configs),
                              backend=WallClockBackend(study.kernels_of),
                              policy="eager", tolerance=0.3, trials=3)
    t0 = time.perf_counter()
    res = session.run()
    wall_s = time.perf_counter() - t0
    executed = sum(r.executed for r in res.records)
    skipped = sum(r.skipped for r in res.records)
    assert len(res.records) == max_configs, len(res.records)
    assert executed > 0, executed
    assert all(math.isfinite(r.predicted) and r.predicted > 0
               for r in res.records), [r.predicted for r in res.records]
    assert math.isfinite(res.speedup) and res.speedup > 0, res.speedup
    _log(f"tune: chosen {res.chosen.name}; kernel runs in the last trial "
         f"of each configuration: executed {executed}, skipped {skipped}; "
         f"speedup {res.speedup:.3f}, optimum quality "
         f"{res.optimum_quality:.3f}")
    _timing("tune", session_with_compiles=wall_s,
            full_execution=res.full_tuning_time,
            selective_execution=res.selective_tuning_time)
    return {"chosen": res.chosen.name, "executed": executed,
            "skipped": skipped, "speedup": res.speedup}


def kernel_cases(*, d_model: int, d_ff: int, n_heads: int, n_kv: int,
                 head_dim: int, tokens: int, seq: int):
    """(label, op, input shapes) at one model's widths."""
    return [
        ("matmul ffn-up", "matmul", ((tokens, d_model), (d_model, d_ff))),
        ("matmul ffn-down", "matmul", ((tokens, d_ff), (d_ff, d_model))),
        ("rmsnorm", "rmsnorm", ((tokens, d_model),)),
        ("rmsnorm ragged rows", "rmsnorm", ((3, 100, d_model),)),
        ("flash prefill", "flash_attention",
         ((1, seq, n_heads, head_dim), (1, seq, n_kv, head_dim))),
        ("flash ragged", "flash_attention",
         ((1, seq // 2 + 1, n_heads, head_dim),
          (1, seq // 2 + 1, n_kv, head_dim))),
    ]


def phase_kernels(cases=None, *, interpret: bool = False,
                  seed: int = 0) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import kernels
    from repro.kernels import ref

    if cases is None:
        from repro.configs import get_config
        cfg = get_config(ARCH)
        cases = kernel_cases(d_model=cfg.d_model, d_ff=cfg.d_ff,
                             n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                             head_dim=cfg.head_dim, tokens=4096, seq=2048)
    key = jax.random.PRNGKey(seed)
    out = {}
    for label, op, shapes in cases:
        key, k1, k2, k3 = jax.random.split(key, 4)
        if op == "matmul":
            args = (jax.random.normal(k1, shapes[0], jnp.bfloat16),
                    jax.random.normal(k2, shapes[1], jnp.bfloat16))
            fn = lambda a, b: kernels.matmul(a, b, interpret=interpret)
            want, rtol, atol = ref.matmul_ref(*args), 3e-2, 0.24
        elif op == "rmsnorm":
            (shape,) = shapes
            args = (jax.random.normal(k1, shape, jnp.bfloat16),
                    (0.1 * jax.random.normal(k2, shape[-1:]))
                    .astype(jnp.bfloat16))
            fn = lambda x, w: kernels.rmsnorm(x, w, interpret=interpret)
            want, rtol, atol = ref.rmsnorm_ref(*args), 3e-2, 3e-2
        else:
            qs, kvs = shapes
            args = (jax.random.normal(k1, qs, jnp.bfloat16),
                    jax.random.normal(k2, kvs, jnp.bfloat16),
                    jax.random.normal(k3, kvs, jnp.bfloat16))
            fn = lambda q, k, v: kernels.flash_attention(
                q, k, v, interpret=interpret)
            want, rtol, atol = ref.flash_attention_ref(*args), 4e-2, 4e-2
        t0 = time.perf_counter()
        got = jax.block_until_ready(fn(*args))
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(3):
            got = jax.block_until_ready(fn(*args))
        steady_s = (time.perf_counter() - t0) / 3
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=rtol, atol=atol, err_msg=label)
        _timing(f"kernel {label} {[list(s) for s in shapes]}",
                first_call_with_compile=first_s, steady_call=steady_s)
        out[label] = steady_s
    return out


def phase_jaxdist(*, m: int = 4096, n: int = 4096, tall: int = 65536,
                  panel: int = 256, block: int = 512, seed: int = 0) -> dict:
    """matmul_3d, tsqr and cholesky_3d on a (2, 1, 2) mesh of the first 4
    devices, each against the single-device ``jnp`` result."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.jaxdist import cholesky_3d, make_3d_mesh, matmul_3d, tsqr

    mesh = make_3d_mesh((2, 1, 2))
    one = jax.devices()[0]
    rng = np.random.default_rng(seed)

    def put(x, *spec):
        return jax.device_put(x, NamedSharding(mesh, P(*spec)))

    def timed(label, fn, *args):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        _timing(f"jaxdist {label}", first_call_with_compile=first_s,
                steady_call=time.perf_counter() - t0)
        return out

    def close(got, want, tol, what):
        got, want = np.asarray(got), np.asarray(want)
        err = np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)
        _log(f"jaxdist {what}: max error {err:.3e} relative to max |ref|")
        assert err < tol, (what, err)

    # f32 throughout: compare the algorithms, not bf16 matmul passes
    with jax.default_matmul_precision("float32"):
        A = rng.standard_normal((m, n)).astype(np.float32)
        B = rng.standard_normal((n, m)).astype(np.float32)
        C = timed("matmul_3d", jax.jit(lambda a, b: matmul_3d(a, b, mesh)),
                  put(A, "x", "z"), put(B, "z", "y"))
        C_ref = jax.jit(jnp.dot)(jax.device_put(A, one),
                                 jax.device_put(B, one))
        close(C, C_ref, 1e-5, "matmul_3d vs jnp.dot")

        T = rng.standard_normal((tall, panel)).astype(np.float32)
        Q, R = timed("tsqr", jax.jit(lambda a: tsqr(a, mesh, "x")),
                     put(T, "x", None))
        _, R_ref = jax.jit(jnp.linalg.qr)(jax.device_put(T, one))
        # R is unique up to the sign of each row
        close(np.abs(np.asarray(R)), np.abs(np.asarray(R_ref)), 1e-4,
              "tsqr |R| vs jnp.linalg.qr")
        close(np.asarray(Q) @ np.asarray(R), T, 1e-4, "tsqr Q R vs input")

        M = rng.standard_normal((n, n)).astype(np.float32)
        S = (M @ M.T / n + np.eye(n, dtype=np.float32)).astype(np.float32)
        L, Linv = timed("cholesky_3d",
                        jax.jit(lambda a: cholesky_3d(a, mesh, block)),
                        put(S, "x", "y"))
        L_ref = jax.jit(jnp.linalg.cholesky)(jax.device_put(S, one))
        close(L, L_ref, 1e-3, "cholesky_3d L vs jnp.linalg.cholesky")
        close(np.asarray(L) @ np.asarray(Linv), np.eye(n), 1e-3,
              "cholesky_3d L Linv vs I")
    return {"mesh": dict(mesh.shape)}


# ------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only jaxdist on a (2, 1, 2) mesh of 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, and JAX found {platform!r} "
              f"devices; nothing was run", file=sys.stderr)
        return 1
    if args.four_chips and len(devices) != 4:
        print(f"chip_smoke: --four-chips needs 4 chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    if not SRC.is_dir():
        print(f"chip_smoke: no {SRC}: run it from a checkout of the repo",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compilation_cache

    _log(f"device: {platform} {devices[0].device_kind} x {len(devices)}; "
         f"compile cache {enable_compilation_cache()}")
    if args.four_chips:
        phases = [("jaxdist", lambda: phase_jaxdist(seed=args.seed))]
    else:
        phases = [("train", lambda: phase_train(seed=args.seed)),
                  ("serve", lambda: phase_serve(seed=args.seed)),
                  ("tune", lambda: phase_tune(seed=args.seed)),
                  ("kernels", lambda: phase_kernels(seed=args.seed))]
    for name, run in phases:
        _log(f"== {name} ==")
        t0 = time.perf_counter()
        run()
        _timing(f"phase {name} passed", wall=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
