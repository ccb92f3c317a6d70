"""Tile sweep and on-chip check of the causal flash-attention kernels.

    python benchmarks/flash_tiles.py [--out PATH] [--reps N]

Needs a TPU.  For each shape the model runs through ``attn_block`` (the
train and tune cells' batch x 2048 at smollm-135m's heads of 64 and
batch x 1024 at granite-3-8b's heads of 128, the serving engine's batch-1
prefill buckets) it times each of the three kernels (``flash_fwd``,
``flash_bwd_dkv``, ``flash_bwd_dq``) alone at every (query rows, key rows)
tile in {128, 256, 512, 1024} that divides the length (square tiles only
at the smaller batches), with bf16 operands in the kernels' layout, as the
median of ``--reps`` back-to-back calls.  Then, at the largest batch of
each width and the default tiles (``tiles.flash_tiles``), it times one
attention block's forward and backward under ``jax.checkpoint`` (the
tuner's ``attn_fb`` kernel) on the flash path and on the chunked jnp path,
and reports the gap of each path's gradients to a float32
``highest``-precision reference.  Prints one JSON object, also written to
``--out``.
"""

from __future__ import annotations

import argparse
import importlib
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

TILES = (128, 256, 512, 1024)
# name, batch, seq, heads, kv heads, head dim, backward too, square tiles
# only, layer check
SHAPES = [("smollm.b8", 8, 2048, 9, 3, 64, True, False, True),
          ("smollm.b2", 2, 2048, 9, 3, 64, True, True, False),
          ("granite.b4", 4, 1024, 32, 8, 128, True, False, True),
          ("granite.b1", 1, 1024, 32, 8, 128, True, True, False),
          ("prefill.1536", 1, 1536, 9, 3, 64, False, False, False),
          ("prefill.512", 1, 512, 9, 3, 64, False, False, False),
          ("prefill.128", 1, 128, 9, 3, 64, False, False, False)]


def _time(fn, args, reps: int) -> float:
    import jax
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def sweep(shape, reps: int) -> dict:
    import jax
    import jax.numpy as jnp
    F = importlib.import_module("repro.kernels.flash_attention")

    _, B, S, H, KVH, d, bwd, square, _ = shape
    key = jax.random.PRNGKey(0)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    q = jax.random.normal(k1, (B, H, S, d), jnp.bfloat16)
    k = jax.random.normal(k2, (B, KVH, S, d), jnp.bfloat16)
    v = jax.random.normal(k3, (B, KVH, S, d), jnp.bfloat16)
    do = jax.random.normal(k4, (B, H, S, d), jnp.bfloat16)
    _, lse = jax.jit(lambda *a: F._fwd_call(*a, (128, 128), False))(q, k, v)
    di = jnp.zeros_like(lse)
    calls = {"fwd": (lambda t: lambda q, k, v: F._fwd_call(q, k, v, t, False),
                     (q, k, v))}
    if bwd:
        calls["dkv"] = (lambda t: lambda *a: F._dkv_call(*a, t, False),
                        (q, k, v, do, lse, di))
        calls["dq"] = (lambda t: lambda *a: F._dq_call(*a, t, False),
                       (q, k, v, do, lse, di))
    out = {}
    for name, (make, args) in calls.items():
        row = {}
        for bq in TILES:
            for bk in TILES:
                if S % bq or S % bk or (square and bq != bk):
                    continue
                try:
                    row[f"{bq}x{bk}"] = _time(jax.jit(make((bq, bk))), args,
                                              reps)
                except Exception as e:  # a tile the chip refuses
                    row[f"{bq}x{bk}"] = f"failed: {type(e).__name__}"
        timed = {t: s for t, s in row.items() if isinstance(s, float)}
        out[name] = {"best": min(timed, key=timed.get), "s": row}
    return out


def layer_check(shape, reps: int) -> dict:
    """One attention block's forward and backward, flash vs chunked."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import ArchConfig
    from repro.models import layers as L

    name, B, S, H, KVH, d = shape[:6]
    cfg = ArchConfig(name="probe", family="dense", n_layers=1,
                     d_model=H * d, n_heads=H, n_kv_heads=KVH, d_ff=4 * d,
                     vocab=256, d_head=d)
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 6)
    D = cfg.d_model
    p = {"ln": jnp.zeros((D,)),
         "wq": jax.random.normal(ks[0], (D, H, d)) / D ** 0.5,
         "wk": jax.random.normal(ks[1], (D, KVH, d)) / D ** 0.5,
         "wv": jax.random.normal(ks[2], (D, KVH, d)) / D ** 0.5,
         "wo": jax.random.normal(ks[3], (H, d, D)) / D ** 0.5}
    x = jax.random.normal(ks[4], (B, S, D))
    pos = jnp.arange(S)

    def loss(p, x, kv_chunk):
        h, _ = L.attn_block(p, x, cfg, positions=pos, kv_chunk=kv_chunk)
        return jnp.sum(h * h)

    out = {}
    grads = {}
    for path, kv_chunk in (("flash", 64), ("chunked.kv64", 64),
                           ("chunked.kv16", 16)):
        fn = jax.jit(jax.grad(jax.checkpoint(
            lambda p, x, c=kv_chunk: loss(p, x, c)), argnums=(0, 1)))
        force = path.startswith("chunked")
        orig = L.flash_applies
        if force:
            L.flash_applies = lambda: False
        try:
            out[f"{path}_s"] = _time(fn, (p, x), reps)
            grads[path] = fn(p, x)
        finally:
            L.flash_applies = orig
    with jax.default_matmul_precision("highest"):
        L.flash_applies, orig = (lambda: False), L.flash_applies
        try:
            want = jax.jit(jax.grad(lambda p, x: loss(p, x, 256),
                                    argnums=(0, 1)))(p, x)
        finally:
            L.flash_applies = orig
    for path, got in grads.items():
        gaps = [float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
                for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))]
        out[f"{path}_grad_gap"] = max(gaps)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="benchmarks/results/flash_tiles.json")
    ap.add_argument("--reps", type=int, default=10)
    args = ap.parse_args(argv)
    import jax
    if jax.default_backend() != "tpu":
        print("flash_tiles.py measures the TPU kernels and needs a TPU",
              file=sys.stderr)
        return 1
    dev = jax.devices()[0]
    res = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "shapes": {}}
    for shape in SHAPES:
        r = sweep(shape, args.reps)
        if shape[8]:
            r["layer"] = layer_check(shape, args.reps)
        res["shapes"][shape[0]] = r
        print(shape[0], json.dumps(r), flush=True)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(res, indent=1))
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
