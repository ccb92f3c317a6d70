"""Plain reference of the dense decoder LM (llama-style GQA block + SwiGLU
FFN + tied or untied head) and of AdamW, in straightforward ``jax.numpy``.

It imports nothing of the program under test.  It follows the published
description of these models as the program implements it:

- RMSNorm with a ``(1 + w)`` gain (weights start at 0), eps from the config;
- rotary embedding on the two halves of each head (llama convention),
  frequencies ``theta ** (-i / half)``;
- causal grouped-query attention, query head ``h`` reading key/value head
  ``h // (n_heads // n_kv_heads)``, scale ``1 / sqrt(head_dim)``;
- SwiGLU FFN ``(silu(h Wg) * (h Wu)) Wd``;
- pre-norm residual blocks, a final RMSNorm, logits ``x E^T`` (tied) or
  ``x W`` (untied), mean cross-entropy;
- AdamW with global-norm clipping and a warm-up/cosine schedule.

``dtype`` selects the precision: float32 at ``highest`` matmul precision
for the reference; ``FP8`` for the control.  The configurations state
float32 storage with one bfloat16 pass per matrix product (the TPU's
default precision), so the nearest precision below is fp8 products: under
``FP8`` every matrix product's operands, and AdamW's inputs, are rounded to
float8 e4m3 (saturating at its largest finite value) and the rest stays
float32.  Every function works on a block of batch rows, so a caller sums
blocks to keep the peak small.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
_FP8_MAX = 448.0


def fp8_round(x):
    """x rounded to float8 e4m3, saturating, back in float32."""
    return jnp.clip(x.astype(jnp.float32), -_FP8_MAX, _FP8_MAX) \
        .astype(FP8).astype(jnp.float32)


def _ein(spec, a, b, fp8=False):
    if fp8:
        a, b = fp8_round(a), fp8_round(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def rope(x, positions, theta):
    """x: (B, S, heads, dh)."""
    dh = x.shape[-1]
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention_block(p, x, cfg, fp8=False):
    """Pre-norm GQA attention; returns the block's output (no residual)."""
    B, S, _ = x.shape
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["hidden_size"] // H
    h = rms_norm(x, p["ln"], cfg["rms_norm_eps"])
    q = _ein("bsd,dhk->bshk", h, p["wq"], fp8)
    k = _ein("bsd,dhk->bshk", h, p["wk"], fp8)
    v = _ein("bsd,dhk->bshk", h, p["wv"], fp8)
    pos = jnp.arange(S)
    q = rope(q, pos, cfg["rope_theta"])
    k = rope(k, pos, cfg["rope_theta"])
    rep = H // KV
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = _ein("bqhk,bchk->bhqc", q, k, fp8) / math.sqrt(dh)
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None, None], s, -1e30)
    s = s - jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
    e = jnp.exp(s)
    a = e / jnp.sum(e, axis=-1, keepdims=True)
    o = _ein("bhqc,bchk->bqhk", a, v, fp8)
    return _ein("bshk,hkd->bsd", o, p["wo"], fp8)


def ffn_block(p, x, cfg, fp8=False):
    h = rms_norm(x, p["ln"], cfg["rms_norm_eps"])
    g = _ein("bsd,df->bsf", h, p["w_gate"], fp8)
    u = _ein("bsd,df->bsf", h, p["w_up"], fp8)
    return _ein("bsf,fd->bsd", jax.nn.silu(g) * u, p["w_down"], fp8)


def head_logits(params, x, cfg, fp8=False):
    """Final norm and logits over the held vocabulary."""
    x = rms_norm(x, params["final"]["ln"], cfg["rms_norm_eps"])
    if "head" in params:
        return _ein("bsd,dv->bsv", x, params["head"]["w"], fp8)
    return _ein("bsd,vd->bsv", x, params["embed"]["tok"], fp8)


def store(dtype):
    """The dtype tensors are kept in: float32 under ``FP8``."""
    return jnp.float32 if dtype == FP8 else dtype


def cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(store(dtype))
                        if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


# --------------------------------------------------------- tuner kernels

def _row_blocks(n, rows):
    return [(i, min(i + rows, n)) for i in range(0, n, rows)]


def kernel_grad(kind, params, x_or_tokens, cfg, *, dtype, rows=1):
    """The gradient that one tuner kernel returns, summed over blocks of
    ``rows`` batch rows (every kernel's objective is a sum or mean over
    rows, so blocks add up exactly).

    kind: ``attn_fb`` (params = the mixer's ln/wq/wk/wv/wo), ``ffn_fb``
    (ln/w_gate/w_up/w_down), ``embed_loss_fb`` (params = embed/final/head,
    ``x_or_tokens`` the token ids; objective mean(logits ** 2)).
    """
    p = cast(params, dtype)
    fp8 = dtype == FP8
    n = x_or_tokens.shape[0]
    if kind == "embed_loss_fb":
        count = x_or_tokens.size * (
            params["head"]["w"].shape[1] if "head" in params
            else params["embed"]["tok"].shape[0])

        def obj(pp, tok):
            x = jnp.take(pp["embed"]["tok"], tok, axis=0)
            lg = head_logits(pp, x, cfg, fp8).astype(jnp.float32)
            return jnp.sum(lg * lg) / count
    else:
        blk = attention_block if kind == "attn_fb" else ffn_block

        def obj(pp, xx):
            h = blk(pp, xx.astype(store(dtype)), cfg, fp8)
            h = h.astype(jnp.float32)
            return jnp.sum(h * h)
    g = jax.jit(jax.grad(obj))
    total = None
    for a, b in _row_blocks(n, rows):
        gb = jax.tree.map(lambda t: t.astype(jnp.float32),
                          g(p, x_or_tokens[a:b]))
        total = gb if total is None else jax.tree.map(jnp.add, total, gb)
    return total


# ------------------------------------------------------------ optimizer

def adamw(opt, params, grads, state, *, dtype=jnp.float32):
    """One AdamW step; ``opt`` holds lr, b1, b2, eps, weight_decay,
    clip_norm, warmup, decay_steps, min_lr_ratio.  Returns new (params, m,
    v) as float32 trees."""
    p, g, m, v = (cast(t, dtype) for t in (params, grads, state["m"],
                                           state["v"]))
    if dtype == FP8:
        p, g, m, v = (jax.tree.map(fp8_round, t) for t in (p, g, m, v))
    dtype = store(dtype)
    step = (state["step"] + 1).astype(jnp.float32)
    leaves = jax.tree.leaves(g)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                         for l in leaves))
    scale = jnp.minimum(1.0, opt["clip_norm"] / (gnorm + 1e-9))
    warm = jnp.minimum(step / max(opt["warmup"], 1), 1.0)
    t = jnp.clip((step - opt["warmup"])
                 / max(opt["decay_steps"] - opt["warmup"], 1), 0.0, 1.0)
    lr = opt["lr"] * warm * (opt["min_lr_ratio"]
                             + (1 - opt["min_lr_ratio"])
                             * 0.5 * (1 + jnp.cos(jnp.pi * t)))
    b1c = 1 - opt["b1"] ** step
    b2c = 1 - opt["b2"] ** step

    def one(pp, gg, mm, vv):
        gg = gg * scale.astype(dtype)
        mm = opt["b1"] * mm + (1 - opt["b1"]) * gg
        vv = opt["b2"] * vv + (1 - opt["b2"]) * gg * gg
        upd = (mm / b1c.astype(dtype)) / (jnp.sqrt(vv / b2c.astype(dtype))
                                          + opt["eps"]) \
            + opt["weight_decay"] * pp
        return (pp - lr.astype(dtype) * upd).astype(jnp.float32), \
            mm.astype(jnp.float32), vv.astype(jnp.float32)

    out = jax.tree.map(one, p, g, m, v)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,  # noqa: E731
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2)


# ------------------------------------------------------------ whole model

def _layer(pp, x, cfg, fp8):
    mix = {k[4:]: v for k, v in pp.items() if k.startswith("mix_")}
    ffn = {k[4:]: v for k, v in pp.items() if k.startswith("ffn_")}
    x = x + attention_block(mix, x, cfg, fp8)
    return x + ffn_block(ffn, x, cfg, fp8)


def lm_logits(params, tokens, cfg, *, dtype):
    """Logits at every position of ``tokens`` (rows, S), one forward."""
    p = cast(params, dtype)
    fp8 = dtype == FP8

    def fwd(pp, t):
        x = jnp.take(pp["embed"]["tok"], t, axis=0)
        x, _ = jax.lax.scan(lambda h, q: (_layer(q, h, cfg, fp8), None), x,
                            pp["pos0"])
        return head_logits(pp, x, cfg, fp8).astype(jnp.float32)
    return jax.jit(fwd)(p, tokens)


def lm_loss_sum(params, tokens, labels, cfg, fp8=False):
    """Summed next-token cross-entropy over a block of rows; params in
    the program's layout (``pos0`` leaves stacked over layers)."""
    x = jnp.take(params["embed"]["tok"], tokens, axis=0)
    body = jax.checkpoint(lambda h, pp: (_layer(pp, h, cfg, fp8), None))
    x, _ = jax.lax.scan(body, x, params["pos0"])
    logits = head_logits(params, x, cfg, fp8).astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - tgt)


def lm_grad(params, tokens, labels, cfg, *, dtype, rows=1):
    """(mean loss, gradient) over the batch, summed over blocks of rows."""
    p = cast(params, dtype)
    n = tokens.shape[0] * tokens.shape[1]
    vg = jax.jit(jax.value_and_grad(
        lambda pp, t, l: lm_loss_sum(pp, t, l, cfg, dtype == FP8)))
    loss, total = 0.0, None
    for a, b in _row_blocks(tokens.shape[0], rows):
        lb, gb = vg(p, tokens[a:b], labels[a:b])
        gb = jax.tree.map(lambda t: t.astype(jnp.float32), gb)
        loss += float(lb)
        total = gb if total is None else jax.tree.map(jnp.add, total, gb)
    return loss / n, jax.tree.map(lambda g: g / n, total)


def train_steps(opt, params, batches, cfg, *, dtype, rows=1):
    """Steps of AdamW training from ``params`` over ``batches`` (host
    dicts of tokens and labels).  Returns the losses, the first gradient
    as the optimizer applies it (clipped), and the parameters after."""
    p = cast(params, jnp.float32)
    state = {"m": jax.tree.map(jnp.zeros_like, p),
             "v": jax.tree.map(jnp.zeros_like, p),
             "step": jnp.zeros((), jnp.int32)}
    losses, first = [], None
    for bt in batches:
        loss, g = lm_grad(p, jnp.asarray(bt["tokens"]),
                          jnp.asarray(bt["labels"]), cfg, dtype=dtype,
                          rows=rows)
        losses.append(loss)
        if first is None:
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(l))
                                 for l in jax.tree.leaves(g)))
            scale = jnp.minimum(1.0, opt["clip_norm"] / (gnorm + 1e-9))
            first = jax.tree.map(lambda t: t * scale, g)
        p, m, v = adamw(opt, p, g, state, dtype=dtype)
        state = {"m": m, "v": v, "step": state["step"] + 1}
    return losses, first, p
