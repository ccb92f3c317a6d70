"""Recurrent mixers: Mamba (selective SSM), mLSTM and sLSTM (xLSTM).

All three share ``chunked_scan``: an outer ``lax.scan`` over sequence chunks
whose body is checkpointed (so backward saves only chunk-boundary states)
and an inner ``lax.scan`` over steps.  This bounds both the live activation
set (one chunk's discretized tensors) and the autodiff residuals — the
memory-hierarchy adaptation of Mamba's fused-kernel insight (DESIGN.md §2):
on TPU we block for HBM/VMEM via scan structure instead of a CUDA kernel.

Per-channel recurrences are independent across the inner dimension, so the
'inner' logical axis shards over 'model' with zero cross-shard traffic in
the recurrent core.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.parallel.sharding import annotate, current_rules, is_axes_leaf
from .layers import rms_norm


def _manual_scan(scan_fn, arg_axes, out_axes, args):
    """Run ``scan_fn(*args)`` inside shard_map when rules are active.

    Why: the recurrent cores use shared weights (R, A) whose gradients
    contract over the batch-sharded dim; under plain SPMD the backward scan
    all-reduces that partial EVERY STEP (measured 2.3e11 B/dev on
    xlstm x train_4k).  Under shard_map, AD accumulates weight-gradient
    partials shard-locally and inserts one psum at the region boundary
    (EXPERIMENTS.md §Perf H1).

    ``arg_axes``/``out_axes``: logical-axes trees matching args/outputs
    (leaves are axis tuples).
    """
    rules = current_rules()
    if rules is None or rules.mesh is None:
        return scan_fn(*args)
    import jax as _jax
    from jax.sharding import PartitionSpec as _P

    def spec_of(ax, leaf):
        return rules.spec(*ax, dims=leaf.shape)
    in_specs = _jax.tree.map(spec_of, tuple(arg_axes), tuple(args),
                             is_leaf=is_axes_leaf)
    out_shapes = _jax.eval_shape(scan_fn, *args)
    out_specs = _jax.tree.map(spec_of, out_axes, out_shapes,
                              is_leaf=is_axes_leaf)
    fn = jax.shard_map(scan_fn, mesh=rules.mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(*args)


def chunked_scan(step_fn, carry, xs, *, chunk: int, checkpoint: bool = True):
    """scan(step_fn, carry, xs) with xs leaves shaped (S, ...), restructured
    as nc chunks of ``chunk`` steps; the chunk body is rematerialized in
    backward.  Returns (final_carry, ys) with ys leaves (S, ...)."""
    S = jax.tree_util.tree_leaves(xs)[0].shape[0]
    if S <= chunk:
        return lax.scan(step_fn, carry, xs)
    nc = S // chunk
    assert nc * chunk == S, (S, chunk)
    xs_c = jax.tree.map(
        lambda a: a.reshape((nc, chunk) + a.shape[1:]), xs)

    def chunk_body(c, x_chunk):
        return lax.scan(step_fn, c, x_chunk)

    if checkpoint:
        chunk_body = jax.checkpoint(
            chunk_body, policy=jax.checkpoint_policies.nothing_saveable)
    carry, ys_c = lax.scan(chunk_body, carry, xs_c)
    ys = jax.tree.map(
        lambda a: a.reshape((S,) + a.shape[2:]), ys_c)
    return carry, ys


# ---------------------------------------------------------------------------
# causal depthwise conv (shared by mamba/mlstm)
# ---------------------------------------------------------------------------

def causal_conv(x, w, state=None):
    """x: (B, S, C), w: (K, C) depthwise.  ``state``: (B, K-1, C) carried
    from the previous segment (decode); returns (y, new_state)."""
    B, S, C = x.shape
    K = w.shape[0]
    if state is None:
        state = jnp.zeros((B, K - 1, C), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)
    y = jnp.zeros_like(x)
    for i in range(K):  # K is 4: unrolled shifts beat conv_general here
        y = y + xp[:, i:i + S, :] * w[i]
    new_state = xp[:, S:, :] if K > 1 else state
    return y, new_state


# ---------------------------------------------------------------------------
# Mamba (selective SSM)
# ---------------------------------------------------------------------------

def _mamba_inner(p, xz, cfg, conv_state, ssm_state, *, chunk):
    """xz: (B, S, 2*di) from in_proj.  Returns (y (B,S,di), conv, ssm)."""
    di = cfg.d_inner
    N = cfg.d_state
    x, z = xz[..., :di], xz[..., di:]
    x, conv_state = causal_conv(x, p["conv_w"], conv_state)
    x = jax.nn.silu(x)
    x = annotate(x, "batch", "seq", "inner")

    dbc = jnp.einsum("bsc,cr->bsr", x, p["x_proj"])
    dtr = di // 16
    dt = jax.nn.softplus(
        jnp.einsum("bsr,rc->bsc", dbc[..., :dtr], p["dt_w"]) + p["dt_b"])
    Bc = dbc[..., dtr:dtr + N]
    Cc = dbc[..., dtr + N:]
    A = -jnp.exp(p["a_log"].astype(jnp.float32))          # (di, N)

    # step over (S,)-leading tensors; per-chunk discretization only.
    # the recurrent core runs under shard_map (_manual_scan): A's gradient
    # then accumulates shard-locally instead of all-reducing per step.
    def scan_part(A_, ssm_state, x_s, dt_s, b_s, c_s):
        def step(h, inp):
            x_t, dt_t, b_t, c_t = inp                # (B,di),(B,di),(B,N)
            dA = jnp.exp(dt_t.astype(jnp.float32)[..., None] * A_)
            dBx = (dt_t * x_t).astype(jnp.float32)[..., None] * \
                b_t.astype(jnp.float32)[:, None, :]
            h = h * dA + dBx
            y_t = jnp.einsum("bcn,bn->bc", h, c_t.astype(jnp.float32))
            return h, y_t.astype(x_t.dtype)
        return chunked_scan(step, ssm_state, (x_s, dt_s, b_s, c_s),
                            chunk=chunk)

    xs = (x.transpose(1, 0, 2), dt.transpose(1, 0, 2),
          Bc.transpose(1, 0, 2), Cc.transpose(1, 0, 2))
    b_ax = ("batch",)
    ssm_state, ys = _manual_scan(
        scan_part,
        (("inner", "state"), ("batch", "inner", "state"),
         (None, "batch", "inner"), (None, "batch", "inner"),
         (None, "batch", None), (None, "batch", None)),
        (("batch", "inner", "state"), (None, "batch", "inner")),
        (A, ssm_state) + xs)
    y = ys.transpose(1, 0, 2) + x * p["d"]
    y = y * jax.nn.silu(z)
    return annotate(y, "batch", "seq", "inner"), conv_state, ssm_state


def mamba_block(p, x, cfg, *, chunk=256, conv_state=None, ssm_state=None):
    """Full mamba block.  Returns (out, (conv_state, ssm_state))."""
    B = x.shape[0]
    di, N = cfg.d_inner, cfg.d_state
    if ssm_state is None:
        ssm_state = jnp.zeros((B, di, N), jnp.float32)
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    xz = jnp.einsum("bsd,dc->bsc", h, p["in_proj"])
    xz = annotate(xz, "batch", "seq", "inner")
    y, conv_state, ssm_state = _mamba_inner(
        p, xz, cfg, conv_state, ssm_state, chunk=chunk)
    out = jnp.einsum("bsc,cd->bsd", y, p["out_proj"])
    return annotate(out, "batch", "seq", "embed"), (conv_state, ssm_state)


# ---------------------------------------------------------------------------
# mLSTM (xLSTM matrix-memory block, recurrent-chunked form)
# ---------------------------------------------------------------------------

def mlstm_block(p, x, cfg, *, chunk=128, conv_state=None, state=None,
                mode: str = "chunkwise"):
    """Returns (out, (conv_state, (C, n, m))).

    State: C (B, nh, dv, dk) matrix memory, n (B, nh, dk) normalizer,
    m (B, nh) log-space stabilizer.  ``mode``: 'chunkwise' (matmul-shaped,
    default for S>1) or 'recurrent' (the oracle; always used for S=1)."""
    B, S, D = x.shape
    di = cfg.d_inner
    nh = cfg.n_heads
    dh = di // nh
    if state is None:
        state = (jnp.zeros((B, nh, dh, dh), jnp.float32),
                 jnp.zeros((B, nh, dh), jnp.float32),
                 jnp.full((B, nh), -1e30, jnp.float32))
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    xz = jnp.einsum("bsd,dc->bsc", h, p["up"])
    xi, z = xz[..., :di], xz[..., di:]
    xi, conv_state = causal_conv(xi, p["conv_w"], conv_state)
    xi = jax.nn.silu(xi)
    xi = annotate(xi, "batch", "seq", "inner")

    q = jnp.einsum("bsc,ce->bse", xi, p["wq"]).reshape(B, S, nh, dh)
    k = jnp.einsum("bsc,ce->bse", xi, p["wk"]).reshape(B, S, nh, dh)
    v = jnp.einsum("bsc,ce->bse", xi, p["wv"]).reshape(B, S, nh, dh)
    # shard ONLY the v-dim (C's rows): q/k stay replicated on dh so the
    # recurrence's q.k contraction and the C/n updates are all shard-local
    # (a sharded k-dim costs one all-reduce PER RECURRENCE STEP — measured
    # 2.3e11 B/dev on train_4k; see EXPERIMENTS.md §Perf H1)
    q = annotate(q, "batch", "seq", None, None)
    k = annotate(k, "batch", "seq", None, None)
    v = annotate(v, "batch", "seq", None, "head_ff")
    gif = jnp.einsum("bsc,cg->bsg", xi, p["wif"]) + p["b_if"]
    ig, fg = gif[..., :nh], gif[..., nh:]
    scale = 1.0 / math.sqrt(dh)

    if mode == "chunkwise" and S > 1:
        state, y4 = _mlstm_chunkwise(q, k, v, ig, fg, state,
                                     chunk=chunk, scale=scale)
        y = y4.reshape(B, S, di)
        y = annotate(y, "batch", "seq", "inner")
        y = y * jax.nn.silu(z)
        out = jnp.einsum("bsc,cd->bsd", y, p["down"])
        return annotate(out, "batch", "seq", "embed"), (conv_state, state)

    def scan_part(state, q_s, k_s, v_s, i_s, f_s):
        def step(carry, inp):
            C, n, m = carry
            q_t, k_t, v_t, i_t, f_t = inp
            i_t = i_t.astype(jnp.float32)
            logf = -jax.nn.softplus(-f_t.astype(jnp.float32))
            m_new = jnp.maximum(logf + m, i_t)
            fe = jnp.exp(logf + m - m_new)
            ie = jnp.exp(i_t - m_new)
            kf = k_t.astype(jnp.float32) * scale
            C = C * fe[..., None, None] + \
                ie[..., None, None] * v_t.astype(jnp.float32)[..., None] * \
                kf[:, :, None, :]
            n = n * fe[..., None] + ie[..., None] * kf
            qy = jnp.einsum("bhvk,bhk->bhv", C, q_t.astype(jnp.float32))
            denom = jnp.maximum(
                jnp.abs(jnp.einsum("bhk,bhk->bh", n,
                                   q_t.astype(jnp.float32))),
                jnp.exp(-m_new))[..., None]
            y_t = qy / denom
            return (C, n, m_new), y_t.astype(q_t.dtype)
        return chunked_scan(step, state, (q_s, k_s, v_s, i_s, f_s),
                            chunk=chunk)

    xs = (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2, 3),
          v.transpose(1, 0, 2, 3), ig.transpose(1, 0, 2),
          fg.transpose(1, 0, 2))
    st_ax = (("batch", None, "head_ff", None), ("batch", None, None),
             ("batch", None))
    state, ys = _manual_scan(
        scan_part,
        (st_ax,
         (None, "batch", None, None), (None, "batch", None, None),
         (None, "batch", None, "head_ff"),
         (None, "batch", None), (None, "batch", None)),
        (st_ax, (None, "batch", None, "head_ff")),
        (state,) + xs)
    y = ys.transpose(1, 0, 2, 3).reshape(B, S, di)
    y = annotate(y, "batch", "seq", "inner")
    y = y * jax.nn.silu(z)
    out = jnp.einsum("bsc,cd->bsd", y, p["down"])
    return annotate(out, "batch", "seq", "embed"), (conv_state, state)


def _mlstm_chunkwise(q, k, v, ig, fg, state, *, chunk: int, scale: float):
    """Chunkwise-parallel mLSTM (beyond-paper; EXPERIMENTS.md §Perf H2-k).

    Exact reformulation of the recurrent form: with a_t = cumsum(logsig f),
    b_s = i_s - a_s and stabilizer m_t = a_t + mm_t where
    mm_t = max(m_in, cummax b), every intra-chunk weight collapses to
    exp(b_s - mm_t)·(q_t·k_s) — two (L x L) masked matmuls and two state
    products per chunk instead of L sequential outer products: MXU-shaped
    compute, state carried once per chunk (HBM carry traffic / L).

    q,k,v: (B,S,nh,dh); ig,fg: (B,S,nh); state=(C,n,m) as in mlstm_block.
    Returns (state, y (B,S,nh,dh)).
    """
    B, S, nh, dh = q.shape
    L = min(chunk, S)
    while S % L:
        L -= 1
    nc = S // L

    def to_chunks(x):
        return x.reshape((B, nc, L) + x.shape[2:]).swapaxes(0, 1)

    qc, kc, vc = to_chunks(q), to_chunks(k), to_chunks(v)
    ic, fc = to_chunks(ig.astype(jnp.float32)), \
        to_chunks(fg.astype(jnp.float32))

    def chunk_body(carry, xs):
        C, n, m_in = carry                       # (B,h,dv,dk),(B,h,dk),(B,h)
        q_, k_, v_, i_, f_ = xs                  # (B,L,h,...)
        logf = -jax.nn.softplus(-f_)             # (B,L,h)
        a = jnp.cumsum(logf, axis=1)
        b = i_ - a
        mm = jnp.maximum(jax.lax.cummax(b, axis=1), m_in[:, None])
        qf = q_.astype(jnp.float32)
        kf = k_.astype(jnp.float32) * scale
        vf = v_.astype(jnp.float32)

        sqk = jnp.einsum("blhd,bshd->bhls", qf, kf)          # (B,h,L,L)
        b_bhs = b.transpose(0, 2, 1)                          # (B,h,S)
        mm_bht = mm.transpose(0, 2, 1)                        # (B,h,T)
        # dec[b,h,t,s] = exp(b_s - mm_t); mask s<=t
        dec = jnp.exp(b_bhs[:, :, None, :] - mm_bht[:, :, :, None])
        mask = jnp.tril(jnp.ones((L, L), bool))
        Wt = jnp.where(mask[None, None], sqk * dec, 0.0)
        intra = jnp.einsum("bhts,bshd->bthd", Wt, vf)

        inter_scale = jnp.exp(m_in[:, None] - mm)            # (B,L,h)
        inter = jnp.einsum("bhvk,blhk->blhv", C, qf) * \
            inter_scale[..., None]

        Nw = jnp.where(mask[None, None], dec, 0.0)           # (B,h,t,s)
        n_t = jnp.einsum("bhts,bshk->bthk", Nw, kf) + \
            n[:, None] * inter_scale[..., None]
        qn = jnp.einsum("blhk,blhk->blh", qf, n_t)
        m_t = a + mm                                          # absolute
        denom = jnp.maximum(jnp.abs(qn), jnp.exp(-m_t))[..., None]
        y = ((inter + intra) / denom).astype(q_.dtype)

        mm_L = mm[:, -1]
        wS = jnp.exp(b - mm_L[:, None])                       # (B,L,h)
        C_out = jnp.einsum("blh,blhv,blhk->bhvk", wS, vf, kf) + \
            jnp.exp(m_in - mm_L)[..., None, None] * C
        n_out = jnp.einsum("blh,blhk->bhk", wS, kf) + \
            jnp.exp(m_in - mm_L)[..., None] * n
        m_out = a[:, -1] + mm_L
        return (C_out, n_out, m_out), y

    state, ys = lax.scan(chunk_body, state, (qc, kc, vc, ic, fc))
    y = ys.swapaxes(0, 1).reshape(B, S, nh, dh)
    return state, y


# ---------------------------------------------------------------------------
# sLSTM (xLSTM scalar-memory block)
# ---------------------------------------------------------------------------

def slstm_block(p, x, cfg, *, chunk=128, state=None):
    """Strictly sequential scalar-memory LSTM with exponential gating and
    per-head block-diagonal recurrence.  Returns (out, state);
    state = (c, n, h, m) each (B, D) [(B, nh) for m]."""
    B, S, D = x.shape
    nh = cfg.n_heads
    dh = D // nh
    di = cfg.d_inner
    if state is None:
        state = (jnp.zeros((B, D), jnp.float32),
                 jnp.zeros((B, D), jnp.float32),
                 jnp.zeros((B, D), jnp.float32),
                 jnp.full((B, nh), -1e30, jnp.float32))
    xh = rms_norm(x, p["ln"], cfg.norm_eps)
    wx = jnp.einsum("bsd,dg->bsg", xh, p["w"]) + p["b"]     # (B,S,4D)

    def scan_part(r_, state, wx_s):
        def step(carry, wx_t):
            c, n, h, m = carry
            hh = h.reshape(-1, nh, dh)
            rg = jnp.einsum("bhk,hkg->bhg", hh, r_).reshape(h.shape[0],
                                                            4 * D)
            g = (wx_t.astype(jnp.float32) + rg)
            zt = jnp.tanh(g[..., :D])
            it = g[..., D:2 * D].reshape(-1, nh, dh).mean(-1)
            ft = g[..., 2 * D:3 * D].reshape(-1, nh, dh).mean(-1)
            ot = jax.nn.sigmoid(g[..., 3 * D:])
            logf = -jax.nn.softplus(-ft)
            m_new = jnp.maximum(logf + m, it)
            fe = jnp.exp(logf + m - m_new)[..., None]
            ie = jnp.exp(it - m_new)[..., None]
            fe = jnp.broadcast_to(fe, it.shape + (dh,)).reshape(h.shape)
            ie = jnp.broadcast_to(ie, it.shape + (dh,)).reshape(h.shape)
            c_new = fe * c + ie * zt
            n_new = fe * n + ie
            h_new = ot * c_new / jnp.maximum(n_new, 1.0)
            return (c_new, n_new, h_new, m_new), h_new.astype(wx_t.dtype)
        return chunked_scan(step, state, wx_s, chunk=chunk)

    st_ax = (("batch", None), ("batch", None), ("batch", None),
             ("batch", None))
    state, ys = _manual_scan(
        scan_part,
        ((None, None, None), st_ax, (None, "batch", None)),
        (st_ax, (None, "batch", None)),
        (p["r"], state, wx.transpose(1, 0, 2)))
    h_seq = ys.transpose(1, 0, 2)
    # per-block projection FFN (d_ff=0 archs carry their own up/down)
    u = jnp.einsum("bsd,dc->bsc", h_seq, p["up"])   # (B,S,2*di) GLU
    u = annotate(u, "batch", "seq", "inner")
    out = jnp.einsum("bsc,cd->bsd", jax.nn.silu(u[..., :di]) * u[..., di:],
                     p["down"])
    return annotate(out, "batch", "seq", "embed"), state
