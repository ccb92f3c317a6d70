"""Shared layer primitives: norms, rope, GQA/MLA attention, dense FFN.

All functions are *global-program* JAX: they never mention mesh axes.
Sharding is injected via ``annotate(x, 'batch', 'seq', ...)`` logical
constraints; on a bare CPU (no active rules) those are no-ops.

Attention uses an online-softmax formulation chunked over the KV length
(``lax.scan``) so the score matrix never materializes at (Sq x Skv) — the
pure-jnp oracle for the Pallas flash kernel, and the memory-feasible path
for the 32k prefill cells.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.parallel.sharding import annotate, current_rules

_NEG_INF = -1e30


def rms_norm(x, w, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    y = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(dt)


def rope_tables(positions, dim: int, theta: float):
    """cos/sin tables for the given absolute positions; positions may be any
    shape, tables get a trailing (dim/2) axis."""
    half = dim // 2
    freqs = jnp.exp(
        -math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: (..., n_heads, dim); cos/sin: broadcastable (..., dim/2).

    Rotates pairs split at half (llama convention)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate(
        [x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# chunked (flash-style) attention — the jnp reference path
# ---------------------------------------------------------------------------

def chunked_attention(q, k, v, *, q_positions, kv_positions, causal=True,
                      kv_chunk: int = 1024, softmax_scale=None,
                      kv_expand=None):
    """Online-softmax attention with GQA.

    q:  (B, Sq, H, dk)         k: (B, Skv, KVH, dk)   v: (B, Skv, KVH, dv)
    q_positions: (Sq,) absolute positions (global — causal masking works
    unchanged when Sq is sequence-sharded); kv_positions: (Skv,).

    ``kv_expand``: optional fn(chunk_slice) -> (k_chunk, v_chunk) producing
    the chunk's keys/values lazily (MLA expands per-chunk from the latent so
    the full per-head K/V never materialize).
    Returns (B, Sq, H, dv).
    """
    B, Sq, H, dk = q.shape
    if kv_expand is None:
        Skv, KVH = k.shape[1], k.shape[2]
        dv = v.shape[-1]
    else:
        Skv, KVH, dk_, dv = kv_expand.shape_info  # type: ignore[attr-defined]
    G = H // KVH
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(dk)
    chunk = min(kv_chunk, Skv)
    n_chunks = -(-Skv // chunk)
    ragged = Skv % chunk != 0

    qg = q.reshape(B, Sq, KVH, G, dk)

    def body(carry, i):
        acc, m, l = carry
        # when no chunk divides Skv, the last chunk is shifted back to end
        # at Skv, and the keys it shares with the chunk before are masked
        s0 = jnp.minimum(i * chunk, Skv - chunk) if ragged else i * chunk
        if kv_expand is None:
            kc = lax.dynamic_slice_in_dim(k, s0, chunk, axis=1)
            vc = lax.dynamic_slice_in_dim(v, s0, chunk, axis=1)
        else:
            kc, vc = kv_expand(s0, chunk)
        pos_c = lax.dynamic_slice_in_dim(kv_positions, s0, chunk, axis=0)
        # scores: (B, KVH, G, Sq, C)
        s = jnp.einsum("bqhgd,bchd->bhgqc", qg, kc,
                       preferred_element_type=jnp.float32) * scale
        mask = None
        if causal:
            mask = q_positions[:, None] >= pos_c[None, :]
        if ragged:
            fresh = (s0 + jnp.arange(chunk) >= i * chunk)[None, :]
            mask = fresh if mask is None else mask & fresh
        if mask is not None:
            s = jnp.where(mask[None, None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhgqc,bchd->bhgqd", p.astype(vc.dtype), vc,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((B, KVH, G, Sq, dv), jnp.float32)
    m0 = jnp.full((B, KVH, G, Sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KVH, G, Sq), jnp.float32)
    (acc, m, l), _ = lax.scan(body, (acc0, m0, l0), jnp.arange(n_chunks))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, dv)
    return out.astype(q.dtype)


def _t_col(t):
    """t scalar or (B,) -> column (1,1)/(B,1) for broadcasting with (B,S)."""
    t = jnp.asarray(t)
    return t[None, None] if t.ndim == 0 else t[:, None]


def decode_attention(q, k, v, k_new, v_new, *, t, kv_positions,
                     softmax_scale=None):
    """Single-step attention against a (possibly seq-sharded) KV cache and
    the step's own key and value.

    q: (B, 1, H, dk); k: (B, S, KVH, dk); v: (B, S, KVH, dv); cache
    positions from ``t`` on (scalar or per-row (B,)) are masked, and the
    new token k_new (B, 1, KVH, dk), v_new (B, 1, KVH, dv) takes position
    ``t``, so the cache is only read here.  Written globally — when the
    cache's S dim is sharded over 'model', the SPMD partitioner emits
    exactly the flash-decode partial-softmax + combine pattern (max/sum
    all-reduces).
    """
    B, _, H, dk = q.shape
    KVH = k.shape[2]
    G = H // KVH
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(dk)
    qg = q.reshape(B, KVH, G, dk)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k,
                   preferred_element_type=jnp.float32) * scale
    s1 = jnp.einsum("bhgd,bhd->bhg", qg, k_new[:, 0],
                    preferred_element_type=jnp.float32) * scale
    valid = (kv_positions[None, :] < _t_col(t))[:, None, None, :]
    s = jnp.where(valid, s, _NEG_INF)
    m = jnp.maximum(jnp.max(s, axis=-1), s1)
    p = jnp.exp(s - m[..., None])
    p1 = jnp.exp(s1 - m)
    l = jnp.sum(p, axis=-1) + p1
    out = jnp.einsum("bhgs,bshd->bhgd", (p / l[..., None]).astype(v.dtype),
                     v, preferred_element_type=jnp.float32)
    out = out + (p1 / l)[..., None] * v_new[:, 0, :, None, :]
    return out.reshape(B, 1, H, v.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------

def gqa_project_qkv(p, x, cfg, positions):
    """x: (B,S,D) -> q (B,S,H,dh), k,v (B,S,KV,dh) with rope applied."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    cos, sin = rope_tables(positions, cfg.head_dim, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    q = annotate(q, "batch", "seq", "heads", None)
    k = annotate(k, "batch", "seq", "kv_heads", None)
    v = annotate(v, "batch", "seq", "kv_heads", None)
    return q, k, v


def attn_block(p, x, cfg, *, positions, kv_chunk=1024):
    """Full-sequence (train/prefill) GQA attention; returns (out, (k, v))."""
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q, k, v = gqa_project_qkv(p, h, cfg, positions)
    o = chunked_attention(q, k, v, q_positions=positions,
                          kv_positions=positions, causal=True,
                          kv_chunk=kv_chunk)
    o = annotate(o, "batch", "seq", "heads", None)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return annotate(out, "batch", "seq", "embed"), (k, v)


def attn_decode(p, x, cache_kv, cfg, *, t, layer, kv_positions):
    """One-token GQA attention against the cache.  x: (B,1,D).
    cache_kv: (k, v), the stacks (P_, B, S, KV, dh) of every layer; attends
    over this ``layer``'s cache before ``t`` and the new token, writes the
    new rows at ``t`` and returns out, (k, v).
    """
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    pos = _t_col(t)                     # (1,1) or (B,1)
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    k1 = jnp.einsum("bsd,dhk->bshk", h, p["wk"])
    v1 = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
    cos, sin = rope_tables(pos, cfg.head_dim, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k1 = apply_rope(k1, cos, sin)
    # read, then write: with the write first, XLA lays the carried cache
    # out unlike the argument and copies it whole at both ends of the scan
    k, v = cache_kv
    o = decode_attention(q, _layer(k, layer), _layer(v, layer), k1, v1, t=t,
                         kv_positions=kv_positions)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return annotate(out, "batch", None, "embed"), (
        cache_update(k, k1, t, layer), cache_update(v, v1, t, layer))


def _layer(stack, layer):
    return lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)


def kv_seq_sharded(shape) -> bool:
    """Whether the active rules shard the sequence dim of a stacked cache
    leaf of ``shape`` (P_, B, S, ...), divisibility fallback included."""
    rules = current_rules()
    if rules is None or rules.mesh is None:
        return False
    spec = rules.spec("layers", "batch", "kv_seq", dims=shape[:3])
    return len(spec) > 2 and spec[2] is not None


def cache_update(stack, new, t, layer):
    """Write ``new`` (B, 1, ...) at sequence position ``t`` (scalar or (B,),
    below S) of ``layer`` in the stacked cache ``stack`` (P_, B, S, ...).

    Where the sequence dim is unsharded (no rules, or rules that leave
    ``kv_seq`` whole) each slot's row is one dynamic-update-slice: with the
    cache donated and carried through the decode's layer scan, a step
    writes B rows a layer in place.  (One scatter would write them all,
    but on the TPU it wants the cache row-major, while XLA keeps it with
    the sequence dim minor for attention: the scatter then costs a copy of
    the whole cache.)  Where the active rules shard ``kv_seq`` the layer's
    cache is rewritten by a one-hot blend instead: a write at a traced
    index into a sharded dim degrades to gather/scatter under SPMD, while
    the blend stays elementwise."""
    new = new.astype(stack.dtype)
    B, S = stack.shape[1], stack.shape[2]
    if not kv_seq_sharded(stack.shape):
        rows = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (B,))
        rest = (jnp.int32(0),) * (stack.ndim - 3)
        for b in range(B):
            stack = lax.dynamic_update_slice(
                stack, new[None, b:b + 1],
                (layer, jnp.int32(b), rows[b]) + rest)
        return stack
    oh = (jnp.arange(S)[None, :] == _t_col(t)).astype(stack.dtype)
    oh = oh.reshape(oh.shape + (1,) * (stack.ndim - 3))
    blended = _layer(stack, layer) * (1 - oh) + new * oh
    return lax.dynamic_update_index_in_dim(stack, blended, layer, 0)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------

def mla_project_q(p, h, cfg):
    m = cfg.mla
    ql = rms_norm(jnp.einsum("bsd,dr->bsr", h, p["wq_a"]), p["q_ln"],
                  cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", ql, p["wq_b"])   # (B,S,H,nope+rope)
    return q


class _MLAExpand:
    """Lazy per-chunk K/V expansion from the cached latent (absorbed form is
    used in decode; prefill expands chunk-by-chunk inside the online-softmax
    scan so the (S, H, dk) tensors never exist at full length)."""

    def __init__(self, p, ckv, k_rope, cfg):
        self.p, self.ckv, self.k_rope, self.cfg = p, ckv, k_rope, cfg
        m = cfg.mla
        B, S = ckv.shape[0], ckv.shape[1]
        H = cfg.n_heads
        self.shape_info = (S, H, m.d_nope + m.d_rope, m.d_v)

    def __call__(self, s0, chunk):
        p, cfg = self.p, self.cfg
        m = cfg.mla
        cc = lax.dynamic_slice_in_dim(self.ckv, s0, chunk, axis=1)
        rc = lax.dynamic_slice_in_dim(self.k_rope, s0, chunk, axis=1)
        k_nope = jnp.einsum("bsr,rhk->bshk", cc, p["wk_b"])
        v = jnp.einsum("bsr,rhk->bshk", cc, p["wv_b"])
        H = cfg.n_heads
        k_rope = jnp.broadcast_to(rc[:, :, None, :],
                                  k_nope.shape[:3] + (m.d_rope,))
        k = jnp.concatenate([k_nope, k_rope.astype(k_nope.dtype)], axis=-1)
        return k, v


def mla_block(p, x, cfg, *, positions, kv_chunk=1024):
    """MLA train/prefill; returns (out, (c_kv, k_rope)) latent cache."""
    m = cfg.mla
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q = mla_project_q(p, h, cfg)
    q_nope, q_rope = q[..., :m.d_nope], q[..., m.d_nope:]
    cos, sin = rope_tables(positions, m.d_rope, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    q = annotate(q, "batch", "seq", "heads", None)

    kv = jnp.einsum("bsd,dr->bsr", h, p["wkv_a"])
    ckv = rms_norm(kv[..., :m.kv_lora], p["kv_ln"], cfg.norm_eps)
    k_rope = kv[..., m.kv_lora:]
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0, :]
    ckv = annotate(ckv, "batch", "seq", "lora")

    expand = _MLAExpand(p, ckv, k_rope, cfg)
    scale = 1.0 / math.sqrt(m.d_nope + m.d_rope)
    o = chunked_attention(q, None, None, q_positions=positions,
                          kv_positions=positions, causal=True,
                          kv_chunk=kv_chunk, softmax_scale=scale,
                          kv_expand=expand)
    o = annotate(o, "batch", "seq", "heads", None)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return annotate(out, "batch", "seq", "embed"), (ckv, k_rope)


def mla_decode(p, x, cache, cfg, *, t, layer, kv_positions):
    """Absorbed-matmul MLA decode: attention runs in the latent space; the
    per-head K/V are never expanded.  cache = (c_kv (P_,B,S,r), k_rope
    (P_,B,S,dr)), the stacks of every layer; attends over this ``layer``'s
    cache before ``t`` and the new token, writes the new rows at ``t`` and
    returns out, cache.
    """
    m = cfg.mla
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q = mla_project_q(p, h, cfg)                       # (B,1,H,nope+rope)
    q_nope, q_rope = q[..., :m.d_nope], q[..., m.d_nope:]
    pos = _t_col(t)
    cos, sin = rope_tables(pos, m.d_rope, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)

    kv = jnp.einsum("bsd,dr->bsr", h, p["wkv_a"])
    ckv1 = rms_norm(kv[..., :m.kv_lora], p["kv_ln"], cfg.norm_eps)
    kr1 = apply_rope(kv[..., None, m.kv_lora:], cos, sin)[:, :, 0, :]
    ckv, k_rope = _layer(cache[0], layer), _layer(cache[1], layer)

    # absorb W_uk into q: q_lat (B,H,r) = q_nope . W_uk; the cache holds
    # positions before t, the step's own row takes t
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["wk_b"])[:, 0]
    q_lat = q_lat.astype(jnp.float32)
    q_rope = q_rope[:, 0].astype(jnp.float32)
    scale = 1.0 / math.sqrt(m.d_nope + m.d_rope)
    s = (jnp.einsum("bhr,bsr->bhs", q_lat, ckv.astype(jnp.float32))
         + jnp.einsum("bhk,bsk->bhs", q_rope,
                      k_rope.astype(jnp.float32))) * scale
    s1 = (jnp.einsum("bhr,br->bh", q_lat, ckv1[:, 0].astype(jnp.float32))
          + jnp.einsum("bhk,bk->bh", q_rope,
                       kr1[:, 0].astype(jnp.float32))) * scale
    valid = (kv_positions[None, :] < _t_col(t))[:, None, :]
    s = jnp.where(valid, s, _NEG_INF)
    m_ = jnp.maximum(jnp.max(s, axis=-1), s1)
    pr = jnp.exp(s - m_[..., None])
    p1 = jnp.exp(s1 - m_)
    l = jnp.sum(pr, axis=-1) + p1
    o_lat = jnp.einsum("bhs,bsr->bhr", (pr / l[..., None]).astype(ckv.dtype),
                       ckv)
    o_lat = o_lat + (p1 / l)[..., None].astype(ckv.dtype) * ckv1
    o = jnp.einsum("bhr,rhk->bhk", o_lat, p["wv_b"])   # absorb W_uv
    out = jnp.einsum("bhk,hkd->bd", o, p["wo"])[:, None]
    return annotate(out, "batch", None, "embed"), (
        cache_update(cache[0], ckv1, t, layer),
        cache_update(cache[1], kr1, t, layer))


# ---------------------------------------------------------------------------
# dense FFN
# ---------------------------------------------------------------------------

def ffn_block(p, x, cfg):
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    g = jnp.einsum("bsd,df->bsf", h, p["w_gate"])
    u = jnp.einsum("bsd,df->bsf", h, p["w_up"])
    g = annotate(g, "batch", "seq", "ffn")
    u = annotate(u, "batch", "seq", "ffn")
    y = jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, p["w_down"])
    return annotate(y, "batch", "seq", "embed")
