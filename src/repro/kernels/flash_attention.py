"""Causal GQA flash attention Pallas kernel.

Grid (B, H, Sq/bq, Skv/bk), KV innermost.  Each (b, h, iq) owns an online-
softmax state (m, l, acc) in VMEM scratch that survives across KV steps —
scores for one (bq, bk) tile exist only in VMEM/VREGs, never in HBM (the
jnp reference path materializes (B, H, Sq, bk) per chunk in HBM; this
kernel is the memory-term fix identified in EXPERIMENTS.md §Perf).

GQA is handled in the index map: KV head = h // (H // KVH), so KV tiles are
re-streamed for the query heads of one group (VMEM-friendly; an alternative
blocking over grouped heads is a tuning knob left to the autotuner).

Tile defaults 128x128: MXU-aligned in both the q-row and kv-row dims.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiles import block

_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  n_k: int, bq: int, bk: int, scale: float, causal: bool,
                  q_offset: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if causal:
        # queries align to the END of the KV sequence (suffix semantics:
        # Sq < Skv means the queries are the last Sq positions)
        qpos = q_offset + iq * bq + \
            jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(qpos >= kpos, s, _NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
    v = v_ref[0, 0].astype(jnp.float32)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == n_k - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True, bq: int = 128,
                           bk: int = 128, interpret: bool = False):
    """q: (B, H, Sq, d); k/v: (B, KVH, Skv, d) -> (B, H, Sq, d)."""
    B, H, Sq, d = q.shape
    KVH, Skv = k.shape[1], k.shape[2]
    G = H // KVH
    # query rows are independent, so a partial last q block is exact; a
    # partial KV block would feed garbage into the softmax, so a KV length
    # without such a divisor is taken whole
    bq, bk = block(bq, Sq, 8), block(bk, Skv, 128)
    if Skv % bk:
        bk = Skv
    n_k = Skv // bk
    grid = (B, H, pl.cdiv(Sq, bq), n_k)
    scale = 1.0 / math.sqrt(d)
    return pl.pallas_call(
        functools.partial(_flash_kernel, n_k=n_k, bq=bq, bk=bk,
                          scale=scale, causal=causal, q_offset=Skv - Sq),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
