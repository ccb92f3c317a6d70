"""Causal GQA flash attention: a Pallas TPU kernel with its own backward.

The model's full-sequence self-attention (``models.layers.attn_block``)
reaches it on the TPU; ``models.layers.chunked_attention`` is the jnp
reference that the CPU, latent attention and sharded programs run.

Layout.  The model's (B, S, H, d) queries and (B, S, KVH, d) keys and values
are padded to a multiple of 128 positions (a padded key lies after every
real query, so causality masks it) and laid out (B, heads, S, d).  The
products take what XLA's default precision gives an f32 product on the
backend that runs them: on the TPU the operands are rounded to bf16 for
one MXU pass, in the Pallas interpreter they keep the inputs' dtype.
The queries come scaled by 1/sqrt(d), so no kernel scales a score tile.
Scores, the softmax state and every accumulator stay f32; the outputs
come back in the inputs' dtype.

Three kernels, each over a grid (B, KVH, outer tile, inner tile) whose block
holds the G = H / KVH query heads of one KV head, stacked as G * bq rows, so
each K/V tile is fetched once per group:

- ``flash_fwd``: per query tile, the online softmax over KV tiles, with
  (m, l, acc) in VMEM scratch across the KV axis; writes o and each row's
  log-sum-exp.
- ``flash_bwd_dkv``: per KV tile, over query tiles, dK and dV from the
  saved log-sum-exp.  Its scores are computed transposed, (bk, G * bq), so
  the per-row statistics broadcast as lane-major rows.
- ``flash_bwd_dq``: per query tile, over KV tiles, dQ.

Causal skipping: a tile wholly above the diagonal runs no dot, and its
index map repeats the block of the step before, so no DMA is issued for
it.  Only tiles that cross the diagonal are masked.

Tile sizes come from the shapes alone (``tiles.flash_tiles``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiles import FlashTiles, flash_tiles

_NEG_INF = -1e30
_LANES = 128
_NT = (((1,), (1,)), ((), ()))      # contract the last dims: a @ b.T
_VMEM_LIMIT = 64 * 2**20


def _lanes(x, n: int):
    """(rows, 128) lane-replicated columns -> (rows, n)."""
    return x[:, :n] if n <= _LANES else jnp.tile(x, (1, n // _LANES))


def _row(ref):
    """(G, bq) per-row statistics -> one (1, G * bq) row."""
    return jnp.concatenate([ref[g:g + 1, :] for g in range(ref.shape[0])],
                           axis=1)


def _columns(ref):
    """(G, bq) per-row statistics -> (G * bq, 128) lane-replicated."""
    G, bq = ref.shape
    return jnp.concatenate(
        [jnp.broadcast_to(ref[g:g + 1, :], (_LANES, bq)).T
         for g in range(G)], axis=0)


def _causal_steps(step, *, q_lo, bq: int, k_lo, bk: int):
    """Run ``step(masked)`` for one (query tile, key tile) pair: not at all
    when every key lies after every query, unmasked when every key lies at
    or before every query, masked where the tile crosses the diagonal."""
    below = k_lo + bk - 1 <= q_lo
    crosses = jnp.logical_and(k_lo <= q_lo + bq - 1,
                              jnp.logical_not(below))
    pl.when(below)(lambda: step(False))
    pl.when(crosses)(lambda: step(True))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref):
    G, bq, d = q_ref.shape
    bk = k_ref.shape[0]
    n = G * bq
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def step(masked: bool):
        s = lax.dot_general(q_ref[...].reshape(n, d), k_ref[...], _NT,
                            preferred_element_type=jnp.float32)
        if masked:
            shape = (G, bq, bk)
            qpos = iq * bq + lax.broadcasted_iota(jnp.int32, shape, 1)
            kpos = ik * bk + lax.broadcasted_iota(jnp.int32, shape, 2)
            s = jnp.where(kpos <= qpos, s.reshape(shape),
                          _NEG_INF).reshape(n, bk)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, bk))
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * _lanes(alpha, d) + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...],
            preferred_element_type=jnp.float32)

    _causal_steps(step, q_lo=iq * bq, bq=bq, k_lo=ik * bk, bk=bk)

    @pl.when(ik == pl.num_programs(3) - 1)
    def _done():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / _lanes(l, d)).reshape(
            G, bq, d).astype(o_ref.dtype)
        lse = (m_ref[...] + jnp.log(l)).T            # (128, n), rows equal
        for g in range(G):
            lse_ref[g:g + 1, :] = lse[0:1, g * bq:(g + 1) * bq]


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_acc, dv_acc):
    G, bq, d = q_ref.shape
    bk = k_ref.shape[0]
    n = G * bq
    ik, iq = pl.program_id(2), pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    def step(masked: bool):
        q = q_ref[...].reshape(n, d)
        do = do_ref[...].reshape(n, d)
        st = lax.dot_general(k_ref[...], q, _NT,
                             preferred_element_type=jnp.float32)
        if masked:
            shape = (bk, bq)
            kpos = ik * bk + lax.broadcasted_iota(jnp.int32, shape, 0)
            qpos = iq * bq + lax.broadcasted_iota(jnp.int32, shape, 1)
            keep = jnp.tile((kpos <= qpos).astype(jnp.int32), (1, G))
            st = jnp.where(keep != 0, st, _NEG_INF)
        pt = jnp.exp(st - _row(lse_ref))                        # (bk, n)
        dv_acc[...] += jnp.dot(pt.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
        dpt = lax.dot_general(v_ref[...], do, _NT,
                              preferred_element_type=jnp.float32)
        dst = pt * (dpt - _row(di_ref))
        dk_acc[...] += jnp.dot(dst.astype(q.dtype), q,
                               preferred_element_type=jnp.float32)

    _causal_steps(step, q_lo=iq * bq, bq=bq, k_lo=ik * bk, bk=bk)

    @pl.when(iq == pl.num_programs(3) - 1)
    def _done():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, dq_acc,
               lse_col, di_col, *, scale: float):
    G, bq, d = q_ref.shape
    bk = k_ref.shape[0]
    n = G * bq
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)
        lse_col[...] = _columns(lse_ref)
        di_col[...] = _columns(di_ref)

    def step(masked: bool):
        k = k_ref[...]
        s = lax.dot_general(q_ref[...].reshape(n, d), k, _NT,
                            preferred_element_type=jnp.float32)
        if masked:
            shape = (G, bq, bk)
            qpos = iq * bq + lax.broadcasted_iota(jnp.int32, shape, 1)
            kpos = ik * bk + lax.broadcasted_iota(jnp.int32, shape, 2)
            s = jnp.where(kpos <= qpos, s.reshape(shape),
                          _NEG_INF).reshape(n, bk)
        p = jnp.exp(s - _lanes(lse_col[...], bk))
        dp = lax.dot_general(do_ref[...].reshape(n, d), v_ref[...], _NT,
                             preferred_element_type=jnp.float32)
        ds = p * (dp - _lanes(di_col[...], bk))
        dq_acc[...] += jnp.dot(ds.astype(k.dtype), k,
                               preferred_element_type=jnp.float32)

    _causal_steps(step, q_lo=iq * bq, bq=bq, k_lo=ik * bk, bk=bk)

    @pl.when(ik == pl.num_programs(3) - 1)
    def _done():
        dq_ref[...] = (dq_acc[...] * scale).reshape(G, bq, d).astype(
            dq_ref.dtype)


# ------------------------------------------------------------ pallas calls

def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _fwd_call(q, k, v, tiles, interpret):
    """Kernel layout: q (B, H, S, d), k/v (B, KVH, S, d) ->
    o (B, H, S, d) f32, lse (B, KVH, G, S) f32."""
    B, H, S, d = q.shape
    KVH = k.shape[1]
    G = H // KVH
    bq, bk = tiles
    last = lambda i: ((i + 1) * bq - 1) // bk      # noqa: E731
    q_spec = pl.BlockSpec((None, G, bq, d), lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec(
        (None, None, bk, d),
        lambda b, h, i, j: (b, h, jnp.minimum(j, last(i)), 0))
    return pl.pallas_call(
        _fwd_kernel,
        grid=(B, KVH, S // bq, S // bk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec,
                   pl.BlockSpec((None, None, G, bq),
                                lambda b, h, i, j: (b, h, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((B, H, S, d), jnp.float32),
                   jax.ShapeDtypeStruct((B, KVH, G, S), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((G * bq, _LANES), jnp.float32),
                        pltpu.VMEM((G * bq, _LANES), jnp.float32),
                        pltpu.VMEM((G * bq, d), jnp.float32)],
        compiler_params=_params(), interpret=interpret, name="flash_fwd",
    )(q, k, v)


def _dkv_call(q, k, v, do, lse, di, tiles, interpret):
    B, H, S, d = q.shape
    KVH = k.shape[1]
    G = H // KVH
    bq, bk = tiles
    first = lambda j: (j * bk) // bq               # noqa: E731
    q_spec = pl.BlockSpec(
        (None, G, bq, d),
        lambda b, h, j, i: (b, h, jnp.maximum(i, first(j)), 0))
    row_spec = pl.BlockSpec(
        (None, None, G, bq),
        lambda b, h, j, i: (b, h, 0, jnp.maximum(i, first(j))))
    kv_spec = pl.BlockSpec((None, None, bk, d), lambda b, h, j, i: (b, h, j, 0))
    return pl.pallas_call(
        _dkv_kernel,
        grid=(B, KVH, S // bk, S // bq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct((B, KVH, S, d), jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32)] * 2,
        compiler_params=_params(), interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, di)


def _dq_call(q, k, v, do, lse, di, tiles, interpret):
    B, H, S, d = q.shape
    KVH = k.shape[1]
    G = H // KVH
    bq, bk = tiles
    last = lambda i: ((i + 1) * bq - 1) // bk      # noqa: E731
    q_spec = pl.BlockSpec((None, G, bq, d), lambda b, h, i, j: (b, h, i, 0))
    row_spec = pl.BlockSpec((None, None, G, bq),
                            lambda b, h, i, j: (b, h, 0, i))
    kv_spec = pl.BlockSpec(
        (None, None, bk, d),
        lambda b, h, i, j: (b, h, jnp.minimum(j, last(i)), 0))
    return pl.pallas_call(
        functools.partial(_dq_kernel, scale=1.0 / math.sqrt(d)),
        grid=(B, KVH, S // bq, S // bk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, S, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((G * bq, d), jnp.float32),
                        pltpu.VMEM((G * bq, _LANES), jnp.float32),
                        pltpu.VMEM((G * bq, _LANES), jnp.float32)],
        compiler_params=_params(), interpret=interpret, name="flash_bwd_dq",
    )(q, k, v, do, lse, di)


# ------------------------------------------------------ layout and autodiff

def _to_kernel(x, s_pad: int, interpret: bool):
    """(B, S, heads, d) -> (B, heads, s_pad, d), zero-padded; bf16 on the
    TPU (see the module docstring)."""
    x = jnp.pad(x, ((0, 0), (0, s_pad - x.shape[1]), (0, 0), (0, 0)))
    return x.transpose(0, 2, 1, 3).astype(x.dtype if interpret
                                          else jnp.bfloat16)


def _from_kernel(x, S: int, dtype):
    """(B, heads, s_pad, d) -> (B, S, heads, d)."""
    return x[:, :, :S].transpose(0, 2, 1, 3).astype(dtype)


def _rows(x, KVH: int, s_pad: int):
    """Per-row statistics (B, S, H) -> (B, KVH, G, s_pad), zero-padded."""
    B, S, H = x.shape
    x = jnp.pad(x, ((0, 0), (0, s_pad - S), (0, 0)))
    return x.transpose(0, 2, 1).reshape(B, KVH, H // KVH, s_pad)


def _forward(q, k, v, tiles: FlashTiles, interpret: bool):
    S = q.shape[1]
    s_pad = -(-S // _LANES) * _LANES
    qt = _to_kernel(q * (1.0 / math.sqrt(q.shape[-1])), s_pad, interpret)
    kt, vt = (_to_kernel(x, s_pad, interpret) for x in (k, v))
    o, lse = _fwd_call(qt, kt, vt, tiles.fwd, interpret)
    return _from_kernel(o, S, q.dtype), (qt, kt, vt, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attention(q, k, v, tiles, interpret):
    return _forward(q, k, v, tiles, interpret)[0]


def _attention_fwd(q, k, v, tiles, interpret):
    o, res = _forward(q, k, v, tiles, interpret)
    return o, (o, *res)


def _attention_bwd(tiles, interpret, res, do):
    o, qt, kt, vt, lse = res
    B, S, H, d = o.shape
    KVH, s_pad = kt.shape[1], kt.shape[2]
    di = _rows(jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                       axis=-1), KVH, s_pad)
    dot = _to_kernel(do, s_pad, interpret)
    dk, dv = _dkv_call(qt, kt, vt, dot, lse, di, tiles.dkv, interpret)
    dq = _dq_call(qt, kt, vt, dot, lse, di, tiles.dq, interpret)
    return (_from_kernel(dq, S, o.dtype), _from_kernel(dk, S, o.dtype),
            _from_kernel(dv, S, o.dtype))


_attention.defvjp(_attention_fwd, _attention_bwd)


def flash_attention(q, k, v, *, tiles: FlashTiles | None = None,
                    interpret: bool = False):
    """Causal self-attention, differentiable.

    q: (B, S, H, d); k, v: (B, S, KVH, d), H a multiple of KVH; position i
    attends to positions 0..i.  Returns (B, S, H, d) in q's dtype.
    ``tiles`` overrides the shape-derived ``tiles.flash_tiles`` (tests and
    tile sweeps); ``interpret`` runs the Pallas interpreter.
    """
    B, S, H, d = q.shape
    if k.shape[1] != S or v.shape != k.shape or H % k.shape[2]:
        raise ValueError(f"causal self-attention needs q (B, S, H, d) and "
                         f"k = v (B, S, KVH, d) with KVH dividing H: "
                         f"{q.shape}, {k.shape}, {v.shape}")
    s_pad = -(-S // _LANES) * _LANES
    if tiles is None:
        tiles = flash_tiles(s_pad, d)
    if any(t % _LANES or s_pad % t for pair in tiles for t in pair):
        raise ValueError(f"flash tiles {tiles} must be multiples of "
                         f"{_LANES} that divide {s_pad}")
    return _attention(q, k, v, tiles, interpret)
