"""Gradient compression collectives.

Two pieces:

- ``simulate_int8_roundtrip`` — blockwise int8 quantize/dequantize applied to
  already-reduced gradients.  Numerically identical to what a compressed
  wire format loses; used by the train step's ``grad_compression='int8'``
  flag and by the error-feedback wrapper.  Pure elementwise — lowers on any
  mesh.

- ``ring_allreduce_int8`` — an explicit shard_map ring reduce-scatter +
  all-gather whose wire payload is int8 blocks (+ f32 scales/block): the
  collective-bytes term of the roofline drops ~4x vs f32.  Requantization
  happens per hop (values are accumulated in f32, re-encoded to int8), which
  is the standard trade of compressed rings.  Used on the cross-pod axis.

- ``ErrorFeedback`` — residual accumulation so that compression error is
  re-injected next step (Karimireddy et al.); keeps convergence at int8.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

BLOCK = 256


def _pad_to(x, m):
    n = x.size
    pad = (-n) % m
    if pad:
        x = jnp.concatenate([x.reshape(-1), jnp.zeros((pad,), x.dtype)])
    return x.reshape(-1), pad


def quantize_int8(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, int]:
    """x (any shape) -> (q int8 (nb, BLOCK), scales f32 (nb,), pad)."""
    flat, pad = _pad_to(x.astype(jnp.float32), BLOCK)
    blocks = flat.reshape(-1, BLOCK)
    scale = jnp.max(jnp.abs(blocks), axis=1) / 127.0
    scale = jnp.maximum(scale, 1e-30)
    q = jnp.clip(jnp.round(blocks / scale[:, None]), -127, 127) \
        .astype(jnp.int8)
    return q, scale, pad


def dequantize_int8(q, scale, pad, shape, dtype):
    flat = (q.astype(jnp.float32) * scale[:, None]).reshape(-1)
    if pad:
        flat = flat[:-pad]
    return flat.reshape(shape).astype(dtype)


def simulate_int8_roundtrip(x: jnp.ndarray) -> jnp.ndarray:
    if x.ndim == 0:
        return x
    q, s, pad = quantize_int8(x)
    return dequantize_int8(q, s, pad, x.shape, x.dtype)


class ErrorFeedback:
    """e_{t+1} = g_t + e_t - C(g_t + e_t); apply returns C(g+e)."""

    @staticmethod
    def init(params):
        return jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)

    @staticmethod
    def apply(grads, residual):
        def one(g, e):
            tot = g.astype(jnp.float32) + e
            c = simulate_int8_roundtrip(tot)
            return c.astype(g.dtype), tot - c
        out = jax.tree.map(one, grads, residual)
        g2 = jax.tree.map(lambda t: t[0], out,
                          is_leaf=lambda t: isinstance(t, tuple))
        e2 = jax.tree.map(lambda t: t[1], out,
                          is_leaf=lambda t: isinstance(t, tuple))
        return g2, e2


# ---------------------------------------------------------------------------
# explicit compressed ring (shard_map) — cross-pod gradient reduction
# ---------------------------------------------------------------------------

def _dyn_row(a, i):
    return lax.dynamic_slice_in_dim(a, i, 1, axis=0)[0]


def _set_row(a, i, v):
    return lax.dynamic_update_slice_in_dim(a, v[None], i, axis=0)


def ring_allreduce_int8(stacked: jnp.ndarray, mesh: Mesh, axis: str):
    """All-reduce per-shard contributions over ``axis`` with int8 wire.

    ``stacked``: (n, m) where row i is shard i's contribution, sharded
    ``P(axis)``.  Returns (n, m) where every row equals the sum — i.e. the
    reduced gradient is available on every shard.  Ring reduce-scatter +
    ring all-gather; every hop's payload is int8 blocks + f32 scales
    (wire bytes ~ m/4 vs an f32 ring's m), requantizing partial sums per
    hop (the standard compressed-ring trade-off).
    """
    n = mesh.shape[axis]
    if n == 1:
        return stacked
    perm = [(i, (i + 1) % n) for i in range(n)]

    def ring(local):
        x = local[0]                                   # (m,) this shard
        flat, pad = _pad_to(x.astype(jnp.float32), BLOCK * n)
        chunks = flat.reshape(n, -1)                   # n ring chunks
        r = lax.axis_index(axis)

        # reduce-scatter: after n-1 hops rank r owns chunk (r+1) % n
        for i in range(n - 1):
            send_idx = (r - i) % n
            recv_idx = (r - i - 1) % n
            q, s, p = quantize_int8(_dyn_row(chunks, send_idx))
            q = lax.ppermute(q, axis, perm)
            s = lax.ppermute(s, axis, perm)
            recv = dequantize_int8(q, s, p, (chunks.shape[1],), jnp.float32)
            chunks = _set_row(chunks, recv_idx,
                              _dyn_row(chunks, recv_idx) + recv)
        own_idx = (r + 1) % n
        q, s, p = quantize_int8(_dyn_row(chunks, own_idx))
        own = dequantize_int8(q, s, p, (chunks.shape[1],), jnp.float32)

        # all-gather: circulate the owned chunk n-1 hops
        out = _set_row(jnp.zeros_like(chunks), own_idx, own)
        for i in range(n - 1):
            q = lax.ppermute(q, axis, perm)
            s = lax.ppermute(s, axis, perm)
            piece = dequantize_int8(q, s, p, (chunks.shape[1],), jnp.float32)
            arrived_owner = (r - i - 1) % n            # rank whose chunk this is
            out = _set_row(out, (arrived_owner + 1) % n, piece)
        flat_out = out.reshape(-1)
        if pad:
            flat_out = flat_out[:-pad]
        return flat_out.reshape(x.shape).astype(x.dtype)[None]

    other_none = [None] * (stacked.ndim - 1)
    return jax.shard_map(
        ring, mesh=mesh, in_specs=P(axis, *other_none),
        out_specs=P(axis, *other_none), check_vma=False)(stacked)
