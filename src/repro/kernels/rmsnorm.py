"""Fused RMS-norm Pallas kernel.

One grid step normalizes a (rows_block, D) tile: mean-of-squares reduction,
rsqrt, scale by (1 + w) — all in one VMEM pass (the unfused jnp version
reads x three times from HBM; fused reads once, writes once).  D stays
whole in the lane dimension (norm axis must be resident); rows block to a
multiple of 8 (f32 sublane) to fill the VPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tiles import block


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    o_ref[...] = (y * (1.0 + w_ref[...].astype(jnp.float32))) \
        .astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm_pallas(x, w, *, eps: float = 1e-5, block_rows: int = 256,
                   interpret: bool = False):
    """x: (..., D); w: (D,)."""
    orig_shape = x.shape
    D = x.shape[-1]
    xf = x.reshape(-1, D)
    R = xf.shape[0]
    # rows are independent, so a partial last block is exact
    br = block(block_rows, R, 8)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(pl.cdiv(R, br),),
        in_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, D), x.dtype),
        interpret=interpret,
    )(xf, w)
    return out.reshape(orig_shape)
