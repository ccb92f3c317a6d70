"""Blocked MXU matmul Pallas kernel.

Tiling: C (M,N) is produced in (bm, bn) VMEM tiles; the K dimension is the
innermost grid axis so each (i, j) tile accumulates over K-steps into a VMEM
scratch accumulator in f32 (MXU-native accumulation), writing C once at the
final K step.  Tile sizes default to 128/256 multiples — MXU systolic array
alignment (128x128) and lane width (128) — and are clamped to the problem
(``tiles.block``).

Grid iteration order (k innermost) keeps the C tile resident in VMEM across
K steps: A and B tiles stream HBM->VMEM, C writes once — the standard
TPU matmul blocking (HBM traffic ~ MK + KN + MN instead of O(MNK/bk)).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiles import block


def _matmul_kernel(a_ref, b_ref, c_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        c_ref[...] = acc_ref[...].astype(c_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def matmul_pallas(a, b, *, bm: int = 256, bn: int = 256, bk: int = 512,
                  interpret: bool = False):
    """a: (M, K), b: (K, N) -> (M, N) in a.dtype; f32 accumulation."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    # a partial edge block in M or N only computes rows/columns that are
    # never written back; one in K would add garbage into the accumulator,
    # so K without such a divisor is taken whole
    bm, bn, bk = block(bm, M, 8), block(bn, N, 128), block(bk, K, 128)
    if K % bk:
        bk = K
    n_k = K // bk
    grid = (pl.cdiv(M, bm), pl.cdiv(N, bn), n_k)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, b)
