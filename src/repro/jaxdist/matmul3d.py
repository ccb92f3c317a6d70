"""3D matrix multiplication (Agarwal et al. / ACS) in shard_map.

C(x,y) = sum_z A(x,z) . B(z,y): each of the p^{1/3} 'z' layers computes a
rank-K/p^{1/3} partial product from its A column-block and B row-block; the
reduction over 'z' is the single psum — broadcast-free because the inputs
are *distributed* over (x,z)/(z,y) planes rather than replicated.  This is
exactly the product kernel of Capital's Cholesky (paper §V.A): "broadcasts
along two dimensions of the processor grid, and a reduction along the
third" — in the shard_map formulation the broadcasts become the implicit
resharding of the operands' layouts.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, PartitionSpec as P


def make_3d_mesh(shape: Tuple[int, int, int]) -> Mesh:
    """Mesh with axes (x, y, z) of the given shape over the first
    prod(shape) devices.  A 4-chip host takes (2, 1, 2): the 'z' reduction
    of ``matmul_3d`` then crosses chips."""
    return jax.make_mesh(shape, ("x", "y", "z"),
                         axis_types=(AxisType.Auto,) * 3,
                         devices=jax.devices()[:math.prod(shape)])


def matmul_3d(a, b, mesh: Mesh):
    """a: (M, K) laid out P('x', 'z'); b: (K, N) laid out P('z', 'y');
    returns c: (M, N) laid out P('x', 'y') (replicated over z)."""

    def body(al, bl):
        c_part = jnp.dot(al, bl, preferred_element_type=jnp.float32)
        return jax.lax.psum(c_part, "z").astype(al.dtype)

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P("x", "z"), P("z", "y")),
                       out_specs=P("x", "y"), check_vma=False)
    return fn(a, b)
