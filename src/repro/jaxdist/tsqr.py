"""Communication-avoiding tall-skinny QR (CANDMC's panel kernel).

One-level CAQR over the 'row' axis: local householder QR of each row block,
all-gather of the p (n x n) R factors, redundant QR of the stacked (p·n, n)
matrix, and a local product to recover this block's slice of Q.  Wire
traffic is p·n² (the R stack) instead of the m·n a gather-based panel
factorization would move — the communication-avoiding trade the paper's
QR studies tune around.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def tsqr(a, mesh: Mesh, axis: str = "x"):
    """a: (m, n) with m row-sharded over ``axis`` (m % p == 0, m/p >= n).
    Returns (Q (m, n) row-sharded, R (n, n) replicated over ``axis``)."""
    p = mesh.shape[axis]
    n = a.shape[1]

    def body(al):
        al = al[0] if al.ndim == 3 else al       # (m/p, n)
        q1, r1 = jnp.linalg.qr(al, mode="reduced")
        stack = jax.lax.all_gather(r1, axis, axis=0, tiled=False)
        q2, r = jnp.linalg.qr(stack.reshape(p * n, n), mode="reduced")
        i = jax.lax.axis_index(axis)
        q2_mine = jax.lax.dynamic_slice_in_dim(q2, i * n, n, axis=0)
        q = q1 @ q2_mine
        return q, r

    other = [ax for ax in mesh.axis_names if ax != axis]
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=P(axis, None),
        out_specs=(P(axis, None), P(*[None] * 2)),
        check_vma=False)
    return fn(a)
