"""Inputs and weights made by the benchmark from ``--seed``, on the
device, one jitted call per tree.  The program and the reference are both
handed these arrays; neither makes them."""

from __future__ import annotations

import numpy as np


def seed32(seed: int, *salt: int) -> int:
    """A 31-bit seed folded from a seed of any size and some salt."""
    return int(np.random.SeedSequence([int(seed), *salt])
               .generate_state(1)[0] & 0x7FFFFFFF)


def random_like(shapes, seed: int, salt: int, *, scale_of=None):
    """A float32 tree shaped like ``shapes`` (ShapeDtypeStructs): normal
    draws scaled by ``scale_of(path, shape)`` (default 1/sqrt(fan-in),
    fan-in = the first dimension)."""
    import jax
    import jax.numpy as jnp

    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [jax.tree_util.keystr(p) for p, _ in leaves]
    shp = [tuple(l.shape) for _, l in leaves]
    scales = [(scale_of or _fan_in)(p, s) for p, s in zip(paths, shp)]

    @jax.jit
    def make(key):
        ks = jax.random.split(key, len(shp))
        return [jax.random.normal(k, s, jnp.float32) * c
                for k, s, c in zip(ks, shp, scales)]

    out = make(jax.random.PRNGKey(seed32(seed, salt)))
    return jax.tree_util.tree_unflatten(tree, out)


def _fan_in(path, shape):
    if len(shape) <= 1:
        return 0.1                 # norm gains around their zero start
    return 1.0 / np.sqrt(shape[0])


def _lm_scale(path, shape):
    """Init scale of one leaf of a dense LM, by its place in the
    program's parameter layout (``pos*`` leaves stacked over layers)."""
    if len(shape) == 1 or (path.startswith("['pos") and len(shape) == 2):
        return 0.1                                  # norm gains
    if "embed" in path:
        return 0.02
    if path.startswith("['pos"):
        shape = shape[1:]
        fan = shape[0] * shape[1] if "wo" in path else shape[0]
        return 1.0 / np.sqrt(fan)
    return 1.0 / np.sqrt(shape[0])


def lm_params(model, seed: int):
    """A dense LM's weights in the program's layout, from the seed."""
    return random_like(model.param_shapes(), seed, 30, scale_of=_lm_scale)
