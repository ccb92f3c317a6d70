"""Logical-axis sharding rules (t5x-style), the framework's single source of
sharding truth.

Model code annotates activations with *logical* axis names
(``annotate(x, 'batch', 'seq', 'embed')``); parameter initializers attach
logical axes per weight.  A ``ShardingRules`` table maps logical names to
mesh axes.  The mapping is what the autotuner tunes (DESIGN.md §4): rule
variants are points of the configuration space the paper's technique
searches.

Divisibility fallback: if a dimension is not divisible by the product of its
assigned mesh axes, trailing mesh axes are dropped until it is — so the same
rule table serves every (arch x shape) cell (e.g. ``long_500k``'s batch=1
simply loses its 'data' assignment instead of failing to lower).
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from dataclasses import dataclass, field, replace
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[None, str, Tuple[str, ...]]


@dataclass(frozen=True)
class ShardingRules:
    """logical axis name -> mesh axis (or tuple of mesh axes, major first)."""

    name: str
    table: Dict[str, MeshAxes]
    mesh: Optional[Mesh] = None

    def mesh_axes(self, logical: str) -> Tuple[str, ...]:
        v = self.table.get(logical)
        if v is None:
            return ()
        if isinstance(v, str):
            return (v,)
        return tuple(v)

    def spec(self, *logical: Optional[str],
             dims: Optional[Sequence[int]] = None) -> P:
        """PartitionSpec for a tensor whose dims carry the given logical
        names (None = replicated dim). ``dims`` enables the divisibility
        fallback; pass the concrete shape when available."""
        used = set()
        out = []
        for i, name in enumerate(logical):
            if name is None:
                out.append(None)
                continue
            axes = [a for a in self.mesh_axes(name) if a not in used]
            if dims is not None and self.mesh is not None:
                axes = _fit_axes(axes, int(dims[i]), self.mesh)
            if not axes:
                out.append(None)
            elif len(axes) == 1:
                out.append(axes[0])
                used.add(axes[0])
            else:
                out.append(tuple(axes))
                used.update(axes)
        while out and out[-1] is None:
            out.pop()
        return P(*out)

    def with_mesh(self, mesh: Mesh) -> "ShardingRules":
        # drop assignments to axes the mesh does not have (e.g. 'pod' on the
        # single-pod mesh)
        axis_names = set(mesh.axis_names)
        table = {}
        for k, v in self.table.items():
            axes = (v,) if isinstance(v, str) else tuple(v or ())
            axes = tuple(a for a in axes if a in axis_names)
            table[k] = axes if axes else None
        return ShardingRules(self.name, table, mesh)

    def override(self, **kw: MeshAxes) -> "ShardingRules":
        t = dict(self.table)
        t.update(kw)
        return ShardingRules(self.name, t, self.mesh)


def _fit_axes(axes, dim, mesh):
    """Drop trailing mesh axes until the dim is divisible by their product."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    while axes:
        prod = int(np.prod([sizes[a] for a in axes]))
        if prod and dim % prod == 0:
            return axes
        axes = axes[:-1]
    return []


# ---------------------------------------------------------------------------
# rule variants — the sharding dimension of the tuning space
# ---------------------------------------------------------------------------

def make_rules(variant: str = "cp") -> ShardingRules:
    """Build one of the named rule variants (mesh attached later).

    Logical axes used by the model code:
      batch, seq        activations (tokens)
      kv_seq            KV-cache sequence dim (decode)
      embed             d_model
      heads, kv_heads   attention heads
      ffn               feed-forward hidden
      inner             ssm/xlstm inner width
      vocab             embedding/output vocabulary
      expert            MoE expert dim
      lora              MLA latent dims
      fsdp_embed        weight d_model dim for FSDP sweeps
    """
    if variant == "cp":
        # context parallelism: activations sharded batch->data, seq->model;
        # weights Megatron-sharded on ffn/vocab/experts over model and
        # FSDP-sharded on embed over data.
        table = {
            "batch": ("pod", "data"), "seq": "model", "kv_seq": "model",
            "embed": None, "heads": None, "kv_heads": None,
            "ffn": "model", "inner": "model", "vocab": "model",
            "expert": "model", "lora": "data",
            "fsdp_embed": "data", "state": None,
            "tokens": ("pod", "data", "model"),
            "exp_cap": ("pod", "data"), "head_ff": "model",
            "heads_w": "model",
        }
    elif variant == "dp":
        # pure data parallelism (+FSDP weights): batch over everything.
        table = {
            "batch": ("pod", "data", "model"), "seq": None, "kv_seq": None,
            "embed": None, "heads": None, "kv_heads": None,
            "ffn": None, "inner": None, "vocab": None,
            "expert": None, "lora": ("data", "model"),
            "fsdp_embed": ("data", "model"), "state": None,
            "tokens": ("pod", "data", "model"),
            "exp_cap": ("pod", "data", "model"), "head_ff": None,
            "heads_w": None,
        }
    elif variant == "tp":
        # Megatron head-parallel attention + sharded ffn; batch->data only.
        # Arch-dependent: requires n_heads % model == 0 (fallback drops it).
        table = {
            "batch": ("pod", "data"), "seq": None, "kv_seq": None,
            "embed": None, "heads": "model", "kv_heads": "model",
            "ffn": "model", "inner": "model", "vocab": "model",
            "expert": "model", "lora": "data",
            "fsdp_embed": "data", "state": None,
            "tokens": ("pod", "data"),
            "exp_cap": ("pod", "data"), "head_ff": "model",
            "heads_w": "model",
        }
    elif variant == "cp_fsdp":
        # cp + aggressive FSDP: every weight embed dim sharded over data,
        # activations identical to cp.
        base = make_rules("cp").table
        table = dict(base)
        table["embed"] = None
        table["fsdp_embed"] = "data"
    else:
        raise ValueError(f"unknown sharding variant {variant!r}")
    return ShardingRules(variant, table)


RULE_VARIANTS = ("cp", "dp", "tp", "cp_fsdp")


# ---------------------------------------------------------------------------
# thread-local active rules + annotate()
# ---------------------------------------------------------------------------

_tls = threading.local()


def current_rules() -> Optional[ShardingRules]:
    return getattr(_tls, "rules", None)


@contextmanager
def axis_rules(rules: Optional[ShardingRules]):
    prev = getattr(_tls, "rules", None)
    _tls.rules = rules
    try:
        yield rules
    finally:
        _tls.rules = prev


def logical_spec(shape: Sequence[int], *logical: Optional[str]) -> P:
    """PartitionSpec under the active rules (empty spec when none active)."""
    rules = current_rules()
    if rules is None or rules.mesh is None:
        return P()
    return rules.spec(*logical, dims=shape)


def is_axes_leaf(x) -> bool:
    """True for a logical-axes tuple like ('layers', 'embed', None)."""
    return isinstance(x, tuple) and all(
        e is None or isinstance(e, str) for e in x)


def map_axes(fn, axes_tree, *trees):
    """tree_map where the axes tree's leaves are logical-axes tuples."""
    import jax as _jax
    return _jax.tree.map(fn, axes_tree, *trees, is_leaf=is_axes_leaf)


def annotate(x, *logical: Optional[str]):
    """with_sharding_constraint under the active rules; no-op otherwise.

    Model code is written against logical names only — this is the only
    function through which activation shardings enter the jaxpr.  Inside a
    partial-manual shard_map region (pipeline parallelism over 'pod') the
    constraint is resolved against the CONTEXT abstract mesh, whose manual
    axes must not appear in the spec (the pipeline strips them from its
    rule table).
    """
    rules = current_rules()
    if rules is None or rules.mesh is None:
        return x
    spec = rules.spec(*logical, dims=x.shape)
    ctx = jax.sharding.get_abstract_mesh()
    if ctx.manual_axes:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(ctx, spec))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, spec))
