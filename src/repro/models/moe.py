"""Mixture-of-experts FFN: expert-parallel all-to-all dispatch (default),
sort-based local dispatch, and a dense decoy.

The dispatch implementation is a *tuning parameter* of the step function
(DESIGN.md §7: the dispatch alternative is the configuration knob most
representative of the paper's competing-analytic-costs setting):

- ``a2a``   (default under a mesh): shard_map expert parallelism.  Three
  regimes picked from the active sharding rules:
    * tokens sharded over the expert axis  -> ring all_to_all dispatch
      (tokens travel to their experts' shard, GShard/Switch EP);
    * tokens replicated over the expert axis -> masked local experts +
      psum combine (decode-friendly EP);
    * experts unsharded -> purely local sort dispatch per token shard.
  Expert weights FSDP-sharded over token axes are all-gathered per layer
  inside the body (ZeRO-3 semantics) and re-gathered in backward.
  Falls back to ``sort`` when no mesh/rules are active (CPU smoke tests).
- ``sort``: global-program argsort/capacity dispatch.  Correct everywhere,
  but under SPMD its data-dependent gather/scatter replicates — kept as the
  naive baseline arm the autotuner must learn to reject.
- ``dense``: every expert on every token (tiny configs only).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import annotate, current_rules


def router_topk(logits, k: int, *, renormalize: bool = True):
    """logits (T, E) f32 -> (gates (T,k), idx (T,k))."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, idx = lax.top_k(probs, k)
    if renormalize:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates, idx


def _expert_ffn_local(p, x):
    """x: (E, C, D) -> (E, C, D) per-expert gated MLP; no constraints
    (usable inside shard_map manual regions)."""
    g = jnp.einsum("ecd,edf->ecf", x, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", x, p["w_up"])
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, p["w_down"])


def _expert_ffn(p, x):
    """Global-program variant with logical-axis constraints."""
    g = jnp.einsum("ecd,edf->ecf", x, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", x, p["w_up"])
    g = annotate(g, "expert", "exp_cap", "ffn")
    u = annotate(u, "expert", "exp_cap", "ffn")
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, p["w_down"])


def moe_ffn(p, x, cfg, *, dispatch: str = "a2a"):
    """x: (B, S, D) -> (B, S, D).  p holds router (D,E), expert stacks
    (E,D,F)/(E,F,D), and optionally shared-expert dense weights."""
    moe = cfg.moe
    B, S, D = x.shape
    T = B * S
    xf = x.reshape(T, D)
    xf = annotate(xf, "tokens", "embed")

    if dispatch == "a2a":
        rules = current_rules()
        if rules is None or rules.mesh is None:
            dispatch = "sort"
        else:
            y = _ep_dispatch(p, xf, moe, rules)
            dispatch = None

    if dispatch is not None:
        logits = jnp.einsum("td,de->te", xf, p["router"]) \
            .astype(jnp.float32)
        gates, idx = router_topk(logits, moe.top_k)
        gates = gates.astype(x.dtype)
        if dispatch == "dense":
            h = _expert_ffn({k: p[k] for k in ("w_gate", "w_up", "w_down")},
                            jnp.broadcast_to(xf[None],
                                             (moe.n_experts, T, D)))
            gate_mat = jnp.zeros((T, moe.n_experts), x.dtype)
            gate_mat = gate_mat.at[jnp.arange(T)[:, None], idx].add(gates)
            y = jnp.einsum("etd,te->td", h, gate_mat)
        elif dispatch == "sort":
            y = _sort_dispatch(p, xf, gates, idx, moe)
        else:
            raise ValueError(f"unknown moe dispatch {dispatch!r}")

    if moe.n_shared:
        sh = {"ln": None, "w_gate": p["sh_gate"], "w_up": p["sh_up"],
              "w_down": p["sh_down"]}
        g = jnp.einsum("td,df->tf", xf, sh["w_gate"])
        u = jnp.einsum("td,df->tf", xf, sh["w_up"])
        y = y + jnp.einsum("tf,fd->td", jax.nn.silu(g) * u, sh["w_down"])
    y = annotate(y, "tokens", "embed")
    return y.reshape(B, S, D)


# ---------------------------------------------------------------------------
# expert-parallel shard_map dispatch
# ---------------------------------------------------------------------------

def _axes_tuple(v):
    if v is None:
        return ()
    return (v,) if isinstance(v, str) else tuple(v)


def _gather_weight(w, spec_axes, skip_axis):
    """all-gather weight dims FSDP-sharded over mapped axes (ZeRO-3).
    Minor axis first: a dim sharded (major, minor) reconstructs contiguously
    only when gathered minor-to-major."""
    for dim, axs in enumerate(spec_axes):
        for ax in reversed(_axes_tuple(axs)):
            if ax and ax != skip_axis:
                w = lax.all_gather(w, ax, axis=dim, tiled=True)
    return w


def _capacity(t_loc: int, k: int, n_exp: int, cf: float) -> int:
    c = int(math.ceil(t_loc * k / n_exp * cf))
    return max(8 * ((c + 7) // 8), 8)


def _local_pack(xl, gates, idx, n_exp, cap):
    """Sort local tokens into an (n_exp, cap, D) buffer.

    Returns (buffer, slot (T_loc*k,), src_token (T_loc*k,), gate, keep)."""
    t_loc, d = xl.shape
    k = idx.shape[-1]
    tk = t_loc * k
    fidx = idx.reshape(tk)
    fgate = gates.reshape(tk)
    ftok = jnp.arange(tk, dtype=jnp.int32) // k
    order = jnp.argsort(fidx)
    se, st, sg = fidx[order], ftok[order], fgate[order]
    counts = jnp.bincount(fidx, length=n_exp)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(tk, dtype=jnp.int32) - starts[se].astype(jnp.int32)
    keep = pos < cap
    slot = jnp.where(keep, se.astype(jnp.int32) * cap + pos, tk + n_exp * cap)
    buf = jnp.zeros((n_exp * cap, d), xl.dtype)
    buf = buf.at[slot].set(xl[st] * keep[:, None].astype(xl.dtype),
                           mode="drop")
    return buf.reshape(n_exp, cap, d), slot, st, sg, keep


def _local_combine(y_slots, slot, st, sg, keep, t_loc):
    """Inverse of _local_pack: gather expert outputs back, gate-combine."""
    d = y_slots.shape[-1]
    flat = y_slots.reshape(-1, d)
    y_tok = jnp.take(flat, jnp.where(keep, slot, 0), axis=0)
    y_tok = y_tok * (keep * sg).astype(y_tok.dtype)[:, None]
    return jnp.zeros((t_loc, d), y_slots.dtype).at[st].add(y_tok)


def _ep_dispatch(p, xf, moe, rules):
    """shard_map expert parallelism (see module docstring for regimes)."""
    mesh = rules.mesh
    T, D = xf.shape
    E, k, cf = moe.n_experts, moe.top_k, moe.capacity_factor

    tok_spec = rules.spec("tokens", None, dims=(T, D))
    tok_axes = _axes_tuple(tok_spec[0] if len(tok_spec) else None)
    w_shape = p["w_gate"].shape                      # (E, D, F)
    w_spec = rules.spec("expert", "fsdp_embed", "ffn", dims=w_shape)
    exp_axes = _axes_tuple(w_spec[0] if len(w_spec) else None)
    assert len(exp_axes) <= 1, exp_axes
    exp_ax = exp_axes[0] if exp_axes else None
    n_ep = mesh.shape[exp_ax] if exp_ax else 1
    e_loc = E // n_ep
    t_loc = T
    for ax in tok_axes:
        t_loc //= mesh.shape[ax]
    cap = _capacity(t_loc, k, E, cf)

    w_specs = {nm: rules.spec("expert", "fsdp_embed", "ffn",
                              dims=p[nm].shape)
               for nm in ("w_gate", "w_up", "w_down")}
    # w_down is (E, F, D): recompute with the right logical order
    w_specs["w_down"] = rules.spec("expert", "ffn", "fsdp_embed",
                                   dims=p["w_down"].shape)

    def body(xl, router, wg, wu, wd):
        wg = _gather_weight(wg, w_specs["w_gate"], exp_ax)
        wu = _gather_weight(wu, w_specs["w_up"], exp_ax)
        wd = _gather_weight(wd, w_specs["w_down"], exp_ax)
        logits = (xl @ router).astype(jnp.float32)
        gates, idx = router_topk(logits, k)
        gates = gates.astype(xl.dtype)

        if exp_ax is None:
            # experts fully local
            buf, slot, st, sg, keep = _local_pack(xl, gates, idx, E, cap)
            ye = _expert_ffn_local(
                {"w_gate": wg, "w_up": wu, "w_down": wd}, buf)
            return _local_combine(ye, slot, st, sg, keep, xl.shape[0])

        if exp_ax in tok_axes:
            # ring all_to_all: tokens travel to their experts' shard
            buf, slot, st, sg, keep = _local_pack(xl, gates, idx, E, cap)
            send = buf.reshape(n_ep, e_loc * cap, D)
            recv = lax.all_to_all(send, exp_ax, split_axis=0,
                                  concat_axis=0, tiled=False)
            he = recv.reshape(n_ep, e_loc, cap, D) \
                .transpose(1, 0, 2, 3).reshape(e_loc, n_ep * cap, D)
            ye = _expert_ffn_local(
                {"w_gate": wg, "w_up": wu, "w_down": wd}, he)
            back = ye.reshape(e_loc, n_ep, cap, D) \
                .transpose(1, 0, 2, 3).reshape(n_ep, e_loc * cap, D)
            ret = lax.all_to_all(back, exp_ax, split_axis=0,
                                 concat_axis=0, tiled=False)
            return _local_combine(ret.reshape(E * cap, D), slot, st, sg,
                                  keep, xl.shape[0])

        # tokens replicated over the expert axis: mask to local experts,
        # compute partial outputs, psum-combine
        m_idx = lax.axis_index(exp_ax)
        lo = m_idx * e_loc
        local = (idx >= lo) & (idx < lo + e_loc)
        idx_l = jnp.where(local, idx - lo, e_loc)       # e_loc = overflow
        gates_l = jnp.where(local, gates, 0.0).astype(xl.dtype)
        cap_l = _capacity(xl.shape[0], k, e_loc, cf)
        buf, slot, st, sg, keep = _local_pack(
            xl, gates_l, idx_l, e_loc + 1, cap_l)
        ye = _expert_ffn_local(
            {"w_gate": jnp.concatenate(
                [wg, jnp.zeros((1,) + wg.shape[1:], wg.dtype)]),
             "w_up": jnp.concatenate(
                 [wu, jnp.zeros((1,) + wu.shape[1:], wu.dtype)]),
             "w_down": jnp.concatenate(
                 [wd, jnp.zeros((1,) + wd.shape[1:], wd.dtype)])}, buf)
        y = _local_combine(ye, slot, st, sg, keep, xl.shape[0])
        return lax.psum(y, exp_ax)

    in_specs = (tok_spec, P(None, None),
                w_specs["w_gate"], w_specs["w_up"], w_specs["w_down"])
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=tok_spec, check_vma=False)
    return fn(xf, p["router"].astype(xf.dtype), p["w_gate"], p["w_up"],
              p["w_down"])


def _sort_dispatch(p, xf, gates, idx, moe):
    T, D = xf.shape
    E, k = moe.n_experts, moe.top_k
    Tk = T * k
    cap = int(max(1, round(Tk / E * moe.capacity_factor)))
    # pad capacity to a multiple of 256 for layout friendliness
    cap = -(-cap // 256) * 256 if Tk >= 256 else cap

    fidx = idx.reshape(Tk)
    fgate = gates.reshape(Tk)
    ftok = jnp.arange(Tk, dtype=jnp.int32) // k
    order = jnp.argsort(fidx)
    se, st, sg = fidx[order], ftok[order], fgate[order]
    # position within expert: running index minus expert segment start
    counts = jnp.bincount(fidx, length=E)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos_in_e = jnp.arange(Tk, dtype=jnp.int32) - starts[se].astype(jnp.int32)
    keep = pos_in_e < cap
    slot = jnp.where(keep, se.astype(jnp.int32) * cap + pos_in_e, Tk + E * cap)

    gathered = jnp.zeros((E * cap, D), xf.dtype)
    gathered = gathered.at[slot].set(
        xf[st] * keep[:, None].astype(xf.dtype), mode="drop")
    he = gathered.reshape(E, cap, D)
    he = annotate(he, "expert", "exp_cap", "embed")
    ye = _expert_ffn(p, he)
    ye = annotate(ye, "expert", "exp_cap", "embed")
    y_slots = ye.reshape(E * cap, D)
    y_tok = jnp.take(y_slots, jnp.where(keep, slot, 0), axis=0)
    y_tok = y_tok * (keep[:, None] * sg[:, None]).astype(y_tok.dtype)
    y = jnp.zeros((T, D), xf.dtype).at[st].add(y_tok)
    return y
