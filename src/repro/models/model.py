"""Model assembly: parameters, forward/loss, prefill, decode — all archs.

One ``Model`` class consumes an ``ArchConfig`` and exposes:

  init(key)                          -> params pytree
  param_axes()                       -> same-structure tree of logical axes
  loss(params, batch)                -> scalar CE (+ MoE aux)
  forward(params, batch)             -> logits
  prefill(params, batch, s_max)      -> (last-step logits, cache, t)
  decode_step(params, cache, t, tok) -> (logits, cache)
  init_cache(batch, s_max)           -> cache pytree (+ cache_axes())

Layers are stacked over scan periods (leading ``n_periods`` dim) so the HLO
is depth-independent; within a period the (pattern, ffn_pattern) positions
are unrolled.  Sharding is injected only via logical-axis annotations.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ArchConfig
from repro.parallel.sharding import annotate
from . import layers as L
from . import moe as M
from . import ssm as S

PyTree = Any


@dataclass(frozen=True)
class ModelKnobs:
    """Step-function tuning parameters — the configuration space the
    paper's technique searches over for the LM framework (tune/)."""

    kv_chunk: int = 1024          # flash-attention KV chunk
    moe_dispatch: str = "a2a"     # 'a2a' | 'sort' | 'dense'
    ssm_chunk: int = 256          # mamba/xlstm chunk length
    remat: str = "full"           # 'none' | 'full' | 'dots'
    scan_unroll: int = 1          # lax.scan unroll over periods
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    logits_f32: bool = True


def _kind_params(cfg: ArchConfig, kind: str) -> Dict[str, tuple]:
    """(shape, logical_axes, init_scale) per weight of one mixer kind."""
    D, dh = cfg.d_model, cfg.head_dim
    H, KV = cfg.n_heads, cfg.n_kv_heads
    di = cfg.d_inner
    out: Dict[str, tuple] = {"ln": ((D,), ("embed",), 0.0)}
    if kind == "attn":
        out.update({
            "wq": ((D, H, dh), ("fsdp_embed", "heads_w", None), D),
            "wk": ((D, KV, dh), ("fsdp_embed", "heads_w", None), D),
            "wv": ((D, KV, dh), ("fsdp_embed", "heads_w", None), D),
            "wo": ((H, dh, D), ("heads_w", None, "fsdp_embed"), H * dh),
        })
    elif kind == "mla":
        m = cfg.mla
        out.update({
            "wq_a": ((D, m.q_lora), ("fsdp_embed", "lora"), D),
            "q_ln": ((m.q_lora,), ("lora",), 0.0),
            "wq_b": ((m.q_lora, H, m.d_nope + m.d_rope),
                     ("lora", "heads_w", None), m.q_lora),
            "wkv_a": ((D, m.kv_lora + m.d_rope), ("fsdp_embed", "lora"), D),
            "kv_ln": ((m.kv_lora,), ("lora",), 0.0),
            "wk_b": ((m.kv_lora, H, m.d_nope), ("lora", "heads_w", None),
                     m.kv_lora),
            "wv_b": ((m.kv_lora, H, m.d_v), ("lora", "heads_w", None),
                     m.kv_lora),
            "wo": ((H, m.d_v, D), ("heads_w", None, "fsdp_embed"),
                   H * m.d_v),
        })
    elif kind == "mamba":
        N, dtr = cfg.d_state, di // 16
        out.update({
            "in_proj": ((D, 2 * di), ("fsdp_embed", "inner"), D),
            "conv_w": ((cfg.d_conv, di), (None, "inner"), cfg.d_conv),
            "x_proj": ((di, dtr + 2 * N), ("inner", None), di),
            "dt_w": ((dtr, di), (None, "inner"), dtr),
            "dt_b": ((di,), ("inner",), 0.0),
            "a_log": ((di, N), ("inner", "state"), 0.0),
            "d": ((di,), ("inner",), 0.0),
            "out_proj": ((di, D), ("inner", "fsdp_embed"), di),
        })
    elif kind == "mlstm":
        nh = cfg.n_heads
        out.update({
            "up": ((D, 2 * di), ("fsdp_embed", "inner"), D),
            "conv_w": ((cfg.d_conv, di), (None, "inner"), cfg.d_conv),
            "wq": ((di, di), ("inner", None), di),
            "wk": ((di, di), ("inner", None), di),
            "wv": ((di, di), ("inner", None), di),
            "wif": ((di, 2 * nh), ("inner", None), di),
            "b_if": ((2 * nh,), (None,), 0.0),
            "down": ((di, D), ("inner", "fsdp_embed"), di),
        })
    elif kind == "slstm":
        nh = cfg.n_heads
        dh_s = D // nh
        out.update({
            "w": ((D, 4 * D), ("fsdp_embed", None), D),
            "r": ((nh, dh_s, 4 * dh_s), (None, None, None), dh_s),
            "b": ((4 * D,), (None,), 0.0),
            "up": ((D, 2 * di), ("fsdp_embed", "inner"), D),
            "down": ((di, D), ("inner", "fsdp_embed"), di),
        })
    else:
        raise ValueError(kind)
    return out


def _ffn_params(cfg: ArchConfig, fk: str) -> Dict[str, tuple]:
    D, F = cfg.d_model, cfg.d_ff
    out: Dict[str, tuple] = {}
    if fk == "dense":
        out.update({
            "ln": ((D,), ("embed",), 0.0),
            "w_gate": ((D, F), ("fsdp_embed", "ffn"), D),
            "w_up": ((D, F), ("fsdp_embed", "ffn"), D),
            "w_down": ((F, D), ("ffn", "fsdp_embed"), F),
        })
    elif fk == "moe":
        e = cfg.moe
        E, Fe = e.n_experts, e.d_ff_expert
        out.update({
            "ln": ((D,), ("embed",), 0.0),
            "router": ((D, E), ("fsdp_embed", None), D),
            "w_gate": ((E, D, Fe), ("expert", "fsdp_embed", "ffn"), D),
            "w_up": ((E, D, Fe), ("expert", "fsdp_embed", "ffn"), D),
            "w_down": ((E, Fe, D), ("expert", "ffn", "fsdp_embed"), Fe),
        })
        if e.n_shared:
            Fs = e.n_shared * Fe
            out.update({
                "sh_gate": ((D, Fs), ("fsdp_embed", "ffn"), D),
                "sh_up": ((D, Fs), ("fsdp_embed", "ffn"), D),
                "sh_down": ((Fs, D), ("ffn", "fsdp_embed"), Fs),
            })
    elif fk != "none":
        raise ValueError(fk)
    return out


def _spec_tree(cfg: ArchConfig) -> Dict[str, Dict[str, tuple]]:
    """Full (shape, axes, fan_in) spec tree.  Block weights get a leading
    n_periods stack dim with logical axis 'layers' (always replicated)."""
    D, V = cfg.d_model, cfg.vocab
    ncb = max(cfg.n_codebooks, 1)
    tree: Dict[str, Dict[str, tuple]] = {}
    emb_shape = (V, D) if ncb == 1 else (ncb, V, D)
    emb_axes = ("vocab", "fsdp_embed") if ncb == 1 else \
        (None, "vocab", "fsdp_embed")
    tree["embed"] = {"tok": (emb_shape, emb_axes, -1)}   # -1: embed init
    P_ = cfg.n_periods
    for i, (kind, fk) in enumerate(zip(cfg.pattern, cfg.ffn_pattern)):
        pos: Dict[str, tuple] = {}
        for nm, (shape, axes, fan) in _kind_params(cfg, kind).items():
            pos["mix_" + nm] = ((P_,) + shape, ("layers",) + axes, fan)
        for nm, (shape, axes, fan) in _ffn_params(cfg, fk).items():
            pos["ffn_" + nm] = ((P_,) + shape, ("layers",) + axes, fan)
        tree[f"pos{i}"] = pos
    tree["final"] = {"ln": ((D,), ("embed",), 0.0)}
    head_shape = (D, V) if ncb == 1 else (ncb, D, V)
    head_axes = ("fsdp_embed", "vocab") if ncb == 1 else \
        (None, "fsdp_embed", "vocab")
    if not cfg.tie_embeddings:
        tree["head"] = {"w": (head_shape, head_axes, D)}
    return tree


def init_params(cfg: ArchConfig, key, dtype=jnp.float32) -> PyTree:
    spec = _spec_tree(cfg)
    flat = []
    for g, sub in sorted(spec.items()):
        for nm in sorted(sub):
            flat.append((g, nm))
    keys = jax.random.split(key, len(flat))
    params: Dict[str, Dict[str, jnp.ndarray]] = {}
    for (g, nm), k in zip(flat, keys):
        shape, axes, fan = spec[g][nm]
        if nm.endswith("mix_d") or nm == "mix_d":
            w = jnp.ones(shape, dtype)           # mamba skip weight
        elif nm.endswith(("ln", "dt_b", "b_if", "_b")) or fan == 0.0:
            w = jnp.zeros(shape, dtype)
        elif fan == -1:
            w = (jax.random.normal(k, shape) * 0.02).astype(dtype)
        else:
            w = (jax.random.normal(k, shape) / math.sqrt(max(fan, 1))
                 ).astype(dtype)
        if nm.endswith("a_log"):
            # mamba: A init to -[1..N] per channel (S4D-real)
            N = shape[-1]
            w = jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)),
                shape).astype(dtype)
        if nm.endswith("dt_b"):
            w = jnp.full(shape, math.log(math.expm1(0.01)), dtype)
        params.setdefault(g, {})[nm] = w
    return params


def param_axes(cfg: ArchConfig) -> PyTree:
    spec = _spec_tree(cfg)
    return {g: {nm: axes for nm, (shape, axes, fan) in sub.items()}
            for g, sub in spec.items()}


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

class Model:
    def __init__(self, cfg: ArchConfig, knobs: ModelKnobs = ModelKnobs()):
        self.cfg = cfg
        self.knobs = knobs

    # -- params ---------------------------------------------------------------

    def init(self, key) -> PyTree:
        return init_params(self.cfg, key, self.knobs.param_dtype)

    def param_axes(self) -> PyTree:
        return param_axes(self.cfg)

    def param_shapes(self) -> PyTree:
        spec = _spec_tree(self.cfg)
        return {g: {nm: jax.ShapeDtypeStruct(shape, self.knobs.param_dtype)
                    for nm, (shape, axes, fan) in sub.items()}
                for g, sub in spec.items()}

    # -- embedding / head -------------------------------------------------------

    def _embed(self, params, batch):
        cfg = self.cfg
        cd = self.knobs.compute_dtype
        tok = batch["tokens"]
        table = params["embed"]["tok"].astype(cd)
        if cfg.n_codebooks:
            # (B,S,ncb) tokens; sum of per-codebook embeddings
            parts = [jnp.take(table[c], tok[..., c], axis=0)
                     for c in range(cfg.n_codebooks)]
            x = sum(parts)
        else:
            x = jnp.take(table, tok, axis=0)
        if cfg.n_patches and "patches" in batch:
            patches = batch["patches"].astype(cd)    # (B,P,D) stub frontend
            x = jnp.concatenate([patches, x], axis=1)
        return annotate(x, "batch", "seq", "embed")

    def _head(self, params, x):
        cfg = self.cfg
        table = params["head"]["w"] if "head" in params else None
        if self.knobs.logits_f32:
            x = x.astype(jnp.float32)
        if cfg.n_codebooks:
            w = table.astype(x.dtype)
            logits = jnp.einsum("bsd,cdv->bscv", x, w)
            return annotate(logits, "batch", "seq", None, "vocab")
        if table is None:   # tied
            w = params["embed"]["tok"].astype(x.dtype).T
        else:
            w = table.astype(x.dtype)
        logits = jnp.einsum("bsd,dv->bsv", x, w)
        return annotate(logits, "batch", "seq", "vocab")

    # -- full-sequence forward (train / prefill) --------------------------------

    def _stacked(self, params):
        return [params[f"pos{i}"] for i in range(self.cfg.period)]

    def _period_body_fwd(self, positions, with_cache):
        cfg, kn = self.cfg, self.knobs

        def body(x, per_period):
            caches = []
            for i, (kind, fk) in enumerate(zip(cfg.pattern, cfg.ffn_pattern)):
                p = {k[len("mix_"):]: v for k, v in per_period[i].items()
                     if k.startswith("mix_")}
                pf = {k[len("ffn_"):]: v for k, v in per_period[i].items()
                      if k.startswith("ffn_")}
                if kind == "attn":
                    h, c = L.attn_block(p, x, cfg, positions=positions,
                                        kv_chunk=kn.kv_chunk)
                elif kind == "mla":
                    h, c = L.mla_block(p, x, cfg, positions=positions,
                                       kv_chunk=kn.kv_chunk)
                elif kind == "mamba":
                    h, c = S.mamba_block(p, x, cfg, chunk=kn.ssm_chunk)
                elif kind == "mlstm":
                    h, c = S.mlstm_block(p, x, cfg, chunk=kn.ssm_chunk)
                else:
                    h, c = S.slstm_block(p, x, cfg, chunk=kn.ssm_chunk)
                x = x + h
                if fk == "dense":
                    x = x + L.ffn_block(pf, x, cfg)
                elif fk == "moe":
                    x = x + M.moe_ffn(pf, x, cfg, dispatch=kn.moe_dispatch)
                x = annotate(x, "batch", "seq", "embed")
                caches.append(c)
            return x, (tuple(caches) if with_cache else None)

        if kn.remat == "full":
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)
        elif kn.remat == "dots":
            body = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
        return body

    def _backbone(self, params, batch, *, with_cache=False):
        cfg, kn = self.cfg, self.knobs
        x = self._embed(params, batch)
        S_total = x.shape[1]
        positions = jnp.arange(S_total)
        body = self._period_body_fwd(positions, with_cache)
        stacked = self._stacked(params)
        x, caches = lax.scan(body, x, stacked, unroll=kn.scan_unroll)
        x = L.rms_norm(x, params["final"]["ln"], cfg.norm_eps)
        return x, caches

    def forward(self, params, batch, *, with_cache=False):
        x, caches = self._backbone(params, batch, with_cache=with_cache)
        logits = self._head(params, x)
        return (logits, caches) if with_cache else logits

    # -- loss -------------------------------------------------------------------

    def loss(self, params, batch):
        cfg = self.cfg
        logits = self.forward(params, batch)
        labels = batch["labels"]
        if cfg.n_patches:
            # labels align with the text tail of the concatenated sequence
            logits = logits[:, -labels.shape[1]:]
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        oh = jax.nn.one_hot(labels, cfg.vocab, dtype=logits.dtype)
        tgt = jnp.sum(logits * oh, axis=-1)
        ce = jnp.mean(lse - tgt)
        return ce

    # -- prefill / decode ---------------------------------------------------------

    def cache_axes(self) -> PyTree:
        """Logical axes for every cache leaf (matches init_cache structure)."""
        cfg = self.cfg
        axes = []
        for kind in cfg.pattern:
            if kind == "attn":
                a = ("layers", "batch", "kv_seq", "kv_heads", None)
                axes.append((a, a))
            elif kind == "mla":
                axes.append((("layers", "batch", "kv_seq", "lora"),
                             ("layers", "batch", "kv_seq", None)))
            elif kind == "mamba":
                axes.append((("layers", "batch", None, "inner"),
                             ("layers", "batch", "inner", "state")))
            elif kind == "mlstm":
                # C: (v-dim sharded, k-dim replicated); n tracks the k-dim
                # and stays replicated (see ssm.mlstm_block H1 note)
                axes.append((("layers", "batch", None, "inner"),
                             (("layers", "batch", None, "head_ff", None),
                              ("layers", "batch", None, None),
                              ("layers", "batch", None))))
            else:  # slstm
                axes.append((("layers", "batch", None),) * 3 +
                            (("layers", "batch", None),))
        return tuple(axes)

    def init_cache(self, batch_size: int, s_max: int) -> PyTree:
        cfg = self.cfg
        P_ = cfg.n_periods
        B = batch_size
        cd = self.knobs.compute_dtype
        di, N = cfg.d_inner, cfg.d_state
        out = []
        for kind in cfg.pattern:
            if kind == "attn":
                kv = (P_, B, s_max, cfg.n_kv_heads, cfg.head_dim)
                out.append((jnp.zeros(kv, cd), jnp.zeros(kv, cd)))
            elif kind == "mla":
                m = cfg.mla
                out.append((jnp.zeros((P_, B, s_max, m.kv_lora), cd),
                            jnp.zeros((P_, B, s_max, m.d_rope), cd)))
            elif kind == "mamba":
                out.append((jnp.zeros((P_, B, cfg.d_conv - 1, di), cd),
                            jnp.zeros((P_, B, di, N), jnp.float32)))
            elif kind == "mlstm":
                nh = cfg.n_heads
                dh = di // nh
                out.append((
                    jnp.zeros((P_, B, cfg.d_conv - 1, di), cd),
                    (jnp.zeros((P_, B, nh, dh, dh), jnp.float32),
                     jnp.zeros((P_, B, nh, dh), jnp.float32),
                     jnp.full((P_, B, nh), -1e30, jnp.float32))))
            else:  # slstm
                D = cfg.d_model
                nh = cfg.n_heads
                out.append((jnp.zeros((P_, B, D), jnp.float32),
                            jnp.zeros((P_, B, D), jnp.float32),
                            jnp.zeros((P_, B, D), jnp.float32),
                            jnp.full((P_, B, nh), -1e30, jnp.float32)))
        return tuple(out)

    def decode_step(self, params, cache, t, batch):
        """One new token.  batch['tokens']: (B,1) [or (B,1,ncb)]; ``t``: the
        position written, scalar or per slot (B,), below s_max.
        Returns (logits (B, V[, ncb->(B,ncb,V)]), new cache).

        Attention and MLA caches travel whole in the layer scan's carry:
        each layer attends over its cache before ``t`` and the new token,
        then writes the new row per slot (``L.cache_update``), so jitted
        with the cache donated, a step writes B rows a layer in place.
        Where the active rules shard ``kv_seq`` the write is a one-hot
        blend, which rewrites each layer's cache.  SSM states are small and
        travel as the scan's xs/ys, rewritten whole."""
        cfg, kn = self.cfg, self.knobs
        x = self._embed(params, batch)           # (B,1,D)
        x = annotate(x, "batch", None, "embed")
        s_max = self._cache_smax(cache)
        kv_positions = jnp.arange(s_max)
        keyed = [kind in ("attn", "mla") for kind in cfg.pattern]
        kv = tuple(c if k else None for c, k in zip(cache, keyed))
        states = tuple(None if k else c for c, k in zip(cache, keyed))

        def body(carry, per):
            x, kv = carry
            per_period, state_in, layer = per
            kv, new_states = list(kv), []
            for i, (kind, fk) in enumerate(zip(cfg.pattern, cfg.ffn_pattern)):
                p = {k[len("mix_"):]: v for k, v in per_period[i].items()
                     if k.startswith("mix_")}
                pf = {k[len("ffn_"):]: v for k, v in per_period[i].items()
                      if k.startswith("ffn_")}
                c = state_in[i]
                if kind == "attn":
                    h, kv[i] = L.attn_decode(p, x, kv[i], cfg, t=t,
                                             layer=layer,
                                             kv_positions=kv_positions)
                elif kind == "mla":
                    h, kv[i] = L.mla_decode(p, x, kv[i], cfg, t=t,
                                            layer=layer,
                                            kv_positions=kv_positions)
                elif kind == "mamba":
                    h, c = S.mamba_block(
                        p, x, cfg, chunk=1, conv_state=c[0], ssm_state=c[1])
                elif kind == "mlstm":
                    h, c = S.mlstm_block(
                        p, x, cfg, chunk=1, conv_state=c[0], state=c[1])
                else:
                    h, c = S.slstm_block(p, x, cfg, chunk=1, state=c)
                x = x + h
                if fk == "dense":
                    x = x + L.ffn_block(pf, x, cfg)
                elif fk == "moe":
                    x = x + M.moe_ffn(pf, x, cfg, dispatch=kn.moe_dispatch)
                new_states.append(c)
            return (x, tuple(kv)), tuple(new_states)

        stacked = self._stacked(params)
        (x, kv), states = lax.scan(
            body, (x, kv), (stacked, states, jnp.arange(cfg.n_periods)),
            unroll=kn.scan_unroll)
        x = L.rms_norm(x, params["final"]["ln"], cfg.norm_eps)
        logits = self._head(params, x)
        return logits[:, 0], tuple(c if k else st
                                   for c, st, k in zip(kv, states, keyed))

    def kv_write_in_place(self, batch_size: int, s_max: int) -> bool:
        """Whether ``decode_step`` under the active rules writes the
        attention caches a row per slot (True) or blends them (False)."""
        shapes = jax.eval_shape(lambda: self.init_cache(batch_size, s_max))
        return not any(L.kv_seq_sharded(c[0].shape)
                       for kind, c in zip(self.cfg.pattern, shapes)
                       if kind in ("attn", "mla"))

    def _cache_smax(self, cache):
        for kind, c in zip(self.cfg.pattern, cache):
            if kind in ("attn", "mla"):
                return c[0].shape[2]
        return 0

    def prefill(self, params, batch, s_max: int, logits_at=None):
        """Run the full prompt, build an s_max-capacity cache.

        ``logits_at``: optional (B,) positions of each row's true prompt end
        (right-padded batches); default = last position.  Returns
        (logits (B, V[...]) at those positions, cache, t=prompt_len)."""
        cfg = self.cfg
        x, caches = self._backbone(params, batch, with_cache=True)
        B, S_prompt = x.shape[0], x.shape[1]
        if logits_at is None:
            x_last = x[:, -1:]
        else:
            x_last = jnp.take_along_axis(
                x, logits_at.astype(jnp.int32)[:, None, None], axis=1)
        logits = self._head(params, x_last)[:, 0]
        out = []
        for i, kind in enumerate(cfg.pattern):
            c = caches[i]
            if kind in ("attn", "mla"):
                k, v = c
                out.append((self._pad_cache(k, s_max),
                            self._pad_cache(v, s_max)))
            else:
                out.append(c)
        return logits, tuple(out), S_prompt

    @staticmethod
    def _pad_cache(x, s_max):
        # x: (P_, B, S, ...) -> (P_, B, s_max, ...)
        pad = s_max - x.shape[2]
        if pad <= 0:
            return x
        cfgpad = [(0, 0)] * x.ndim
        cfgpad[2] = (0, pad)
        return jnp.pad(x, cfgpad)
