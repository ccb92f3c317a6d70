"""FLOPs and HBM bytes that a call *needs*, from its shapes alone.

Matrix products count 2 FLOPs per multiply-add.  A forward and backward
pass (gradients of the weights, and of the activations they need) counts
three times the forward's products, and nothing that an implementation
recomputes (``remat``) or computes and masks away: causal attention needs
the lower triangle of its scores only.  Bytes are the least a call moves
through HBM: its inputs read once and its outputs written once, at 4
bytes an element for the float32 the configurations state.  The roofline
time of a call is max(FLOPs / peak FLOP/s, bytes / peak bytes/s).
"""

from __future__ import annotations

F32 = 4


def attn_proj_fwd(tokens, d, h, kv, dh):
    """q, k, v and output projections of one GQA block."""
    return 2.0 * tokens * d * dh * (2 * h + 2 * kv)


def attn_scores_fwd(b, s, h, dh, causal=True):
    """QK^T and PV over (b, h, s, s); causal needs the lower triangle,
    diagonal included."""
    pairs = s * (s + 1) / 2 if causal else s * s
    return 2.0 * 2.0 * b * h * pairs * dh


def attn_fb(b, s, d, h, kv, dh, causal=True):
    """FLOPs and bytes of the tuner's attention kernel: gradient of the
    block's weights (ln, wq, wk, wv, wo) for a (b, s, d) input."""
    fwd = attn_proj_fwd(b * s, d, h, kv, dh) + attn_scores_fwd(
        b, s, h, dh, causal)
    weights = d + d * dh * (2 * h + 2 * kv)
    return 3.0 * fwd, F32 * (2 * weights + b * s * d)


def dense_fb(b, s, d, f):
    """SwiGLU FFN (gate, up, down) fwd+bwd for the weights' gradient."""
    weights = d + 3 * d * f
    return 3.0 * 2.0 * b * s * d * f * 3, F32 * (2 * weights + b * s * d)


def embed_loss_fb(b, s, d, v, tied):
    """Embedding, final norm and logits over v rows, fwd+bwd."""
    tables = v * d * (1 if tied else 2) + d
    return 3.0 * 2.0 * b * s * d * v, F32 * (2 * tables + 2 * b * s)


def adamw(n_params):
    """AdamW reads p, g, m, v and writes p, m, v; its elementwise FLOPs
    are not model FLOPs and count 0."""
    return 0.0, F32 * 7.0 * n_params


def train_flops_per_token(cfg: dict, seq: int, causal=True) -> float:
    """Model FLOPs per trained token of a dense decoder (fwd + bwd, no
    recompute): 6 x the matrix-product weights (the head included, the
    embedding gather not) plus causal attention's scores."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, layers, v = d // h, cfg["num_hidden_layers"], cfg["vocab_size"]
    per_layer = d * dh * (2 * h + 2 * kv) + 3 * d * f
    scores = attn_scores_fwd(1, seq, h, dh, causal) / seq
    return 6.0 * (layers * per_layer + d * v) + 3.0 * layers * scores


def roofline_s(flops, nbytes, peak):
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
