"""mfu.train: model FLOPs of the tokens trained in the traced window
(harness/flops.py: 6 x matrix-product weights + causal attention per
token, remat recompute not counted) over the window times the chip's
bf16 peak.  Moves train_tokens_per_s."""


def read(ctx):
    if not ctx.counters.get("flops") or ctx.trace.window_s <= 0:
        return None
    return 100.0 * ctx.counters["flops"] / (ctx.trace.window_s
                                            * ctx.peak["bf16_flops"])
