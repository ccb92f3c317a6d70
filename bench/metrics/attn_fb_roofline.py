"""attn_fb_roofline: the tuner's attn_fb kernels' share of their roofline.

The FLOPs and bytes each executed attn_fb call needs (harness/flops.py, from
its shapes: no recompute, causal attention's lower triangle only), as the
least time the chip's peaks allow, over the device time inside those
calls' host spans, in the traced window.  Moves tune_s_per_config.
"""

from harness.readers import roofline_pct


def read(ctx):
    return roofline_pct(ctx, "attn_fb")
