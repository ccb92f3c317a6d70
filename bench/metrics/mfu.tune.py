"""mfu.tune: model FLOPs of every kernel the tuning campaigns executed in
the traced window (harness/flops.py, no recompute) over the window times
the chip's bf16 peak.  Moves tune_s_per_config."""

from harness.readers import kernel_mfu_pct


def read(ctx):
    return kernel_mfu_pct(ctx)
