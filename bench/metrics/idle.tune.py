"""idle.tune: the device's idle share of the traced tuning window, 1 -
(union of device op intervals) / window.  Moves tune_s_per_config."""

from harness.readers import idle_pct


def read(ctx):
    return idle_pct(ctx)
