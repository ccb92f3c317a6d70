"""idle_stats.tune: the device's idle share of the traced tuning window
that ``SelectiveTimer``'s statistics spans are innermost over
(``tuner.decide``: the skip decision before a kernel; ``tuner.update``:
the statistics update after it).  Read from ``self_idle_s``
(harness/idle_split.py).  Moves tune_s_per_config."""

from harness.idle_split import idle_pct


def read(ctx):
    return idle_pct(ctx, ("tuner.decide", "tuner.update"))
