"""idle_session.tune: the device's idle share of the traced tuning window
that the session's host spans are innermost over (``tuner.kernels_of``:
a point's kernel list built; ``tuner.bookkeeping``: records, statistics
resets and the session's work around the search driver).  Read from
``self_idle_s`` (harness/idle_split.py).  Moves tune_s_per_config."""

from harness.idle_split import idle_pct


def read(ctx):
    return idle_pct(ctx, ("tuner.kernels_of", "tuner.bookkeeping"))
