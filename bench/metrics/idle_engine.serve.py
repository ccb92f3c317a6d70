"""idle_engine.serve: the device's idle share of the traced serving window
that the engine's own spans (``engine.*``: prefill, splice, first_token,
decode, sample, and the harness's ``engine.step`` around them) are
innermost over: the engine's host work the device waits on.  Read from
``self_idle_s`` (harness/idle_split.py).  Moves tpot_p95_ms."""

from harness.idle_split import idle_pct


def read(ctx):
    return idle_pct(ctx, ("engine.",))
