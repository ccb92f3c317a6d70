"""idle.serve: the device's idle share of the traced serving window.
Moves tpot_p95_ms."""

from harness.readers import idle_pct


def read(ctx):
    return idle_pct(ctx)
