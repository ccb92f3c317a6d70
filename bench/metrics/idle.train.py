"""idle.train: the device's idle share of the traced training window.
Moves train_tokens_per_s."""

from harness.readers import idle_pct


def read(ctx):
    return idle_pct(ctx)
