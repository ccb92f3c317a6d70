"""Arithmetic shared by the per-layer metrics' readers (bench/metrics/).
Each returns None where the trace holds nothing to read."""

from __future__ import annotations

from . import flops as F


def kernel_spans(ctx, kind=None):
    """(count, device_s, FLOPs, bytes) summed over the traced kernel spans
    of one signature kind (``attn_fb``, ``dense_fb``, ...), or of all."""
    n = dev = fl = by = 0.0
    prefix = "kernel " if kind is None else f"kernel comp:{kind}("
    for name, rec in ctx.trace.spans.items():
        cost = ctx.kernel_costs.get(name)
        if not name.startswith(prefix) or cost is None:
            continue
        n += rec["count"]
        dev += rec["device_s"]
        fl += rec["count"] * cost[0]
        by += rec["count"] * cost[1]
    return n, dev, fl, by


def roofline_pct(ctx, kind):
    n, dev, fl, by = kernel_spans(ctx, kind)
    if n == 0 or dev <= 0:
        return None
    return 100.0 * F.roofline_s(fl, by, ctx.peak) / dev


def kernel_mfu_pct(ctx):
    n, dev, fl, by = kernel_spans(ctx)
    if n == 0 or ctx.trace.window_s <= 0:
        return None
    return 100.0 * fl / (ctx.trace.window_s * ctx.peak["bf16_flops"])


def idle_pct(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * ctx.trace.idle_share
