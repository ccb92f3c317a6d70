"""admit_ms.serve: host time to admit one request, mean over the traced
window's admissions: the ``engine.prefill`` span (the batch-1 prefill
called), ``engine.splice`` (its cache written into the batch cache) and
``engine.first_token`` (its logits pulled to the host, the first token
sampled).  Moves ttft_p95_ms."""

PARTS = ("engine.prefill", "engine.splice", "engine.first_token")


def read(ctx):
    spans = ctx.trace.spans
    prefill = spans.get("engine.prefill")
    if not prefill or not prefill["count"]:
        return None
    wall = sum(spans[n]["wall_s"] for n in PARTS if n in spans)
    return 1e3 * wall / prefill["count"]
