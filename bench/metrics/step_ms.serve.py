"""step_ms.serve: mean host-clock time of one ``Engine.step`` (admit and
prefill, one decode step, the logits pulled to the host and sampled) over
the engine steps of the traced window.  Moves tpot_p95_ms."""


def read(ctx):
    steps = ctx.counters.get("engine_steps", 0)
    if not steps:
        return None
    return 1e3 * ctx.counters["engine_step_s"] / steps
