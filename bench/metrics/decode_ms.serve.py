"""decode_ms.serve: device time inside one ``engine.decode`` span (the
decode call and its logits pulled to the host), mean over the traced
window's decode steps.  Moves tpot_p95_ms."""


def read(ctx):
    rec = ctx.trace.spans.get("engine.decode")
    if not rec or not rec["count"]:
        return None
    return 1e3 * rec["device_s"] / rec["count"]
