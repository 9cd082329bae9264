"""Device milliseconds per fused decode iteration: the executions of the
decode-loop program (``lm.paged_decode_loop``) in the traced burst, over
the loop iterations they ran (the ``fused_tokens`` counter).  Layer:
model step."""

UNIT = "ms"


def read(ctx):
    steps = ctx.counters["fused_tokens"]
    if ctx.programs is None or not ctx.programs[1] or not steps:
        return None
    return 1e3 * sum(e - s for _, s, e in ctx.programs[1]) / steps
