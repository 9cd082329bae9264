"""Device milliseconds per mixed step: the executions of the mixed-step
program (``lm.paged_step``) in the traced burst, over how many ran.
Layer: model step."""

UNIT = "ms"


def read(ctx):
    if ctx.programs is None or not ctx.programs[0]:
        return None
    mixed = ctx.programs[0]
    return 1e3 * sum(e - s for _, s, e in mixed) / len(mixed)
