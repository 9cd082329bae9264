"""Share of the traced burst in which no operation ran on the device:
one minus the union of device-operation intervals over the window.
Layer: device."""

UNIT = "%"


def read(ctx):
    from bench import tracing

    t0, t1 = ctx.window
    if t1 <= t0 or not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - tracing.busy(ctx.trace.ops, t0, t1) / (t1 - t0))
