"""90th percentile of the traced burst's queue waits: each request's
``RequestResult.queue_time`` (from its arrival coming due to its first
admission to a batch row), which the program stamps on its
``serve.admit`` span.  Layer: scheduler."""

UNIT = "s"


def read(ctx):
    from bench import spans, stats

    t0, t1 = ctx.window
    waits = [s.args["queue_s"] for s in spans.within(spans.of(ctx).spans, t0, t1,
                                                      "serve.admit")]
    return stats.percentile(waits, 90) if waits else None
