"""Host milliseconds per dispatch: the mean, over the traced burst's
``serve.iteration`` spans that dispatched, of the iteration's time
outside its blocking reads (``serve.wait``): planning, building the
device arguments and dispatching, sampling, committing.  Layer: host
loop."""

UNIT = "ms"


def read(ctx):
    from bench import spans

    t0, t1 = ctx.window
    sp = spans.of(ctx).spans
    its = spans.dispatches(sp, t0, t1)
    if not its:
        return None
    return 1e3 * sum(spans.host_seconds(sp, it) for it in its) / len(its)
