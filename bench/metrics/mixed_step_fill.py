"""Share of the mixed steps' positions that fed live tokens: the
positions the program fed (the ``fed`` argument of its mixed
``serve.iteration`` spans, which the engine's ``fed_positions`` counter
sums) over the positions the steps computed (``max_batch`` rows of
``prefill_chunk``).  The rest is padding that every linear computes
too.  Layer: scheduler."""

UNIT = "%"


def read(ctx):
    from bench import spans

    t0, t1 = ctx.window
    mixed = [s for s in spans.dispatches(spans.of(ctx).spans, t0, t1)
             if s.args.get("kind") == "mixed"]
    if not mixed:
        return None
    shape = ctx.serve["max_batch"] * ctx.serve["prefill_chunk"]
    return 100.0 * sum(s.args["fed"] for s in mixed) / (len(mixed) * shape)
