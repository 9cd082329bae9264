"""Share of the serving loop's dispatches that were mixed steps (chunked
prefill plus single-token decodes, ``lm.paged_step``) rather than fused
decode runs: engine counters over the traced burst.  Layer: scheduler."""

UNIT = "%"


def read(ctx):
    steps = ctx.counters["step_calls"]
    if not steps:
        return None
    return 100.0 * (steps - ctx.counters["decode_run_calls"]) / steps
