"""Whole-step utilization: the model operations of every position the
traced burst ran (``counts.request_work``: dense-shaped linears plus
attention over the live context) over the burst's length and the chip's
int8 peak.  The served linears are int8 x int8 -> int32; attention,
which runs in bfloat16, is counted against the same higher peak, so the
share cannot pass 100%.  Layer: model step."""

UNIT = "%"


def read(ctx):
    if not ctx.work["ops"] or not ctx.peaks or not ctx.trace.ops:
        return None
    return 100.0 * ctx.work["ops"] / (ctx.window_s * ctx.peaks["int8_ops"])
