"""The DBB linears' share of their roofline: the least time the traced
burst's linears needed (``bench/linear_counts.py``: each dispatch's
live positions at the dense shape over the int8 peak, or the packed
weight bytes over the HBM bandwidth, whichever is longer; a fused
decode run is one pass a loop iteration) over the device time of the
operations under the program's ``dbb_linear`` scope
(``models/common.py`` ``linear``).  Layer: kernels."""

UNIT = "%"


def read(ctx):
    from bench import linear_counts, spans

    t0, t1 = ctx.window
    its = spans.dispatches(spans.of(ctx).spans, t0, t1)
    if not ctx.peaks or not its:
        return None
    least = 0.0
    for it in its:
        kind, rows, fed = it.args["kind"], it.args["rows"], it.args["fed"]
        if kind == "mixed":
            least += linear_counts.least_linear_s(ctx.model, fed, ctx.peaks)
        elif kind == "decode_run":
            least += fed // rows * linear_counts.least_linear_s(
                ctx.model, rows, ctx.peaks)
        else:  # a speculative round's draft and verify passes: not counted
            return None
    linear_s = spans.scope_seconds(spans.of(ctx).ops, "dbb_linear", t0, t1)
    return 100.0 * least / linear_s if linear_s else None
