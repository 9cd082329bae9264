"""Share of the page tables that the fused paged-attention kernel walks:
the pages it walked a layer (the ``walked_pages`` argument of the
``serve.iteration`` spans, each row's live pages rounded up to whole
blocks, ``kernels/paged_attn.walked_pages``) over the pages of every
row's full table (``max_batch`` rows of ``max_seq / page_size`` pages)
a kernel call, one call a mixed step and one a decode run's token.
A kernel that walks whole tables reads 100.  Layer: kernels."""

UNIT = "%"


def read(ctx):
    from bench import spans

    t0, t1 = ctx.window
    its = [s for s in spans.dispatches(spans.of(ctx).spans, t0, t1)
           if "walked_pages" in s.args]
    if not its:  # no fused kernel, or a program without the counter
        return None
    serve = ctx.serve
    table = serve["max_batch"] * -(-serve["max_seq"] // serve["page_size"])
    calls = sum(1 if s.args["kind"] == "mixed" else s.args["fed"] // s.args["rows"]
                for s in its)
    return 100.0 * sum(s.args["walked_pages"] for s in its) / (table * calls)
