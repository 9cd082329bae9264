"""Fused paged attention's share of its roofline: the least time the
traced burst's attention needed (the int8 keys, values and scales of
each query's live context, plus queries and outputs, over the HBM
bandwidth; ``counts.request_work``) over the device time of the kernel
(``kernels/paged_attn.py``) in the trace.  Layer: kernels.

The kernel carries no name into the trace: its events are the Pallas
custom calls that take the int8 page pool (``s8[<pages>,<page_size>,``)
as an operand.  Both the mixed step and the decode loop call it, once a
layer."""

UNIT = "%"


def is_kernel(name: str, serve: dict) -> bool:
    ps = serve["page_size"]
    pages = serve.get("max_pages") or (
        serve["max_batch"] * -(-serve["max_seq"] // ps) + 1)
    return ('custom_call_target="tpu_custom_call"' in name
            and f"s8[{pages},{serve['page_size']}," in name)


def read(ctx):
    from bench import tracing

    t0, t1 = ctx.window
    events = [iv for iv in ctx.trace.ops if is_kernel(iv[0], ctx.serve)]
    kernel_s = sum(e - s for _, s, e in tracing.clip(events, t0, t1))
    if not kernel_s or not ctx.peaks:
        return None
    least = (ctx.work["kv_bytes"] + ctx.work["qo_bytes"]) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / kernel_s
