"""The reduction from trace to metrics, on a small synthetic trace."""

import pytest

from bench import readers, tracing


SERVE = {"page_size": 16, "max_seq": 64, "max_batch": 2}  # 2 x 4 + 1 pages
KERNEL = ('%closed_call.4 = bf16[2,1,4,32]{3,2,1,0} custom-call(s32[2,4]{1,0} %t, '
          's8[9,16,64]{2,1,0} %k, f32[2,4,16]{2,1,0} %s), '
          'custom_call_target="tpu_custom_call"')


def synthetic():
    """A 1.0 s burst: two mixed steps (each followed by the sampler) and
    one decode run; the paged-attention kernel inside each program."""
    modules = [
        ("jit__lambda_(1)", 0.10, 0.30), ("jit_sample_at", 0.31, 0.32),
        ("jit__lambda_(1)", 0.40, 0.60), ("jit_sample_at", 0.61, 0.62),
        ("jit__lambda_(2)", 0.70, 0.90), ("jit_convert_element_type", 0.92, 0.925),
    ]
    ops = [
        ("fusion.1", 0.10, 0.20), (KERNEL, 0.20, 0.30),
        ("sort.3", 0.31, 0.32),
        ("fusion.1", 0.40, 0.50), (KERNEL, 0.50, 0.60),
        ("sort.3", 0.61, 0.62),
        ("while.9", 0.70, 0.90), (KERNEL, 0.80, 0.85),
        ("%convert.2 = s32[] convert(s32[] %p)", 0.92, 0.925),
    ]
    host = [("burst", 0.0, 1.0), ("on_token", 0.63, 0.64)]
    return tracing.Trace(ops=ops, modules=modules, host=host, n_devices=1,
                         device_ops={0: ops})


def test_busy_and_gaps():
    tr = synthetic()
    assert tr.window() == (0.0, 1.0)
    assert tracing.busy(tr.ops, 0.0, 1.0) == pytest.approx(0.625)
    gaps = tracing.idle_gaps(tr.ops, 0.0, 1.0)
    assert gaps[0] == (0.0, 0.10) and gaps[-1] == (0.925, 1.0)
    assert sum(e - s for s, e in gaps) == pytest.approx(0.375)
    assert tracing.busy(tr.ops, 0.15, 0.25) == pytest.approx(0.10)


def test_top_ops_self_time_and_gap_labels():
    tr = synthetic()
    top = dict(tracing.top_ops(tr.ops, 0.0, 1.0))
    kernel = "%closed_call.4 custom-call tpu_custom_call"
    assert top[kernel] == pytest.approx(0.25)
    assert top["fusion.1"] == pytest.approx(0.2)
    assert top["while.9"] == pytest.approx(0.15)  # less the kernel inside it
    assert top["%convert.2 convert"] == pytest.approx(0.005)
    labels = tracing.label_gaps(tr, 0.0, 1.0, n=10)
    assert labels[0] == ["window start -> jit__lambda_(1), 0 commits", pytest.approx(0.10)]
    assert ["jit_sample_at -> jit__lambda_(2), 1 commits", pytest.approx(0.08)] in labels


def test_split_programs_by_the_sampler():
    mixed, decode = tracing.split_programs(synthetic().modules, 0.0, 1.0)
    assert [s for _, s, _ in mixed] == [0.10, 0.40]
    assert [s for _, s, _ in decode] == [0.70]  # not the conversion


def test_readers_on_the_synthetic_trace():
    tr = synthetic()
    ctx = readers.Context(
        model={}, peaks={"int8_ops": 1e12, "hbm_bytes_per_s": 1e9}, trace=tr,
        window=(0.0, 1.0),
        counters={"step_calls": 3, "decode_run_calls": 1, "fused_tokens": 8},
        work={"ops": 5e11, "kv_bytes": 1.0e8, "qo_bytes": 0.5e8, "tokens": 10},
        programs=tracing.split_programs(tr.modules, 0.0, 1.0), serve=SERVE,
    )
    got = {name: readers.load_reader(name).read(ctx) for name in (
        "mixed_step_share", "mixed_step_ms", "decode_step_ms", "mfu",
        "paged_attn_roofline", "idle_share")}
    assert got["mixed_step_share"] == pytest.approx(100 * 2 / 3)
    assert got["mixed_step_ms"] == pytest.approx(200.0)
    assert got["decode_step_ms"] == pytest.approx(200.0 / 8)
    assert got["mfu"] == pytest.approx(50.0)
    # least time 0.15 s over 0.25 s of kernel
    assert got["paged_attn_roofline"] == pytest.approx(60.0)
    assert got["idle_share"] == pytest.approx(37.5)
    ctx.programs = None
    ctx.peaks = None
    assert readers.load_reader("mixed_step_ms").read(ctx) is None
    assert readers.load_reader("mfu").read(ctx) is None
