"""The program's spans and named scopes, on the synthetic burst of
``test_tracing``: gap labels led by spans, idle time by span, device
operations by scope, and the four readers that need them."""

import pytest

from bench import linear_counts, readers, spans, tracing
from bench.tests.test_tracing import KERNEL, synthetic

Span = spans.Span
SERVE = {"page_size": 16, "max_seq": 64, "max_batch": 2, "prefill_chunk": 32}
TINY = dict(n_layers=1, d_model=8, n_heads=1, n_kv_heads=1, head_dim=8,
            d_ff=16, vocab=8, padded_vocab=8)
PEAKS = {"int8_ops": 1e6, "hbm_bytes_per_s": 1e5}


def program_spans():
    """The host side of the synthetic burst: two mixed steps and a decode
    run of 8 iterations, a collection inside the run's dispatch."""
    def it(k, s, e, kind, fed):
        return Span("serve.iteration", s, e,
                    {"it": k, "kind": kind, "rows": 2, "fed": fed})

    out = [
        Span("serve.requests", 0.06, 0.95, {"n": 3}),
        it(0, 0.07, 0.34, "mixed", 40),
        Span("serve.plan", 0.07, 0.09, {}),
        Span("serve.admit", 0.08, 0.08, {"rid": 1, "queue_s": 0.01}),
        Span("serve.admit", 0.081, 0.081, {"rid": 2, "queue_s": 0.02}),
        Span("serve.dispatch", 0.09, 0.10, {}),
        Span("serve.sample", 0.10, 0.11, {}),
        Span("serve.wait", 0.11, 0.32, {}),
        Span("serve.commit", 0.32, 0.335, {}),
        it(1, 0.35, 0.64, "mixed", 24),
        Span("serve.plan", 0.35, 0.355, {}),
        Span("serve.admit", 0.352, 0.352, {"rid": 3, "queue_s": 0.5}),
        Span("serve.dispatch", 0.355, 0.40, {}),
        Span("serve.sample", 0.40, 0.41, {}),
        Span("serve.wait", 0.41, 0.62, {}),
        Span("serve.commit", 0.62, 0.64, {}),
        it(2, 0.65, 0.93, "decode_run", 16),
        Span("serve.plan", 0.65, 0.655, {}),
        Span("serve.dispatch", 0.655, 0.70, {}),
        Span("python.gc", 0.657, 0.699, {"generation": 2}),
        Span("serve.wait", 0.70, 0.90, {}),
        Span("serve.commit", 0.90, 0.93, {}),
    ]
    return sorted(out, key=lambda s: (s.start, -s.end))


def scoped_ops():
    """``synthetic()``'s operations with the scopes the program names."""
    scope = {"fusion.1": "dbb_linear", KERNEL: "paged_attn"}
    ops = [spans.Op(n, s, e, scope.get(n)) for n, s, e in synthetic().ops]
    # two overlapping linear fusions inside the decode loop
    return ops + [spans.Op("fusion.7", 0.72, 0.78, "dbb_linear"),
                  spans.Op("fusion.8", 0.75, 0.80, "dbb_linear")]


def context(with_spans=True):
    tr = synthetic()
    ctx = readers.Context(
        model=TINY, peaks=PEAKS, trace=tr, window=(0.0, 1.0),
        counters={"step_calls": 3, "decode_run_calls": 1, "fused_tokens": 8},
        work={"ops": 0, "kv_bytes": 0, "qo_bytes": 0, "tokens": 0},
        programs=tracing.split_programs(tr.modules, 0.0, 1.0), serve=SERVE,
    )
    ctx.program = (spans.Program(program_spans(), scoped_ops()) if with_spans
                   else spans.Program([], []))
    return ctx


def test_gap_labels_lead_with_the_deepest_span():
    labels = dict(spans.gap_labels(synthetic(), program_spans(), 0.0, 1.0))
    assert spans.gap_labels(synthetic(), program_spans(), 0.0, 1.0)[0] == [
        "no span | window start -> jit__lambda_(1), 0 commits",
        pytest.approx(0.10)]
    assert labels == {
        "no span | window start -> jit__lambda_(1), 0 commits": pytest.approx(0.10),
        "serve.dispatch | jit_sample_at -> jit__lambda_(1), 0 commits":
            pytest.approx(0.08),
        "python.gc | jit_sample_at -> jit__lambda_(2), 1 commits":
            pytest.approx(0.08),
        "no span | jit_convert_element_type -> window end, 0 commits":
            pytest.approx(0.075),
        "serve.commit | jit__lambda_(2) -> jit_convert_element_type, 0 commits":
            pytest.approx(0.02),
        "serve.wait | jit__lambda_(1) -> jit_sample_at, 0 commits":
            pytest.approx(0.01),
    }


def test_idle_time_by_span_adds_up():
    tr = synthetic()
    by = dict(spans.idle_by_span(tr, program_spans(), 0.0, 1.0))
    assert sum(by.values()) == pytest.approx(1.0 - tracing.busy(tr.ops, 0.0, 1.0))
    # before serve.requests and after it
    assert by["no span"] == pytest.approx(0.06 + 0.05)
    assert by["python.gc"] == pytest.approx(0.699 - 0.657)
    assert by["serve.wait"] == pytest.approx(0.02)
    assert by["serve.commit"] == pytest.approx(0.015 + 0.02 + 0.02 + 0.005)


def test_scopes_read_from_names_and_metadata():
    assert spans.scope_of([KERNEL.replace("%closed_call.4", "%paged_attn.4")]) == "paged_attn"
    assert spans.scope_of(["%fusion.3 = f32[2] fusion(...)",
                           "jit(paged_step)/jit(main)/while/body/dbb_linear/dot_general"]
                          ) == "dbb_linear"
    assert spans.scope_of(["jit(decode_run)/dap_pack/reduce_max"]) == "dap_pack"
    assert spans.scope_of(["%fusion.3 = f32[2] fusion(...)", "jit(f)/mul"]) is None


def test_top_ops_by_scope_keeps_times_and_order():
    ops = scoped_ops()
    plain = tracing.top_ops([(n, s, e) for n, s, e, _ in ops], 0.0, 1.0)
    named = spans.top_ops_by_scope(ops, 0.0, 1.0)
    assert [v for _, v in named] == pytest.approx([v for _, v in plain])
    assert [k.split(" [")[0] for k, _ in named] == [k for k, _ in plain]
    assert named[0][0] == "%closed_call.4 custom-call tpu_custom_call [paged_attn]"
    assert "fusion.1 [dbb_linear]" in dict(named)
    assert spans.scope_seconds(ops, "dbb_linear", 0.0, 1.0) == pytest.approx(0.28)


def test_readers_of_the_program_spans():
    ctx = context()
    got = {name: readers.load_reader(name).read(ctx) for name in (
        "mixed_step_fill", "queue_wait_p90_s", "host_step_ms",
        "dbb_linear_roofline")}
    assert got["mixed_step_fill"] == pytest.approx(100 * 64 / (2 * 2 * 32))
    assert got["queue_wait_p90_s"] == pytest.approx(0.02 + 0.8 * 0.48)
    # iterations less their waits: 0.06, 0.08, 0.08
    assert got["host_step_ms"] == pytest.approx(1e3 * 0.22 / 3)
    least = (linear_counts.least_linear_s(TINY, 40, PEAKS)
             + linear_counts.least_linear_s(TINY, 24, PEAKS)
             + 8 * linear_counts.least_linear_s(TINY, 2, PEAKS))
    assert least == pytest.approx(2 * 40 * 704 / 1e6 + 2 * 24 * 704 / 1e6
                                  + 8 * 760 / 1e5)
    assert got["dbb_linear_roofline"] == pytest.approx(100 * least / 0.28)


def test_readers_read_nothing_without_spans():
    """A program without spans (the parent of this change) reports none
    of the four, and the six older readers are unmoved."""
    ctx = context(with_spans=False)
    for name in ("mixed_step_fill", "queue_wait_p90_s", "host_step_ms",
                 "dbb_linear_roofline"):
        assert readers.load_reader(name).read(ctx) is None
    assert readers.load_reader("mixed_step_ms").read(ctx) == pytest.approx(200.0)
