"""The serving loop's spans and counters (docs/serving.md "Tracing"): a
smoke engine served under ``jax.profiler.trace`` and read back from the
trace file, the queue-time stamp under staggered arrivals, and the named
scopes and programs of the compiled mixed step."""

import dataclasses
import gc

import jax
import numpy as np
import pytest

from bench import spans as trace_spans
from repro import configs
from repro.models import lm
from repro.serve import scheduler as sched_mod
from repro.serve.engine import Engine, ServeConfig
from repro.serve.scheduler import Request, Scheduler

CHILDREN = ("serve.plan", "serve.dispatch", "serve.sample", "serve.wait",
            "serve.commit")


def small_cfg():
    cfg = configs.get_config("granite_3_8b", smoke=True)
    return dataclasses.replace(cfg, vocab=64, d_model=64, d_ff=128,
                               n_layers=2, dtype="float32")


def _engine(cfg, **kw):
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    scfg = dict(prefill_mode="continuous", max_seq=48, page_size=4,
                max_batch=3, max_pages=40, prefill_chunk=4, decode_block=4,
                temperature=0.7, seed=11, prefix_cache=False)
    scfg.update(kw)
    return Engine(params, cfg, ServeConfig(**scfg))


def _prompts(lengths, vocab=64):
    rng = np.random.default_rng(3)
    return [rng.integers(0, vocab, (n,)).astype(np.int32) for n in lengths]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced ``serve_requests`` call of four requests (the fourth
    queues for a row) and a forced collection, with every plan the
    scheduler returned."""
    eng = _engine(small_cfg())
    prompts = _prompts((9, 5, 12, 7))
    eng.serve_requests(prompts, 6)  # compile outside the trace
    plans = []
    real_plan = Scheduler.plan

    def plan(self):
        p = real_plan(self)
        if p is not None:
            rows = sum(r is not None for r in p.rows)
            fed = (sum(p.n_new) if isinstance(p, sched_mod.StepPlan)
                   else rows * p.n_steps)
            plans.append(("mixed" if isinstance(p, sched_mod.StepPlan)
                          else "decode_run", rows, fed))
        return p

    c0 = (eng.step_calls, eng.fed_positions)
    out = tmp_path_factory.mktemp("trace")
    Scheduler.plan = plan
    try:
        with jax.profiler.trace(str(out)):
            results = eng.serve_requests(prompts, 6)
            gc.collect()
    finally:
        Scheduler.plan = real_plan
    counts = (eng.step_calls - c0[0], eng.fed_positions - c0[1])
    return trace_spans.load(out).spans, plans, counts, results


def test_one_iteration_span_per_dispatch(traced):
    spans, plans, (steps, _), _ = traced
    its = [s for s in spans if s.name == "serve.iteration"]
    assert len(its) == steps == len(plans)
    assert [s.args["it"] for s in its] == sorted(s.args["it"] for s in its)
    (req,) = [s for s in spans if s.name == "serve.requests"]
    assert req.args["n"] == 4
    assert all(req.start <= s.start and s.end <= req.end for s in its)


def test_children_nest_in_order(traced):
    spans = traced[0]
    for it in (s for s in spans if s.name == "serve.iteration"):
        kids = [s for s in spans if s.name in CHILDREN
                and it.start <= s.start and s.end <= it.end]
        names = [s.name for s in kids]
        if it.args["kind"] == "mixed":
            assert names == list(CHILDREN)
        else:  # the fused loop samples on the device
            assert names == ["serve.plan", "serve.dispatch", "serve.wait",
                             "serve.commit"]
        assert all(a.end <= b.start for a, b in zip(kids, kids[1:]))


def test_kind_and_fed_match_the_plans(traced):
    spans, plans, (_, fed_positions), _ = traced
    its = [s for s in spans if s.name == "serve.iteration"]
    assert [(s.args["kind"], s.args["rows"], s.args["fed"]) for s in its] == plans
    assert {k for k, _, _ in plans} == {"mixed", "decode_run"}
    assert fed_positions == sum(f for k, _, f in plans if k == "mixed")


def test_admissions_carry_queue_time(traced):
    spans, _, _, results = traced
    admits = [s for s in spans if s.name == "serve.admit"]
    assert sorted(s.args["rid"] for s in admits) == sorted(r.rid for r in results)
    by_rid = {s.args["rid"]: s.args["queue_s"] for s in admits}
    for r in results:
        assert by_rid[r.rid] == pytest.approx(r.queue_time)
    # the fourth request waits for a row: rows are max_batch 3
    assert results[3].queue_time > max(r.queue_time for r in results[:3])


def test_forced_collection_is_a_gc_span(traced):
    spans = traced[0]
    (req,) = [s for s in spans if s.name == "serve.requests"]
    gcs = [s for s in spans if s.name == "python.gc" and s.start >= req.end]
    assert any(s.args["generation"] == 2 for s in gcs)


def test_health_step_times_come_from_the_iteration_span():
    eng = _engine(small_cfg())
    eng.serve_requests(_prompts((9, 5)), 4)
    h = eng.health()
    assert not hasattr(eng, "_step_timer")
    assert len(eng._step_samples) == eng.step_calls
    assert h["step_p99_us"] >= h["step_p50_us"] > 0.0


def test_queue_time_counts_from_the_arrival(monkeypatch):
    """A request due at iteration 3 waits from iteration 3, not from the
    call that handed it over."""
    now = [100.0]
    monkeypatch.setattr(sched_mod.time, "monotonic", lambda: now[0])
    s = Scheduler(max_batch=2, page_size=4, n_pages=32, max_pages_per_req=8,
                  prefill_chunk=4)
    early = Request(rid=1, prompt=np.arange(1, 13, dtype=np.int32),
                    max_new_tokens=2)
    late = Request(rid=2, prompt=np.arange(1, 5, dtype=np.int32),
                   max_new_tokens=2, arrival=3)
    s.add(early)
    s.add(late)
    while late.t_admit == 0.0:
        now[0] = 100.0 + 10 * s.iteration
        plan = s.plan()
        if plan is None:
            s.tick()
            continue
        s.commit(plan, np.zeros((2,), np.int32), ok=np.ones((2,), bool))
    assert early.t_enqueue == 100.0
    assert late.t_enqueue == late.t_admit == 130.0  # a row was free
    res = Engine._result(late)
    assert res.queue_time == 0.0


@pytest.fixture(scope="module")
def int8_engine(tmp_path_factory):
    """An engine on the int8 DBB wire, and a trace of one call of it."""
    eng = _engine(small_cfg(), pack_weights=True, wire_dtype="int8")
    out = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(out)):
        eng.serve_requests(_prompts((9, 5)), 4)
    return eng, out


def test_compiled_mixed_step_names_its_scopes(int8_engine):
    eng, _ = int8_engine
    sched = eng._make_scheduler()
    sched.add(Request(rid=100, prompt=np.arange(1, 10, dtype=np.int32),
                      max_new_tokens=2))
    plan = sched.plan()
    lowered = eng._paged_step.lower(
        eng.params, eng._cont["cache"], plan.tokens, plan.positions,
        plan.page_tables, plan.scrub_pages, plan.cow_pages,
    )
    text = lowered.compile().as_text()
    assert "jit_paged_step" in text
    for scope in ("dbb_linear", "dap_pack"):
        assert f"/{scope}/" in text, scope


def test_trace_keeps_the_scopes_of_both_programs(int8_engine):
    """The profiler keeps each program's optimized HLO beside the device
    trace; ``bench/spans.py`` reads the scopes of its instructions from
    there."""
    _, out = int8_engine
    by_program = trace_spans.hlo_scopes(trace_spans.trace_file(out).read_bytes())
    for prog in ("jit_paged_step", "jit_decode_run"):
        (scopes,) = [m for n, m in by_program.items() if n.startswith(prog + "(")]
        assert {"dbb_linear", "dap_pack"} <= set(scopes.values()), prog
