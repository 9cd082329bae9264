"""Seeded-sampling exactness suite (core/sampling.py + the serving stack).

The contract under test (docs/serving.md "Sampling"): every path shares
ONE sampler with PRNG keys derived from ``(request seed, fed-stream
position)``, so under fixed seeds

* fused-loop sampled tokens == stepped-sampler tokens, byte for byte,
  across {GQA, MLA} x {native, int8 wire} x {f32, int8 KV},
* sampled rows are batch-invariant and ``decode_block``-invariant,
* a preempted-then-readmitted request's sampled output is byte-identical
  to its uninterrupted run,
* ``temperature=0`` stays plain argmax — continuous greedy output
  equals the stepped engine's, byte for byte,
* stop tokens finish a request as ``"stop"`` with outcomes identical
  for ``decode_block=1`` and ``16`` (fused-run rewind).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.sampling import SamplingParams, sample_tokens
from repro.models import lm
from repro.serve.engine import Engine, ServeConfig
from repro.serve.scheduler import FINISH_LENGTH, FINISH_STOP


def small_cfg(arch="granite_3_8b", **kw):
    cfg = configs.get_config(arch, smoke=True)
    over = dict(vocab=64, d_model=64, d_ff=128, n_layers=2, dtype="float32")
    if arch == "qwen2_vl_72b":
        over["d_model"] = 128
    over.update(kw)
    return dataclasses.replace(cfg, **over)


def _wire_kwargs(wire):
    return dict(pack_weights=True, wire_dtype="int8") if wire == "int8" else {}


def _mixed_prompts(vocab, lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (s,)).astype(np.int32) for s in lengths]


def _stepped_reference(params, cfg, prompts, n_tokens, **skw):
    """Per-request solo stepped outputs — the byte-exactness oracle
    (same ServeConfig sampling knobs as the continuous engine)."""
    ref = Engine(params, cfg, ServeConfig(
        max_seq=64, prefill_mode="stepped", **skw
    ))
    return [ref.generate(p[None], n_tokens)[0] for p in prompts]


CONT_KW = dict(
    prefill_mode="continuous", max_seq=32, page_size=8, max_batch=2,
    prefill_chunk=4,
)


# ------------------------------------------------------- sampler unit tests


def _row_args(b, temp=0.7, top_k=0, top_p=1.0, seed=0, pos=5):
    return (
        jnp.full((b,), temp, jnp.float32),
        jnp.full((b,), top_k, jnp.int32),
        jnp.full((b,), top_p, jnp.float32),
        jnp.full((b,), seed, jnp.uint32),
        jnp.full((b,), pos, jnp.int32),
    )


def test_sample_tokens_zero_temperature_is_argmax():
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(4, 64)),
                         jnp.float32)
    toks = sample_tokens(logits, *_row_args(4, temp=0.0))
    np.testing.assert_array_equal(
        np.asarray(toks), np.argmax(np.asarray(logits), axis=-1)
    )


def test_sample_tokens_deterministic_and_position_keyed():
    logits = jnp.asarray(np.random.default_rng(1).normal(size=(8, 64)),
                         jnp.float32)
    a = np.asarray(sample_tokens(logits, *_row_args(8, pos=5)))
    b = np.asarray(sample_tokens(logits, *_row_args(8, pos=5)))
    np.testing.assert_array_equal(a, b)  # same (seed, position) -> same
    c = np.asarray(sample_tokens(logits, *_row_args(8, pos=6)))
    d = np.asarray(sample_tokens(logits, *_row_args(8, seed=1, pos=5)))
    # different position / seed -> different keys; with 8 rows of near-
    # uniform 64-way logits, collision of ALL rows is ~impossible
    assert not np.array_equal(a, c)
    assert not np.array_equal(a, d)


def test_sample_tokens_top_k_one_and_tiny_top_p_are_argmax():
    logits = jnp.asarray(np.random.default_rng(2).normal(size=(6, 64)),
                         jnp.float32)
    greedy = np.argmax(np.asarray(logits), axis=-1)
    np.testing.assert_array_equal(
        np.asarray(sample_tokens(logits, *_row_args(6, top_k=1))), greedy
    )
    np.testing.assert_array_equal(
        np.asarray(sample_tokens(logits, *_row_args(6, top_p=1e-9))), greedy
    )


def test_sample_tokens_full_top_p_keeps_every_token():
    """top_p=1 (and top_k disabled) is plain categorical sampling over the
    whole vocab: the f32 cumulative mass of ~50k near-uniform probabilities
    rounds past 1 before the last token, and that rounding (which varies
    with how the sampling program is compiled) must not trim the tail."""
    b, v = 64, 49155
    logits = jnp.asarray(
        np.random.default_rng(4).normal(size=(b, v)) * 0.1, jnp.float32
    )
    temps, top_ks, top_ps, seeds, _ = _row_args(b)
    pos = jnp.arange(b, dtype=jnp.int32)
    got = sample_tokens(logits, temps, top_ks, top_ps, seeds, pos)
    want = [
        jax.random.categorical(
            jax.random.fold_in(jax.random.PRNGKey(0), i), logits[i] / 0.7
        )
        for i in range(b)
    ]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sample_tokens_top_k_masks_tail():
    """With top_k=2 every draw lands on one of the two largest logits."""
    logits = jnp.asarray(np.random.default_rng(3).normal(size=(16, 64)),
                         jnp.float32)
    top2 = np.argsort(np.asarray(logits), axis=-1)[:, -2:]
    for pos in range(8):
        toks = np.asarray(sample_tokens(
            logits, *_row_args(16, temp=2.0, top_k=2, pos=pos)
        ))
        for r in range(16):
            assert toks[r] in top2[r]


def test_sample_tokens_rows_are_independent():
    """A greedy row co-batched with sampled rows still returns its
    argmax, and a sampled row's token does not depend on neighbors."""
    logits = jnp.asarray(np.random.default_rng(4).normal(size=(3, 64)),
                         jnp.float32)
    temps = jnp.asarray([0.0, 0.9, 0.0], jnp.float32)
    _, top_ks, top_ps, seeds, pos = _row_args(3)
    mixed = np.asarray(
        sample_tokens(logits, temps, top_ks, top_ps, seeds, pos)
    )
    greedy = np.argmax(np.asarray(logits), axis=-1)
    assert mixed[0] == greedy[0] and mixed[2] == greedy[2]
    solo = np.asarray(sample_tokens(
        logits[1:2], *(a[1:2] for a in (temps, top_ks, top_ps, seeds, pos))
    ))
    assert mixed[1] == solo[0]


def test_sampling_params_validation():
    for bad in (
        dict(temperature=-0.1),
        dict(temperature=float("nan")),
        dict(temperature=float("inf")),
        dict(top_k=0),
        dict(top_k=-3),
        dict(top_p=0.0),
        dict(top_p=1.5),
        dict(top_p=float("nan")),
        dict(seed=-1),
    ):
        with pytest.raises(ValueError):
            SamplingParams(**bad)
    for bad in (
        dict(temperature=-1.0), dict(top_k=0), dict(top_p=2.0),
    ):
        with pytest.raises(ValueError):
            ServeConfig(**bad)
    # valid corners construct fine
    SamplingParams(temperature=0.0, top_k=1, top_p=1.0, seed=0)
    ServeConfig(temperature=0.7, top_k=8, top_p=0.9, seed=123)


# --------------------------------------------- path-exactness (the tentpole)


@pytest.mark.parametrize("arch", ["granite_3_8b", "minicpm3_4b"])
@pytest.mark.parametrize("wire", ["native", "int8"])
@pytest.mark.parametrize("kv", ["native", "int8"])
def test_fused_sampled_matches_stepped(arch, wire, kv):
    """Continuous serving (fused decode runs) with temperature>0 is
    byte-identical to the solo stepped sampler under the same seed —
    GQA and MLA, both weight wires, both KV dtypes."""
    cfg = small_cfg(arch)
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    wkw = _wire_kwargs(wire)
    if kv == "int8":
        wkw["kv_dtype"] = "int8"
    skw = dict(temperature=0.7, seed=11, **wkw)
    prompts = _mixed_prompts(cfg.vocab, (9, 5, 12))
    eng = Engine(params, cfg, ServeConfig(**CONT_KW, **skw))
    outs = eng.generate_requests(prompts, 6)
    ref = _stepped_reference(params, cfg, prompts, 6, **skw)
    for i, (got, want) in enumerate(zip(outs, ref)):
        np.testing.assert_array_equal(
            got, want, err_msg=f"request {i} fused != stepped ({arch})"
        )
    # sanity: the run actually used the fused loop and actually sampled
    assert eng.decode_run_calls > 0
    greedy = _stepped_reference(params, cfg, prompts, 6, **wkw)
    assert any(
        not np.array_equal(a, g) for a, g in zip(outs, greedy)
    ), "temperature=0.7 never diverged from greedy"


def test_sampled_tokens_batch_invariant():
    """Co-batched sampled rows equal their solo runs: keys depend on
    (seed, position), never on batch slot or scheduler iteration."""
    cfg = small_cfg()
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    prompts = _mixed_prompts(cfg.vocab, (9, 5, 12))
    skw = dict(temperature=0.7, seed=7, prefix_cache=False)
    batched = Engine(params, cfg, ServeConfig(**CONT_KW, **skw))
    outs = batched.generate_requests(prompts, 6, arrivals=[0, 2, 1])
    for i, p in enumerate(prompts):
        solo = Engine(params, cfg, ServeConfig(**CONT_KW, **skw))
        np.testing.assert_array_equal(
            outs[i], solo.generate_requests([p], 6)[0],
            err_msg=f"request {i} not batch-invariant under sampling",
        )


def test_sampled_invariant_to_decode_block():
    """decode_block=1 (one dispatch per token) and =16 (fused runs)
    produce identical sampled bytes: keys are position-derived, so run
    length cannot matter."""
    cfg = small_cfg()
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    prompts = _mixed_prompts(cfg.vocab, (9, 5, 12))
    skw = dict(temperature=0.9, top_k=16, top_p=0.95, seed=3)
    out16 = Engine(params, cfg, ServeConfig(
        **CONT_KW, decode_block=16, **skw
    )).generate_requests(prompts, 8)
    out1 = Engine(params, cfg, ServeConfig(
        **CONT_KW, decode_block=1, **skw
    )).generate_requests(prompts, 8)
    for a, b in zip(out16, out1):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("arch", ["granite_3_8b", "minicpm3_4b"])
def test_preempt_replay_byte_identical_with_sampling(arch):
    """Preempt-and-recompute under temperature>0: replay feeds the known
    tokens without re-sampling, post-replay samples land on the same
    positions -> same keys -> byte-identical output."""
    cfg = small_cfg(arch)
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    skw = dict(temperature=0.7, seed=9)
    prompts = _mixed_prompts(cfg.vocab, (9, 5, 12, 7), seed=5)
    eng = Engine(params, cfg, ServeConfig(
        prefill_mode="continuous", prefill_chunk=4,
        max_seq=24, page_size=4, max_batch=3, max_pages=13,
        preempt_after=2, **skw,
    ))
    res = eng.serve_requests(prompts, 10)
    assert all(r.finish_reason == FINISH_LENGTH for r in res)
    assert eng.health()["preemptions"] > 0, "pool pressure never preempted"
    ref = _stepped_reference(params, cfg, prompts, 10, **skw)
    for i, (r, want) in enumerate(zip(res, ref)):
        np.testing.assert_array_equal(
            r.tokens, want,
            err_msg=f"sampled request {i} diverged after preemption",
        )


@pytest.mark.parametrize("arch", ["granite_3_8b", "minicpm3_4b"])
def test_greedy_goldens_unchanged(arch):
    """temperature=0 stays plain argmax on every path: continuous greedy
    output (staggered arrivals, chunked prefill, fused runs) equals the
    stepped engine's greedy output on the same prompts, byte for byte."""
    cfg = small_cfg(arch)
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    prompts = _mixed_prompts(cfg.vocab, (9, 5, 12))
    eng = Engine(params, cfg, ServeConfig(**CONT_KW))
    outs = eng.generate_requests(prompts, 6, arrivals=[0, 3, 1])
    ref = _stepped_reference(params, cfg, prompts, 6)
    for got, want in zip(outs, ref):
        np.testing.assert_array_equal(got, want)


def test_one_shot_batched_sampling_matches_stepped():
    """The one-shot batched path (lm.prefill + lock-step decode) runs
    the same sampler with the same position keys."""
    cfg = small_cfg()
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab, (3, 8)).astype(np.int32)
    skw = dict(temperature=0.8, top_k=32, seed=21)
    out_b = Engine(params, cfg, ServeConfig(
        max_seq=48, prefill_mode="batched", **skw)).generate(prompts, 8)
    out_s = Engine(params, cfg, ServeConfig(
        max_seq=48, prefill_mode="stepped", **skw)).generate(prompts, 8)
    np.testing.assert_array_equal(out_b, out_s)
    greedy = Engine(params, cfg, ServeConfig(
        max_seq=48, prefill_mode="batched")).generate(prompts, 8)
    assert not np.array_equal(out_b, greedy)


def test_per_request_sampling_params():
    """Per-request SamplingParams override the config: greedy requests
    co-batched with a sampled one equal the all-greedy run, and the
    sampled one equals its solo run."""
    cfg = small_cfg()
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    prompts = _mixed_prompts(cfg.vocab, (9, 5, 12))
    eng = Engine(params, cfg, ServeConfig(**CONT_KW))
    outs = eng.generate_requests(
        prompts, 6, arrivals=[0, 3, 1],
        sampling=[None, SamplingParams(temperature=0.7, seed=4), None],
    )
    greedy = Engine(params, cfg, ServeConfig(**CONT_KW)).generate_requests(
        prompts, 6, arrivals=[0, 3, 1]
    )
    assert outs[0].tolist() == greedy[0].tolist()
    assert outs[2].tolist() == greedy[2].tolist()
    assert outs[1].tolist() != greedy[1].tolist()
    # the sampled row equals its solo run under the same params
    solo = Engine(params, cfg, ServeConfig(
        **CONT_KW, temperature=0.7, seed=4, prefix_cache=False,
    )).generate_requests([prompts[1]], 6)
    assert outs[1].tolist() == solo[0].tolist()


def test_paged_compiles_stay_two_with_sampling():
    """Fusing the sampler into the loop must not add compile traces:
    mixed steps + fused runs still compile exactly twice, with sampled
    and greedy rows flowing through the same traces."""
    cfg = small_cfg()
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    prompts = _mixed_prompts(cfg.vocab, (9, 5, 12))
    eng = Engine(params, cfg, ServeConfig(
        **CONT_KW, temperature=0.7, seed=2,
    ))
    eng.generate_requests(prompts, 6, arrivals=[0, 3, 1])
    eng.generate_requests(
        prompts, 4,
        sampling=[SamplingParams(), SamplingParams(temperature=1.1, seed=8),
                  None],
    )
    assert eng.paged_compiles == 2


# ------------------------------------------------------------- stop tokens


def test_stop_token_finishes_with_stop_reason():
    """Sampling a stop token ends the request early: finish_reason is
    "stop", the stop token IS the last output token, and ok is True."""
    cfg = small_cfg()
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    prompts = _mixed_prompts(cfg.vocab, (9, 5, 12))
    ref = _stepped_reference(params, cfg, prompts, 6)
    gen0 = ref[0][9:].tolist()  # request 0's greedy continuation
    stop = gen0[2]
    first = gen0.index(stop)
    eng = Engine(params, cfg, ServeConfig(**CONT_KW))
    res = eng.serve_requests(prompts[:1], 6, stop_tokens=[stop])
    assert res[0].finish_reason == FINISH_STOP
    assert res[0].ok
    assert res[0].tokens.tolist() == ref[0][: 9 + first + 1].tolist()
    assert res[0].n_generated == first + 1
    # a stop token the model never samples changes nothing
    unused = next(t for t in range(cfg.vocab) if t not in gen0)
    res2 = eng.serve_requests(prompts[:1], 6, stop_tokens=[unused])
    assert res2[0].finish_reason == FINISH_LENGTH
    np.testing.assert_array_equal(res2[0].tokens, ref[0])


@pytest.mark.parametrize("temp", [0.0, 0.7])
def test_stop_outcomes_invariant_to_decode_block(temp):
    """The fused-run stop rewind: decode_block=16 truncates the run at
    the earliest stop, so outcomes (bytes, reasons, generation counts)
    match decode_block=1 exactly — with and without sampling."""
    cfg = small_cfg()
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    prompts = _mixed_prompts(cfg.vocab, (9, 5, 12))
    skw = dict(temperature=temp, seed=13)
    # choose per-request stops from each request's own reference stream
    ref = _stepped_reference(params, cfg, prompts, 10, **skw)
    stops = [
        [int(ref[0][9 + 4])],  # request 0 stops mid-stream
        None,  # request 1 runs to length
        [int(ref[2][12 + 2])],  # request 2 stops early
    ]
    res = {}
    for block in (1, 16):
        eng = Engine(params, cfg, ServeConfig(
            **CONT_KW, decode_block=block, prefix_cache=False, **skw
        ))
        res[block] = eng.serve_requests(prompts, 10, stop_tokens=stops)
    for i, (a, b) in enumerate(zip(res[1], res[16])):
        assert a.finish_reason == b.finish_reason, f"request {i}"
        assert a.n_generated == b.n_generated, f"request {i}"
        np.testing.assert_array_equal(
            a.tokens, b.tokens, err_msg=f"request {i} stop bytes differ"
        )
    # the stops actually fired early (not just length finishes)
    assert res[16][0].finish_reason == FINISH_STOP
    assert res[16][0].n_generated < 10
    assert res[16][1].finish_reason == FINISH_LENGTH


def test_stop_tokens_per_request_and_mixed_step_path():
    """Stops enforced on the mixed-step commit path too (decode_block=1
    keeps every sample in a mixed/stepped commit), per request."""
    cfg = small_cfg()
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    prompts = _mixed_prompts(cfg.vocab, (9, 5))
    ref = _stepped_reference(params, cfg, prompts, 6)
    stop0 = int(ref[0][9 + 1])
    eng = Engine(params, cfg, ServeConfig(**CONT_KW, decode_block=1))
    res = eng.serve_requests(prompts, 6, stop_tokens=[[stop0], None])
    assert res[0].finish_reason == FINISH_STOP
    assert res[0].n_generated == ref[0][9:].tolist().index(stop0) + 1
    assert res[1].finish_reason == FINISH_LENGTH
    np.testing.assert_array_equal(res[1].tokens, ref[1])


# ------------------------------------------------------------- validation


def test_out_of_vocab_prompt_rejected():
    """An out-of-vocab token id raises up front, naming the request —
    never silently clamped by the embedding gather."""
    cfg = small_cfg()
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    eng = Engine(params, cfg, ServeConfig(**CONT_KW))
    good = np.array([1, 2, 3], np.int32)
    for bad in (
        np.array([1, cfg.vocab, 3], np.int32),
        np.array([-1, 2, 3], np.int32),
    ):
        with pytest.raises(ValueError, match="request 1"):
            eng.generate_requests([good, bad], 3)
        with pytest.raises(ValueError, match="vocab"):
            eng.serve_requests([bad], 3)
    assert eng._cont is None  # nothing reached the paged pool
    # stop tokens are range-checked too
    with pytest.raises(ValueError, match="stop token"):
        eng.serve_requests([good], 3, stop_tokens=[cfg.vocab + 1])


def test_sampling_argument_validation():
    cfg = small_cfg()
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    eng = Engine(params, cfg, ServeConfig(**CONT_KW))
    good = np.array([1, 2, 3], np.int32)
    with pytest.raises(ValueError, match="sampling"):
        eng.generate_requests([good, good], 3, sampling=[SamplingParams()])
    with pytest.raises(ValueError, match="SamplingParams"):
        eng.generate_requests([good], 3, sampling=[0.7])
    with pytest.raises(ValueError, match="stop_tokens"):
        eng.serve_requests([good, good], 3, stop_tokens=[[1], [2], [3]])
