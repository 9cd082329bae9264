"""Serving-engine tests.

Prefill: the batched (single jitted call) prefill must produce exactly
the tokens of the per-token stepped path, issue O(1) dispatches per
prompt, and compose with DBB-packed weights.

Continuous batching (the paged-KV scheduler): for every family in
``BATCHED_PREFILL_FAMILIES`` × wire_dtype ∈ {native, int8},
continuous-batched decode — staggered arrivals, mixed prompt lengths,
queueing beyond max_batch, page recycling — must emit **byte-identical**
tokens per request vs the solo stepped engine; plus batch-invariance
property tests (native exact; int8 exact too, since the engine forces
per-row dynamic activation scales on every int8-wire path).

INT8 KV cache (``ServeConfig.kv_dtype="int8"``): continuous-vs-stepped
and batched-vs-stepped stay **byte-identical within the int8-KV wire**
(GQA, MLA, and with the int8 weight/activation wire stacked on top);
cross-wire token parity vs the f32-KV engine is asserted with a
documented tolerance — KV quantization (~0.4% per-row error) legitimately
flips near-tied argmaxes on these tiny random-weight models, so the
parity tests run 1-layer configs and bound the aggregate mismatch
fraction instead of demanding equality (docs/quantization.md)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import lm
from repro.serve.engine import Engine, ServeConfig


def small_cfg(arch="granite_3_8b", **kw):
    cfg = configs.get_config(arch, smoke=True)
    over = dict(vocab=64, d_model=64, d_ff=128, n_layers=2, dtype="float32")
    if arch == "qwen2_vl_72b":
        # M-RoPE sections of the smoke config need head_dim 32
        over["d_model"] = 128
    over.update(kw)
    return dataclasses.replace(cfg, **over)


def _prompts(vocab, b=2, s0=8, seed=0):
    return np.random.default_rng(seed).integers(0, vocab, (b, s0)).astype(np.int32)


@pytest.mark.parametrize("arch", ["granite_3_8b", "minicpm3_4b"])
def test_batched_prefill_matches_stepped(arch):
    """GQA and MLA: whole-prompt prefill == token-by-token prefill."""
    cfg = small_cfg(arch)
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    prompts = _prompts(cfg.vocab)
    out_b = Engine(params, cfg, ServeConfig(max_seq=48, prefill_mode="batched")).generate(prompts, 8)
    out_s = Engine(params, cfg, ServeConfig(max_seq=48, prefill_mode="stepped")).generate(prompts, 8)
    np.testing.assert_array_equal(out_b, out_s)


def test_batched_prefill_single_dispatch():
    """Batched prefill is O(1) jitted calls per prompt; stepped is O(S0)."""
    cfg = small_cfg()
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    prompts = _prompts(cfg.vocab, s0=8)
    eng = Engine(params, cfg, ServeConfig(max_seq=48))  # auto -> batched
    eng.generate(prompts, 4)
    assert eng.prefill_calls == 1
    assert eng.decode_calls == 4
    eng_s = Engine(params, cfg, ServeConfig(max_seq=48, prefill_mode="stepped"))
    eng_s.generate(prompts, 4)
    assert eng_s.prefill_calls == 8


def test_batched_prefill_with_packed_awdbb_weights():
    """Fused path end-to-end: packed weights + packed activation hand-off
    under batched prefill == the stepped per-token path, token-exact."""
    cfg = small_cfg(sparsity=dataclasses.replace(
        configs.get_config("granite_3_8b", smoke=True).sparsity, mode="awdbb"))
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(1))
    prompts = _prompts(cfg.vocab, s0=6, seed=1)
    kw = dict(max_seq=32, pack_weights=True)
    out_b = Engine(params, cfg, ServeConfig(prefill_mode="batched", **kw)).generate(prompts, 6)
    out_s = Engine(params, cfg, ServeConfig(prefill_mode="stepped", **kw)).generate(prompts, 6)
    np.testing.assert_array_equal(out_b, out_s)


def test_int8_wire_serving_token_stable_vs_native():
    """INT8 wire serving (int8 weights and per-token int8 activations,
    int32 accumulate, fused dequant) stays within a bound of the
    native-dtype wire on the logits of the same tokens.

    Bound: per token, ||logits_int8 - logits_native|| <= 0.1 *
    ||logits_native||.  Each int8 operand rounds to half a step of
    amax/127 — about 0.5% RMS relative error per operand on Gaussian
    data — and the tiny model runs about nine quantized matmuls in
    sequence (4 per layer + the head), which measures 2-4% here; a
    wiring fault (a wrong scale or channel) shows up at order 1.  The
    model is W-DBB (dense activations): under A/W-DBB, DAP's top-4 of 8
    selection is discontinuous, so 0.5% noise that reorders two
    near-equal activations swaps whole terms and the wires legitimately
    differ by tens of percent — no bound a test can pin (argmax tokens
    from random weights flip for the same reason)."""
    cfg = small_cfg(sparsity=dataclasses.replace(
        configs.get_config("granite_3_8b", smoke=True).sparsity, mode="wdbb"))
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(4))
    tokens = jnp.asarray(_prompts(cfg.vocab, s0=6, seed=9))
    kw = dict(max_seq=32, pack_weights=True)
    logits = {}
    for wire in ("native", "int8"):
        eng = Engine(params, cfg, ServeConfig(wire_dtype=wire, **kw))
        out, _ = lm.forward(eng.params, tokens, eng.cfg)
        logits[wire] = np.asarray(out[..., : cfg.vocab], np.float64)
    err = np.linalg.norm(logits["int8"] - logits["native"], axis=-1)
    ref = np.linalg.norm(logits["native"], axis=-1)
    assert np.all(err <= 0.1 * ref), (err / ref).max()


def test_int8_wire_serving_deterministic():
    """The int8 path is deterministic: two engines over the same params
    produce identical tokens (dynamic act scales are data-dependent but
    pure functions of the input)."""
    cfg = small_cfg(sparsity=dataclasses.replace(
        configs.get_config("granite_3_8b", smoke=True).sparsity, mode="awdbb"))
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(2))
    prompts = _prompts(cfg.vocab, s0=5, seed=2)
    kw = dict(max_seq=32, pack_weights=True, wire_dtype="int8")
    out_a = Engine(params, cfg, ServeConfig(**kw)).generate(prompts, 6)
    out_b = Engine(params, cfg, ServeConfig(**kw)).generate(prompts, 6)
    np.testing.assert_array_equal(out_a, out_b)
    assert out_a.shape == (2, 11)


def test_prefill_is_single_pass():
    """lm.prefill runs the layer stack ONCE: with cache, each layer's
    decoder block executes exactly one time (the block fills its own
    K/V ring in-pass — no forward-then-recompute double scan)."""
    from repro.models import blocks

    cfg = small_cfg()
    cfg = dataclasses.replace(cfg, scan_layers=False)  # count real calls
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(_prompts(cfg.vocab, b=1, s0=4))
    cache = lm.make_cache(cfg, 1, 16)
    calls = {"n": 0}
    orig = blocks.decoder_block

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    blocks.decoder_block = counting
    try:
        logits, new_cache = lm.prefill(params, toks, cfg, cache=cache)
    finally:
        blocks.decoder_block = orig
    assert calls["n"] == cfg.n_layers  # seed design traced 2 * n_layers
    # and the single-pass logits match the plain forward pass exactly
    ref_logits = lm.prefill(params, toks, cfg)
    np.testing.assert_array_equal(np.array(logits), np.array(ref_logits))
    # cache got filled (positions 0..3 recorded)
    np.testing.assert_array_equal(
        np.array(new_cache["pos"][0, 0, :4]), np.arange(4)
    )


def test_hybrid_prefill_fills_attention_ring():
    """Hybrid single-pass prefill fills the attention ring through the
    same gqa prefill-fill path as dense families (the recurrent state
    passes through untouched), matching what per-token stepping writes
    up to fp reduction order."""
    cfg = small_cfg("hymba_1_5b")
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(_prompts(cfg.vocab, b=1, s0=5))
    _, c_fill = lm.prefill(params, toks, cfg, cache=lm.make_cache(cfg, 1, 16))
    c_step = lm.make_cache(cfg, 1, 16)
    for t in range(5):
        _, c_step = lm.decode_step(
            params, c_step, toks[:, t : t + 1], jnp.int32(t), cfg
        )
    np.testing.assert_array_equal(
        np.array(c_fill["pos"]), np.array(c_step["pos"])
    )
    np.testing.assert_allclose(
        np.array(c_fill["k"]), np.array(c_step["k"]), atol=2e-3
    )
    np.testing.assert_allclose(
        np.array(c_fill["v"]), np.array(c_step["v"]), atol=2e-3
    )
    # recurrent state untouched by the fill (engines step hybrids)
    np.testing.assert_array_equal(np.array(c_fill["ssm_state"]), 0.0)


def test_wire_dtype_validation():
    """wire_dtype='int8' without packing must fail loudly, not silently
    serve full precision; unknown wire dtypes are rejected."""
    cfg = small_cfg()
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="pack_weights"):
        Engine(params, cfg, ServeConfig(wire_dtype="int8"))
    with pytest.raises(ValueError, match="wire_dtype"):
        Engine(params, cfg, ServeConfig(wire_dtype="int-8", pack_weights=True))


# --------------------------------------------- continuous batching (paged KV)

# one smoke arch per BATCHED_PREFILL_FAMILIES member, plus the MLA
# variant of "dense" (its latent cache pages differently than GQA)
CONTINUOUS_ARCHS = (
    "granite_3_8b",         # dense / GQA
    "minicpm3_4b",          # dense / MLA latent cache
    "granite_moe_1b_a400m", # moe
    "qwen2_vl_72b",         # vlm (M-RoPE positions)
)


def _wire_kwargs(wire):
    return dict(pack_weights=True, wire_dtype="int8") if wire == "int8" else {}


@pytest.mark.parametrize("arch", CONTINUOUS_ARCHS)
@pytest.mark.parametrize("wire", ["native", "int8"])
def test_continuous_matches_stepped_per_request(arch, wire):
    """Token-exactness parity: continuous-batched decode with staggered
    arrivals, mixed prompt lengths, queueing beyond max_batch and page
    recycling emits byte-identical tokens per request vs the solo
    stepped engine.  Exactness under the int8 wire comes from the
    continuous path's per-row dynamic activation scales: the int8
    datapath is integer-exact, so per-token scales decouple a request
    from its co-batch entirely."""
    cfg = small_cfg(arch)
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompts = [
        rng.integers(0, cfg.vocab, (s,)).astype(np.int32) for s in (9, 5, 12)
    ]
    wkw = _wire_kwargs(wire)
    eng = Engine(params, cfg, ServeConfig(
        prefill_mode="continuous", max_seq=32,
        page_size=8, max_batch=2, prefill_chunk=4, **wkw,
    ))
    outs = eng.generate_requests(prompts, 6, arrivals=[0, 3, 1])
    ref = Engine(params, cfg, ServeConfig(max_seq=32, prefill_mode="stepped", **wkw))
    for i, prompt in enumerate(prompts):
        np.testing.assert_array_equal(
            outs[i], ref.generate(prompt[None], 6)[0],
            err_msg=f"request {i} diverged from its solo stepped run",
        )


def test_continuous_generate_matches_batched_api():
    """Engine.generate(prefill_mode='continuous') returns the same
    [B, S0+n] layout as the other modes, token-identical to stepped."""
    cfg = small_cfg()
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    prompts = _prompts(cfg.vocab, b=3, s0=8)
    kw = dict(max_seq=48, page_size=8, max_batch=3, prefill_chunk=4)
    out_c = Engine(
        params, cfg, ServeConfig(prefill_mode="continuous", **kw)
    ).generate(prompts, 8)
    out_s = Engine(
        params, cfg, ServeConfig(max_seq=48, prefill_mode="stepped")
    ).generate(prompts, 8)
    assert out_c.shape == (3, 16)
    np.testing.assert_array_equal(out_c, out_s)


def test_continuous_interleaves_prefill_with_decode():
    """Chunked prefill must not stall in-flight decodes: with a long
    prompt arriving mid-decode, the short request keeps emitting one
    token per iteration while the long prompt streams through in
    chunks — total steps stay near max(prefill_chunks + decodes) rather
    than their serialized sum."""
    cfg = small_cfg()
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    short = rng.integers(0, cfg.vocab, (4,)).astype(np.int32)
    long = rng.integers(0, cfg.vocab, (24,)).astype(np.int32)
    eng = Engine(params, cfg, ServeConfig(
        prefill_mode="continuous", max_seq=40,
        page_size=8, max_batch=2, prefill_chunk=4,
    ))
    outs = eng.generate_requests([short, long], [12, 4], arrivals=[0, 2])
    # short: 1 prefill chunk + 11 decode steps; long: 6 chunks + 3 decodes,
    # admitted at iteration 2 — interleaved upper bound, not the sum
    assert eng.step_calls <= 13
    ref = Engine(params, cfg, ServeConfig(max_seq=40, prefill_mode="stepped"))
    np.testing.assert_array_equal(outs[0], ref.generate(short[None], 12)[0])
    np.testing.assert_array_equal(outs[1], ref.generate(long[None], 4)[0])


@pytest.mark.parametrize("wire", ["native", "int8"])
def test_continuous_batch_invariance(wire):
    """A request's continuous-mode tokens do not depend on which
    requests it is co-batched with (native: row-independent math; int8:
    per-row dynamic scales make the integer-exact path row-independent
    too)."""
    cfg = small_cfg()
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    a = rng.integers(0, cfg.vocab, (8,)).astype(np.int32)
    kw = dict(
        prefill_mode="continuous", max_seq=32,
        page_size=8, max_batch=3, prefill_chunk=4, **_wire_kwargs(wire),
    )
    solo = Engine(params, cfg, ServeConfig(**kw)).generate_requests([a], 8)[0]
    for seed in (100, 101):
        oth = np.random.default_rng(seed).integers(
            0, cfg.vocab, (2, 8)
        ).astype(np.int32)
        co = Engine(params, cfg, ServeConfig(**kw)).generate_requests(
            [a, oth[0], oth[1]], 8
        )[0]
        np.testing.assert_array_equal(solo, co)


def test_batched_prefill_batch_invariance_native():
    """One-shot batched prefill is batch-invariant on the native wire."""
    cfg = small_cfg()
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    a = rng.integers(0, cfg.vocab, (8,)).astype(np.int32)
    scfg = ServeConfig(max_seq=32, prefill_mode="batched")
    solo = Engine(params, cfg, scfg).generate(a[None], 8)[0]
    oth = np.random.default_rng(100).integers(0, cfg.vocab, (3, 8)).astype(np.int32)
    co = Engine(params, cfg, scfg).generate(
        np.concatenate([a[None], oth], 0), 8
    )[0]
    np.testing.assert_array_equal(solo, co)


def test_batched_prefill_batch_invariance_int8():
    """One-shot batched prefill is batch-invariant on the int8 wire: the
    engine forces per-row (per-token) dynamic activation scales on EVERY
    wire_dtype='int8' path, so each token quantizes on its own amax and
    the integer-exact datapath decouples co-batched requests (this was
    the ROADMAP's per-tensor-scale violation, formerly a documented
    xfail)."""
    cfg = small_cfg()
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    a = rng.integers(0, cfg.vocab, (8,)).astype(np.int32)
    scfg = ServeConfig(
        max_seq=32, prefill_mode="batched", pack_weights=True, wire_dtype="int8"
    )
    solo = Engine(params, cfg, scfg).generate(a[None], 8)[0]
    oth = np.random.default_rng(100).integers(0, cfg.vocab, (3, 8)).astype(np.int32)
    co = Engine(params, cfg, scfg).generate(
        np.concatenate([a[None], oth], 0), 8
    )[0]
    np.testing.assert_array_equal(solo, co)


# ------------------------------------------- fused paged-attention kernel

# The continuous==stepped byte-identity guarantee must hold on BOTH
# paged-attention implementations: "gather" (paged_read + mha) and
# "fused" (the in-kernel page-table walk, kernels/paged_attn.py — run
# through the Pallas interpreter on CPU).  The fused path regroups the
# softmax reductions (online rescaling), so this is an fp-parity claim
# at the token level, pinned by seed like the rest of the suite; the
# kernel-level tolerance story lives in tests/test_paged_attn.py.


@pytest.mark.parametrize("arch", CONTINUOUS_ARCHS)
def test_continuous_fused_matches_stepped_per_request(arch):
    """ServeConfig(paged_attn='fused'): continuous decode over the
    in-kernel page walk — staggered arrivals, mixed lengths, chunked
    prefill, page recycling — still emits the solo stepped engine's
    tokens per request, for every continuous-capable family (GQA, the
    MLA latent path, MoE, VLM/M-RoPE)."""
    cfg = small_cfg(arch)
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompts = [
        rng.integers(0, cfg.vocab, (s,)).astype(np.int32) for s in (9, 5, 12)
    ]
    eng = Engine(params, cfg, ServeConfig(
        prefill_mode="continuous", max_seq=32,
        page_size=8, max_batch=2, prefill_chunk=4, paged_attn="fused",
    ))
    outs = eng.generate_requests(prompts, 6, arrivals=[0, 3, 1])
    ref = Engine(params, cfg, ServeConfig(max_seq=32, prefill_mode="stepped"))
    for i, prompt in enumerate(prompts):
        np.testing.assert_array_equal(
            outs[i], ref.generate(prompt[None], 6)[0],
            err_msg=f"request {i} diverged from stepped on the fused path",
        )


@pytest.mark.parametrize("arch", ["granite_3_8b", "minicpm3_4b"])
def test_int8_kv_fused_token_identical_to_gather(arch):
    """Under the int8-KV wire the two paged implementations read the
    SAME stored bytes (write-side quantization is shared; the kernel's
    fused dequant mirrors paged_read elementwise), so fused continuous
    serving is token-identical to gather continuous serving — and both
    match the solo stepped int8-KV engine."""
    cfg = small_cfg(arch)
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompts = [
        rng.integers(0, cfg.vocab, (s,)).astype(np.int32) for s in (9, 5, 12)
    ]
    kw = dict(
        prefill_mode="continuous", max_seq=32,
        page_size=8, max_batch=2, prefill_chunk=4, kv_dtype="int8",
    )
    outs_f = Engine(params, cfg, ServeConfig(paged_attn="fused", **kw)
                    ).generate_requests(prompts, 6, arrivals=[0, 3, 1])
    outs_g = Engine(params, cfg, ServeConfig(paged_attn="gather", **kw)
                    ).generate_requests(prompts, 6, arrivals=[0, 3, 1])
    ref = Engine(params, cfg, ServeConfig(
        max_seq=32, prefill_mode="stepped", kv_dtype="int8"
    ))
    for i, prompt in enumerate(prompts):
        np.testing.assert_array_equal(
            outs_f[i], outs_g[i],
            err_msg=f"request {i}: fused != gather under int8 KV",
        )
        np.testing.assert_array_equal(
            outs_f[i], ref.generate(prompt[None], 6)[0],
            err_msg=f"request {i}: fused int8-KV != stepped",
        )


def test_fused_stacks_with_int8_wire():
    """paged_attn='fused' composes with the full int8 stack (weights +
    activations + KV all int8): continuous tokens match the stepped
    engine within the combined wire."""
    cfg = small_cfg(sparsity=dataclasses.replace(
        configs.get_config("granite_3_8b", smoke=True).sparsity, mode="awdbb"))
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, (s,)).astype(np.int32) for s in (9, 5)]
    wkw = dict(pack_weights=True, wire_dtype="int8", kv_dtype="int8")
    eng = Engine(params, cfg, ServeConfig(
        prefill_mode="continuous", max_seq=32,
        page_size=8, max_batch=2, prefill_chunk=4, paged_attn="fused", **wkw,
    ))
    outs = eng.generate_requests(prompts, 6)
    ref = Engine(params, cfg, ServeConfig(
        max_seq=32, prefill_mode="stepped", **wkw
    ))
    for i, prompt in enumerate(prompts):
        np.testing.assert_array_equal(
            outs[i], ref.generate(prompt[None], 6)[0],
            err_msg=f"request {i} diverged under fused + full int8 stack",
        )


def test_paged_attn_knob_validation():
    """Unknown paged_attn values fail loudly at construction, at both
    the serving and the sparsity layer."""
    with pytest.raises(ValueError, match="paged_attn"):
        ServeConfig(paged_attn="pallas")
    from repro.core.sparsity import SparsityConfig

    with pytest.raises(ValueError, match="paged_attn"):
        SparsityConfig(paged_attn="window")
    # the engine threads the knob into the effective model config
    cfg = small_cfg()
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    eng = Engine(params, cfg, ServeConfig(
        prefill_mode="continuous", paged_attn="fused", max_seq=32,
        page_size=8,
    ))
    assert eng.cfg.sparsity.paged_attn == "fused"


def test_serve_config_validation():
    """page_size/max_pages/max_seq coherence fails loudly at construction
    with actionable messages."""
    with pytest.raises(ValueError, match="page_size"):
        ServeConfig(page_size=0)
    with pytest.raises(ValueError, match="max_seq"):
        ServeConfig(max_seq=0)
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServeConfig(prefill_chunk=0)
    with pytest.raises(ValueError, match="max_batch"):
        ServeConfig(max_batch=0)
    # max_pages too small to hold even one max_seq request
    with pytest.raises(ValueError, match="null page"):
        ServeConfig(max_seq=64, page_size=8, max_pages=8)
    # exactly enough (8 data pages + null) is fine, and derived totals
    scfg = ServeConfig(max_seq=64, page_size=8, max_pages=9)
    assert scfg.pages_per_request == 8
    assert scfg.total_pages == 9
    assert ServeConfig(max_seq=64, page_size=8, max_batch=2).total_pages == 17


def test_continuous_rejects_oversized_and_recurrent():
    """Requests that cannot fit max_seq fail loudly before any compute;
    recurrent families cannot run continuous mode at all."""
    cfg = small_cfg()
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    eng = Engine(params, cfg, ServeConfig(
        prefill_mode="continuous", max_seq=16, page_size=8, max_batch=2,
    ))
    big = np.zeros((14,), np.int32)
    with pytest.raises(ValueError, match="max_seq"):
        eng.generate_requests([big], 4)
    hy_cfg = small_cfg("hymba_1_5b")
    hy_params, _ = lm.init_lm(hy_cfg, jax.random.PRNGKey(0))
    bad = Engine(hy_params, hy_cfg, ServeConfig(prefill_mode="continuous"))
    with pytest.raises(ValueError, match="recurrent"):
        bad.generate(np.zeros((1, 4), np.int32), 1)


# ------------------------------------------------------------ int8 KV cache

# 1-layer / small-vocab variants for the cross-wire parity tests: deeper
# random-weight stacks amplify the ~0.4% per-row KV quantization error
# into argmax flips on near-tied logits (a property of the tiny test
# models, not of the wire), so parity vs f32-KV is asserted as a bounded
# aggregate mismatch fraction on calmer 1-layer configs.  Exactness
# WITHIN the int8-KV wire (continuous == stepped == batched) needs no
# such allowance and is byte-identical on the standard 2-layer configs.
KV_PARITY_TOL = 0.25  # measured: <= 0.16 aggregate mismatch over 8 seeds


def _kv_parity_cfg(arch):
    return small_cfg(arch, n_layers=1, vocab=32)


@pytest.mark.parametrize("arch", ["granite_3_8b", "minicpm3_4b"])
def test_int8_kv_one_shot_token_parity_vs_f32(arch):
    """GQA and MLA one-shot serving with the int8 KV cache emits (almost
    always) the f32-KV engine's greedy tokens; mismatches stay under the
    documented tolerance across seeds."""
    cfg = _kv_parity_cfg(arch)
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    e_f = Engine(params, cfg, ServeConfig(max_seq=32, prefill_mode="batched"))
    e_8 = Engine(params, cfg, ServeConfig(
        max_seq=32, prefill_mode="batched", kv_dtype="int8"
    ))
    tot = mis = 0
    for seed in range(8):
        prompts = _prompts(cfg.vocab, b=2, s0=6, seed=seed)
        out_f = e_f.generate(prompts, 4)[:, 6:]
        out_8 = e_8.generate(prompts, 4)[:, 6:]
        mis += int((out_f != out_8).sum())
        tot += out_f.size
    assert mis / tot <= KV_PARITY_TOL, f"{mis}/{tot} tokens diverged"


@pytest.mark.parametrize("arch", ["granite_3_8b", "minicpm3_4b"])
def test_int8_kv_continuous_token_parity_vs_f32(arch):
    """Continuous (paged) int8-KV serving holds the same cross-wire token
    parity bound vs the f32-KV continuous engine."""
    cfg = _kv_parity_cfg(arch)
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    kw = dict(
        prefill_mode="continuous", max_seq=32,
        page_size=8, max_batch=2, prefill_chunk=4,
    )
    e_f = Engine(params, cfg, ServeConfig(**kw))
    e_8 = Engine(params, cfg, ServeConfig(kv_dtype="int8", **kw))
    tot = mis = 0
    for seed in range(8):
        rng = np.random.default_rng(seed)
        pr = [rng.integers(0, cfg.vocab, (s,)).astype(np.int32) for s in (9, 5)]
        out_f = e_f.generate_requests(pr, 4)
        out_8 = e_8.generate_requests(pr, 4)
        mis += sum(int((out_f[i][-4:] != out_8[i][-4:]).sum()) for i in range(2))
        tot += 8
    assert mis / tot <= KV_PARITY_TOL, f"{mis}/{tot} tokens diverged"


@pytest.mark.parametrize("arch", ["granite_3_8b", "minicpm3_4b"])
def test_int8_kv_batched_prefill_matches_stepped(arch):
    """WITHIN the int8-KV wire, one-shot batched prefill is byte-identical
    to stepped serving: prefill attends over the same quantization
    round-trip the ring stores (attention.kv_roundtrip), so batched and
    stepped read the same cache bytes."""
    cfg = small_cfg(arch)
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    prompts = _prompts(cfg.vocab)
    kw = dict(max_seq=48, kv_dtype="int8")
    out_b = Engine(params, cfg, ServeConfig(prefill_mode="batched", **kw)).generate(prompts, 8)
    out_s = Engine(params, cfg, ServeConfig(prefill_mode="stepped", **kw)).generate(prompts, 8)
    np.testing.assert_array_equal(out_b, out_s)


@pytest.mark.parametrize("arch", ["granite_3_8b", "minicpm3_4b"])
def test_int8_kv_continuous_matches_stepped(arch):
    """WITHIN the int8-KV wire, continuous-batched decode (staggered
    arrivals, mixed lengths, page recycling) stays byte-identical per
    request vs the solo stepped engine: ring and paged backends write the
    same per-token quantization and read it back through the same
    dequant, so the paged-KV exactness guarantee survives quantized
    storage untouched."""
    cfg = small_cfg(arch)
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompts = [
        rng.integers(0, cfg.vocab, (s,)).astype(np.int32) for s in (9, 5, 12)
    ]
    eng = Engine(params, cfg, ServeConfig(
        prefill_mode="continuous", max_seq=32,
        page_size=8, max_batch=2, prefill_chunk=4, kv_dtype="int8",
    ))
    outs = eng.generate_requests(prompts, 6, arrivals=[0, 3, 1])
    ref = Engine(params, cfg, ServeConfig(
        max_seq=32, prefill_mode="stepped", kv_dtype="int8"
    ))
    for i, prompt in enumerate(prompts):
        np.testing.assert_array_equal(
            outs[i], ref.generate(prompt[None], 6)[0],
            err_msg=f"request {i} diverged from its solo stepped int8-KV run",
        )


def test_int8_kv_stacks_with_int8_wire():
    """kv_dtype='int8' composes with wire_dtype='int8' (weights +
    activations + KV all int8): continuous stays byte-identical to the
    stepped engine within the combined wire."""
    cfg = small_cfg(sparsity=dataclasses.replace(
        configs.get_config("granite_3_8b", smoke=True).sparsity, mode="awdbb"))
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(3)
    prompts = [
        rng.integers(0, cfg.vocab, (s,)).astype(np.int32) for s in (9, 5, 12)
    ]
    wkw = dict(pack_weights=True, wire_dtype="int8", kv_dtype="int8")
    eng = Engine(params, cfg, ServeConfig(
        prefill_mode="continuous", max_seq=32,
        page_size=8, max_batch=2, prefill_chunk=4, **wkw,
    ))
    outs = eng.generate_requests(prompts, 6, arrivals=[0, 3, 1])
    ref = Engine(params, cfg, ServeConfig(
        max_seq=32, prefill_mode="stepped", **wkw
    ))
    for i, prompt in enumerate(prompts):
        np.testing.assert_array_equal(
            outs[i], ref.generate(prompt[None], 6)[0],
            err_msg=f"request {i} diverged under int8 wire + int8 KV",
        )


def test_int8_kv_hybrid_stepped_serving():
    """Hybrid (attention ring + recurrent state) serves stepped with the
    int8 KV cache: only the attention ring quantizes, the run is
    deterministic, and tokens stay within the cross-wire tolerance of
    the f32-KV engine."""
    cfg = small_cfg("hymba_1_5b")
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    prompts = _prompts(cfg.vocab, s0=6, seed=1)
    out_f = Engine(params, cfg, ServeConfig(max_seq=48)).generate(prompts, 4)
    e8 = Engine(params, cfg, ServeConfig(max_seq=48, kv_dtype="int8"))
    out_8 = e8.generate(prompts, 4)
    assert out_8.shape == out_f.shape
    np.testing.assert_array_equal(np.array(out_8[:, :6]), prompts)
    # deterministic: a second engine reproduces the tokens exactly
    out_8b = Engine(
        params, cfg, ServeConfig(max_seq=48, kv_dtype="int8")
    ).generate(prompts, 4)
    np.testing.assert_array_equal(out_8, out_8b)
    frac = float((out_f[:, 6:] != out_8[:, 6:]).mean())
    assert frac <= 0.5, f"hybrid int8-KV diverged on {frac:.0%} of tokens"


def test_auto_mode_falls_back_for_recurrent_families():
    """SSM/hybrid have no exact one-shot cache fill: auto must step."""
    cfg = small_cfg("hymba_1_5b")
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    prompts = _prompts(cfg.vocab, s0=6)
    eng = Engine(params, cfg, ServeConfig(max_seq=48))
    out = eng.generate(prompts, 4)
    assert eng.prefill_calls == 6  # stepped
    assert out.shape == (2, 10)
    # forcing batched on a recurrent family must fail loudly, not decode
    # from a zeroed state
    bad = Engine(params, cfg, ServeConfig(max_seq=48, prefill_mode="batched"))
    with pytest.raises(ValueError, match="recurrent"):
        bad.generate(prompts, 1)


# -------------------------------------- shared-prefix caching (byte-exact)


def _prefix_workload(vocab, ps=8, seed=11):
    """Three prompts sharing a 2-full-page (16-token) system prefix with
    distinct tails — the canonical shared-system-prompt workload."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, (2 * ps,)).astype(np.int32)
    tails = [rng.integers(0, vocab, (t,)).astype(np.int32) for t in (3, 6, 1)]
    return [np.concatenate([prefix, t]) for t in tails]


def _serve_kwargs(wire, kv):
    kw = dict(
        prefill_mode="continuous", max_seq=48,
        page_size=8, max_batch=2, prefill_chunk=4,
    )
    kw.update(_wire_kwargs(wire))
    if kv == "int8":
        kw["kv_dtype"] = "int8"
    return kw


@pytest.mark.parametrize("arch", ["granite_3_8b", "minicpm3_4b"])
@pytest.mark.parametrize("wire", ["native", "int8"])
@pytest.mark.parametrize("kv", ["native", "int8"])
def test_shared_prefix_byte_identical_to_cold_start(arch, wire, kv):
    """Prefix-cache hits must be invisible in the tokens: a prompt whose
    leading pages are adopted from an earlier request decodes
    byte-identically to a cold start, across GQA/MLA, the int8 weight
    wire, and the int8 KV cache (stored pages are reused as BYTES, so
    quantized caches hit exactly like f32 ones)."""
    cfg = small_cfg(arch)
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    prompts = _prefix_workload(cfg.vocab)
    kw = _serve_kwargs(wire, kv)
    cold = Engine(params, cfg, ServeConfig(prefix_cache=False, **kw))
    cold_outs = cold.generate_requests(prompts, 5)
    warm = Engine(params, cfg, ServeConfig(**kw))
    # seed the cache, then serve the sharing prompts in a second call
    np.testing.assert_array_equal(
        warm.generate_requests(prompts[:1], 5)[0], cold_outs[0]
    )
    warm_outs = warm.generate_requests(prompts[1:], 5)
    stats = warm.prefix_stats()
    assert stats["page_hits"] > 0, "workload never hit the prefix cache"
    assert stats["prefill_tokens_saved"] >= 2 * 16  # both shared pages
    for i in (1, 2):
        np.testing.assert_array_equal(
            warm_outs[i - 1], cold_outs[i],
            err_msg=f"request {i} diverged after a prefix-cache hit "
                    f"({arch}, wire={wire}, kv={kv})",
        )


@pytest.mark.parametrize("arch", ["granite_3_8b", "minicpm3_4b"])
def test_shared_prefix_byte_identical_fused(arch):
    """Same guarantee under the fused in-kernel page walk with the int8
    KV wire: shared pages are safe to read through the Pallas kernel's
    page-table traversal (page ids may repeat across rows)."""
    cfg = small_cfg(arch)
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    prompts = _prefix_workload(cfg.vocab)
    kw = _serve_kwargs("native", "int8")
    kw["paged_attn"] = "fused"
    cold_outs = Engine(
        params, cfg, ServeConfig(prefix_cache=False, **kw)
    ).generate_requests(prompts, 5)
    warm = Engine(params, cfg, ServeConfig(**kw))
    warm.generate_requests(prompts[:1], 5)
    warm_outs = warm.generate_requests(prompts[1:], 5)
    assert warm.prefix_stats()["page_hits"] > 0
    for i in (1, 2):
        np.testing.assert_array_equal(warm_outs[i - 1], cold_outs[i])


def test_shared_prefix_full_hit_triggers_cow():
    """A prompt FULLY covered by cached pages recomputes only its last
    token; that write diverges inside a shared page and must
    copy-on-write — the original request's pages stay byte-identical
    (its re-decode still matches) and exactly one duplication happens."""
    cfg = small_cfg()
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    prompt = np.random.default_rng(7).integers(0, cfg.vocab, (16,)).astype(np.int32)
    eng = Engine(params, cfg, ServeConfig(**_serve_kwargs("native", "native")))
    cold = eng.generate_requests([prompt], 5)[0]
    warm = eng.generate_requests([prompt], 5)[0]  # full-prefix hit
    np.testing.assert_array_equal(cold, warm)
    alloc = eng._cont["allocator"]
    assert alloc.cow_count == 1, "full-prefix hit should CoW exactly once"
    assert eng.prefix_stats()["prefill_tokens_saved"] == 15  # s0 - 1
    # third pass: unchanged entries, same tokens again
    np.testing.assert_array_equal(eng.generate_requests([prompt], 5)[0], cold)


def test_prefix_cache_disabled_is_cold_every_call():
    cfg = small_cfg()
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    prompt = np.random.default_rng(8).integers(0, cfg.vocab, (16,)).astype(np.int32)
    eng = Engine(params, cfg, ServeConfig(
        prefix_cache=False, **{k: v for k, v in _serve_kwargs("native", "native").items()
                               if k != "prefix_cache"}
    ))
    a = eng.generate_requests([prompt], 4)[0]
    b = eng.generate_requests([prompt], 4)[0]
    np.testing.assert_array_equal(a, b)
    stats = eng.prefix_stats()
    assert stats["page_hits"] == 0 and stats["prefill_tokens_saved"] == 0


# ------------------------------------------- step-loop shape discipline


def test_continuous_compiles_exactly_two_traces():
    """The bucketed plan shapes hold the continuous loop to TWO compiled
    model traces — one mixed [B, prefill_chunk] step, one fused decode
    loop — across mixed prompt lengths, staggered arrivals, queue churn,
    varying run lengths, and repeated generate_requests calls."""
    cfg = small_cfg()
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    eng = Engine(params, cfg, ServeConfig(
        prefill_mode="continuous", max_seq=32,
        page_size=8, max_batch=2, prefill_chunk=4, decode_block=8,
    ))
    prompts = [
        rng.integers(0, cfg.vocab, (s,)).astype(np.int32) for s in (9, 5, 12)
    ]
    eng.generate_requests(prompts, 6, arrivals=[0, 3, 1])
    eng.generate_requests(prompts[:2], 3)
    eng.generate_requests([prompts[2]], 9)
    assert eng.paged_compiles == 2, (
        f"continuous loop compiled {eng.paged_compiles} traces; the "
        "bucketing policy promises 2 (docs/serving.md)"
    )
    assert eng.decode_run_calls > 0 and eng.fused_tokens > 0


def test_decode_block_one_matches_larger_blocks():
    """decode_block=1 (one dispatch per token) and decode_block=16 (fused
    runs) are the same math: byte-identical outputs."""
    cfg = small_cfg()
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    prompts = _prefix_workload(cfg.vocab)
    kw = dict(prefill_mode="continuous", max_seq=48, page_size=8,
              max_batch=2, prefill_chunk=4, prefix_cache=False)
    out_1 = Engine(params, cfg, ServeConfig(decode_block=1, **kw)).generate_requests(prompts, 6)
    out_16 = Engine(params, cfg, ServeConfig(decode_block=16, **kw)).generate_requests(prompts, 6)
    for a, b in zip(out_1, out_16):
        np.testing.assert_array_equal(a, b)


# ----------------------------------------------------------- streaming


def _collect_stream(store, rid, toks, start):
    buf = store.setdefault(rid, [])
    assert start == len(buf), (rid, start, len(buf))
    buf.extend(int(t) for t in toks)


def test_streaming_matches_final_output():
    """``on_token`` delivers exactly the committed output stream —
    in-order, gapless, byte-equal to the final tokens — and honors
    per-request callbacks including ``None`` holes."""
    cfg = small_cfg()
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    prompts = _prefix_workload(cfg.vocab)
    eng = Engine(params, cfg, ServeConfig(
        temperature=0.8, seed=5, **_serve_kwargs("native", "native")
    ))
    streamed = {}

    def cb(rid, toks, start):
        _collect_stream(streamed, rid, toks, start)

    res = eng.serve_requests(prompts, 8, on_token=[cb, None, cb])
    assert sorted(streamed) == sorted([res[0].rid, res[2].rid])
    for r in (res[0], res[2]):
        gen = [int(t) for t in r.tokens[len(r.tokens) - r.n_generated:]]
        assert streamed[r.rid] == gen


def test_streaming_survives_preempt_and_recompute():
    """A preempted-and-recomputed request re-derives the same bytes and
    streams only PAST what it already delivered: the consumer never sees
    a rewind, a duplicate, or a gap."""
    cfg = small_cfg()
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    prompts = [
        rng.integers(0, cfg.vocab, (s,)).astype(np.int32)
        for s in (9, 5, 12, 7)
    ]
    eng = Engine(params, cfg, ServeConfig(
        prefill_mode="continuous", prefill_chunk=4, max_seq=24,
        page_size=4, max_batch=3, max_pages=13, preempt_after=2,
    ))
    streamed = {}
    res = eng.serve_requests(
        prompts, 10,
        on_token=lambda rid, t, s: _collect_stream(streamed, rid, t, s),
    )
    assert eng.health()["preemptions"] > 0, "pool never forced a preempt"
    for r in res:
        gen = [int(t) for t in r.tokens[len(r.tokens) - r.n_generated:]]
        assert streamed.get(r.rid, []) == gen


def test_streaming_stops_at_stop_token():
    """Committed tokens are post-truncation: the stream ends exactly at
    the stop token, never leaking sampled-but-discarded tail tokens."""
    cfg = small_cfg()
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    prompts = _prefix_workload(cfg.vocab)
    kw = _serve_kwargs("native", "native")
    ref = Engine(params, cfg, ServeConfig(**kw)).generate_requests(prompts, 8)
    gen0 = [int(t) for t in ref[0][len(prompts[0]):]]
    stop = gen0[3]
    eng = Engine(params, cfg, ServeConfig(**kw))
    streamed = {}
    res = eng.serve_requests(
        prompts, 8, stop_tokens=[[stop], [], []],
        on_token=lambda rid, t, s: _collect_stream(streamed, rid, t, s),
    )
    r0 = res[0]
    assert r0.finish_reason == "stop"
    assert streamed[r0.rid] == gen0[: gen0.index(stop) + 1]


def test_streaming_rejects_non_callable():
    cfg = small_cfg()
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    eng = Engine(params, cfg, ServeConfig(**_serve_kwargs("native", "native")))
    with pytest.raises(ValueError, match="on_token"):
        eng.generate_requests(_prefix_workload(cfg.vocab), 4, on_token=42)
