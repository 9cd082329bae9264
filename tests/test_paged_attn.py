"""Fused paged-attention kernel parity suite.

The Pallas kernel (``kernels/paged_attn.py``, run here in **interpret
mode** — the same body CPU serving executes) must match the gather path
(``attention.paged_read`` + ``mha`` / absorbed MLA) over adversarial
page layouts: null-page padding, recycled-then-scrubbed pages holding
stale garbage, and mixed per-request positions — across
{GQA, MLA} × {f32, int8} KV wires, plus sliding-window masking and the
bf16 compute-dtype boundary.  The jnp oracle (``ref.paged_attn_ref``)
mirrors the kernel's online-softmax block tiling and is held to the same
bound.  CI runs this file as a dedicated interpret-mode step so a
TPU-only regression cannot hide behind the gather fallback.

Tolerances: the fused path regroups the softmax reductions per block
(flash-style rescaling), so float parity is fp-rounding-bounded
(~1e-6), not bit-exact — token-level serving parity is asserted in
``tests/test_serve.py``.  Comparisons cover valid query rows only:
padding rows (``q_pos = -1``) are fully masked and both paths emit
garbage the scheduler never samples.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypo import given, settings, st  # hypothesis-or-skip shim

from repro.core import quant
from repro.kernels import autotune, paged_attn, ref
from repro.kernels.paged_attn import paged_attn_fused
from repro.models import attention


def _rand(rng, shape, scale=1.0):
    return jnp.asarray(rng.normal(size=shape).astype(np.float32) * scale)


def make_paged_state(
    seed,
    n_tokens=(10, 6),
    n_pages=9,
    ps=4,
    kvd=32,
    int8=False,
    garbage_scale=10.0,
):
    """Random paged K/V state exercising every table invariant.

    Pages are pre-filled with large-magnitude garbage (a recycled page's
    stale bytes), requests get *shuffled* non-aliasing page ids with
    null-page padding, and positions land via the real
    ``paged_update_pos`` + ``paged_update`` write path (so the int8 wire
    quantizes exactly like serving does).  Pages never referenced by any
    table and slots past each request's length keep garbage with
    ``pos = -1`` — the scrubbed-recycled-page shape.
    """
    rng = np.random.default_rng(seed)
    b = len(n_tokens)
    p_cnt = max(-(-t // ps) for t in n_tokens) + 1  # + a null-padding col
    cache = {
        "k": _rand(rng, (n_pages, ps, kvd), garbage_scale),
        "v": _rand(rng, (n_pages, ps, kvd), garbage_scale),
    }
    if int8:
        qk, sk = quant.quantize_rows(cache["k"])
        qv, sv = quant.quantize_rows(cache["v"])
        cache = {"k": qk, "k_scale": sk, "v": qv, "v_scale": sv}
    pos_tbl = jnp.full((n_pages, ps), -1, jnp.int32)

    pool = list(rng.permutation(np.arange(1, n_pages)))
    tables = np.zeros((b, p_cnt), np.int32)  # null-page padded
    for i, t in enumerate(n_tokens):
        need = -(-t // ps)
        assert need <= len(pool), "state generator ran out of pages"
        tables[i, :need] = [pool.pop() for _ in range(need)]
    tables = jnp.asarray(tables)

    s_fill = max(n_tokens)
    positions = np.full((b, s_fill), -1, np.int32)
    for i, t in enumerate(n_tokens):
        positions[i, :t] = np.arange(t)
    positions = jnp.asarray(positions)
    pos_tbl = attention.paged_update_pos(pos_tbl, positions, tables)
    new_k = _rand(rng, (b, s_fill, kvd))
    new_v = _rand(rng, (b, s_fill, kvd))
    cache = {**cache, **attention.paged_update(cache, new_k, new_v, positions, tables)}
    return cache, pos_tbl, tables


def _gather_mha(q, cache, pos_tbl, tables, q_pos, kvh, dh, window=None,
                dtype=jnp.float32):
    b = q.shape[0]
    k_win, v_win, pos_win = attention.paged_read(
        cache, pos_tbl, tables, dtype=dtype
    )
    t = k_win.shape[1]
    return attention.mha(
        q, k_win.reshape(b, t, kvh, dh), v_win.reshape(b, t, kvh, dh),
        q_pos, pos_win, window=window, chunk=None,
    )


def _fused(q, cache, pos_tbl, tables, q_pos, kvh, window=None, **kw):
    return paged_attn_fused(
        q, cache["k"], cache["v"], pos_tbl, tables, q_pos,
        kv_heads=kvh, window=window,
        k_scale=cache.get("k_scale"), v_scale=cache.get("v_scale"),
        interpret=True, **kw,
    )


@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("s", [1, 3], ids=["decode", "chunk"])
def test_gqa_kernel_matches_gather(int8, s):
    """GQA (grouped heads, KV never repeated): kernel == paged_read+mha
    over mixed per-request positions with null padding and garbage in
    unreferenced page slots."""
    kvh, dh = 2, 16
    cache, pos_tbl, tables = make_paged_state(
        0, n_tokens=(10, 6), ps=4, kvd=kvh * dh, int8=int8
    )
    rng = np.random.default_rng(1)
    q = _rand(rng, (2, s, 8, dh))
    # rows at each request's frontier; one padding row on the short one
    q_pos = jnp.asarray(
        [[9] * s, [5] * (s - 1) + [-1]] if s > 1 else [[9], [5]], jnp.int32
    )
    out_ref = _gather_mha(q, cache, pos_tbl, tables, q_pos, kvh, dh)
    out_k = _fused(q, cache, pos_tbl, tables, q_pos, kvh)
    valid = np.asarray(q_pos) >= 0
    np.testing.assert_allclose(
        np.asarray(out_k)[valid], np.asarray(out_ref)[valid],
        atol=1e-5, rtol=1e-5,
    )


@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
def test_mla_latent_kernel_matches_absorbed(int8):
    """MLA: the kernel's latent mode (kv_heads=1, v = latent prefix of
    the k page) == latent gather + _mla_absorbed score/context math."""
    lora, rope_d, h, s = 24, 8, 4, 2
    cache, pos_tbl, tables = make_paged_state(
        2, n_tokens=(7, 11), ps=4, kvd=lora + rope_d, int8=False,
    )
    # MLA quantizes only the latent k plane (v is the 1-wide dummy)
    if int8:
        qk, sk = quant.quantize_rows(cache["k"])
        cache = {"k": qk, "k_scale": sk, "v": cache["v"]}
    rng = np.random.default_rng(3)
    q_abs = _rand(rng, (2, s, h, lora))
    q_rope = _rand(rng, (2, s, h, rope_d))
    q_pos = jnp.asarray([[5, 6], [9, 10]], jnp.int32)
    scale = 1.0 / math.sqrt(lora + rope_d)

    lat, _, pos_win = attention.paged_read(
        cache, pos_tbl, tables, dtype=jnp.float32
    )
    c_all, kr_all = lat[..., :lora], lat[..., lora:]
    logits = (
        jnp.einsum("bshl,btl->bhst", q_abs, c_all,
                   preferred_element_type=jnp.float32)
        + jnp.einsum("bshr,btr->bhst", q_rope, kr_all,
                     preferred_element_type=jnp.float32)
    ) * scale
    bias = attention._mask_bias(q_pos, pos_win, None)[:, None, :, :]
    probs = jax.nn.softmax(logits + bias, axis=-1)
    ctx_ref = jnp.einsum(
        "bhst,btl->bshl", probs.astype(c_all.dtype), c_all,
        preferred_element_type=jnp.float32,
    )

    q_cat = jnp.concatenate([q_abs, q_rope], axis=-1)
    ctx_k = paged_attn_fused(
        q_cat, cache["k"], None, pos_tbl, tables, q_pos,
        kv_heads=1, softmax_scale=scale, latent_dv=lora,
        k_scale=cache.get("k_scale"), interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(ctx_k), np.asarray(ctx_ref), atol=1e-5, rtol=1e-5
    )


def test_sliding_window_masking():
    """The in-kernel window bound matches mha's position-derived window."""
    kvh, dh = 2, 16
    cache, pos_tbl, tables = make_paged_state(4, n_tokens=(12,), ps=4,
                                              kvd=kvh * dh)
    rng = np.random.default_rng(5)
    q = _rand(rng, (1, 1, 4, dh))
    q_pos = jnp.asarray([[11]], jnp.int32)
    for window in (3, 8):
        out_ref = _gather_mha(q, cache, pos_tbl, tables, q_pos, kvh, dh,
                              window=window)
        out_k = _fused(q, cache, pos_tbl, tables, q_pos, kvh, window=window)
        np.testing.assert_allclose(
            np.asarray(out_k), np.asarray(out_ref), atol=1e-5, rtol=1e-5
        )


def test_recycled_page_scrub_invariant():
    """Pages whose positions were scrubbed to -1 (recycled) contribute
    exactly zero even when a whole block of them streams FIRST (their
    garbage accumulates into the online stats, then the first valid
    block's rescale flushes it) — the null-page/scrub invariant the
    gather path documents.  The request holds positions 0..139 on
    16-token pages; the first eight (one block) are scrubbed and refilled
    with huge stale values, and the query at 139 still reads its
    frontier page."""
    kvh, dh, ps = 1, 8, 16
    cache, pos_tbl, tables = make_paged_state(
        6, n_tokens=(140,), n_pages=12, ps=ps, kvd=kvh * dh
    )
    stale = tables[0, :8]
    rng = np.random.default_rng(7)
    for name in ("k", "v"):
        cache[name] = cache[name].at[stale].set(
            _rand(rng, (8, ps, kvh * dh), 100.0))
    pos_tbl = pos_tbl.at[stale].set(-1)
    q = _rand(rng, (1, 1, 2, dh))
    q_pos = jnp.asarray([[139]], jnp.int32)
    out_ref = _gather_mha(q, cache, pos_tbl, tables, q_pos, kvh, dh)
    out_k = _fused(q, cache, pos_tbl, tables, q_pos, kvh)
    np.testing.assert_allclose(
        np.asarray(out_k), np.asarray(out_ref), atol=1e-5, rtol=1e-5
    )


def test_bf16_compute_dtype_boundary():
    """The read boundary honors the model compute dtype (the
    paged_read f32-upcast fix): a bf16 caller gets a bf16 window from
    the gather path — int8 planes dequantize to bf16, native planes are
    not upcast — and the fused kernel matches it at bf16 tolerance.
    The argument-less default stays f32."""
    kvh, dh = 2, 16
    for int8 in (False, True):
        cache, pos_tbl, tables = make_paged_state(
            8, n_tokens=(9, 7), ps=4, kvd=kvh * dh, int8=int8
        )
        k_win, v_win, _ = attention.paged_read(
            cache, pos_tbl, tables, dtype=jnp.bfloat16
        )
        assert k_win.dtype == jnp.bfloat16 and v_win.dtype == jnp.bfloat16
        k_def, _, _ = attention.paged_read(cache, pos_tbl, tables)
        assert k_def.dtype == jnp.float32  # documented default
        rng = np.random.default_rng(9)
        q = _rand(rng, (2, 1, 4, dh)).astype(jnp.bfloat16)
        q_pos = jnp.asarray([[8], [6]], jnp.int32)
        out_ref = _gather_mha(
            q, cache, pos_tbl, tables, q_pos, kvh, dh, dtype=jnp.bfloat16
        )
        out_k = _fused(q, cache, pos_tbl, tables, q_pos, kvh)
        assert out_k.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out_k, np.float32), np.asarray(out_ref, np.float32),
            atol=3e-2, rtol=3e-2,
        )


@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
def test_oracle_mirrors_kernel(int8):
    """ref.paged_attn_ref reproduces the kernel's online-softmax page
    tiling (it is the timed jnp proxy in kernel_bench): same inputs,
    near-identical outputs — and both match the gather path."""
    kvh, dh = 2, 16
    cache, pos_tbl, tables = make_paged_state(
        10, n_tokens=(10, 6), ps=4, kvd=kvh * dh, int8=int8
    )
    rng = np.random.default_rng(11)
    q = _rand(rng, (2, 2, 8, dh))
    q_pos = jnp.asarray([[8, 9], [4, 5]], jnp.int32)
    out_k = _fused(q, cache, pos_tbl, tables, q_pos, kvh)
    out_o = ref.paged_attn_ref(
        q, cache["k"], cache["v"], pos_tbl, tables, q_pos, kv_heads=kvh,
        k_scale=cache.get("k_scale"), v_scale=cache.get("v_scale"),
    )
    np.testing.assert_allclose(
        np.asarray(out_o), np.asarray(out_k), atol=1e-6, rtol=1e-6
    )
    out_g = _gather_mha(q, cache, pos_tbl, tables, q_pos, kvh, dh)
    np.testing.assert_allclose(
        np.asarray(out_o), np.asarray(out_g), atol=1e-5, rtol=1e-5
    )


# Tables wider than the live context, on 16-token pages (8 pages, 128
# keys, a block): a 150-token request whose query ends mid-page in the
# second block, a 37-token one ending mid-page in the first, a row whose
# queries are all padding, and a frontier on a block's last key.  The
# table holds 11 pages (the widest request's 10 and a null column), so
# P is no multiple of the block.  ``kvd`` 128 takes the kernel's by-hand
# page copies, 32 its page BlockSpecs.
WIDE = dict(n_tokens=(150, 37, 20, 128), ps=16)
WIDE_POS = {1: [[149], [36], [-1], [127]],
            3: [[147, 148, 149], [34, 35, 36], [-1, -1, -1], [125, 126, 127]]}
FETCH = {"by_hand": (2, 64), "spec": (2, 16)}  # (kv heads, head dim)


def _wide_case(seed, fetch, s, int8):
    kvh, dh = FETCH[fetch]
    cache, pos_tbl, tables = make_paged_state(
        seed, n_pages=40, kvd=kvh * dh, int8=int8, **WIDE
    )
    rng = np.random.default_rng(seed + 1)
    q = _rand(rng, (4, s, 8, dh))
    return cache, pos_tbl, tables, q, jnp.asarray(WIDE_POS[s], jnp.int32)


@pytest.mark.parametrize("fetch", FETCH)
@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("s", [1, 3], ids=["decode", "chunk"])
def test_live_walk_matches_gather(fetch, int8, s):
    """The walk stops at each row's live pages: over a table wider than
    the live context the kernel equals the gather path (which reads the
    whole table) on every valid query row, and a row with no valid query
    (no live page) comes out finite."""
    kvh, dh = FETCH[fetch]
    cache, pos_tbl, tables, q, q_pos = _wide_case(20, fetch, s, int8)
    assert tables.shape[1] == 11
    out_ref = _gather_mha(q, cache, pos_tbl, tables, q_pos, kvh, dh)
    out_k = _fused(q, cache, pos_tbl, tables, q_pos, kvh)
    valid = np.asarray(q_pos) >= 0
    np.testing.assert_allclose(
        np.asarray(out_k)[valid], np.asarray(out_ref)[valid],
        atol=1e-5, rtol=1e-5,
    )
    assert np.all(np.isfinite(np.asarray(out_k)))


@pytest.mark.parametrize("fetch", FETCH)
@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("s", [1, 3], ids=["decode", "chunk"])
def test_oracle_mirrors_kernel_live_blocks(fetch, int8, s):
    """ref.paged_attn_ref walks the same live blocks as the kernel: on
    the wide tables it matches the kernel on every row, padding rows
    included."""
    kvh, _ = FETCH[fetch]
    cache, pos_tbl, tables, q, q_pos = _wide_case(22, fetch, s, int8)
    out_k = _fused(q, cache, pos_tbl, tables, q_pos, kvh)
    out_o = ref.paged_attn_ref(
        q, cache["k"], cache["v"], pos_tbl, tables, q_pos, kv_heads=kvh,
        k_scale=cache.get("k_scale"), v_scale=cache.get("v_scale"),
    )
    np.testing.assert_allclose(
        np.asarray(out_o), np.asarray(out_k), atol=1e-6, rtol=1e-6
    )


@pytest.mark.parametrize("lora", [24, 120], ids=["spec", "by_hand"])
@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("s", [1, 3], ids=["decode", "chunk"])
def test_mla_live_walk_matches_absorbed(lora, int8, s):
    """MLA's latent mode over tables wider than the live context: the
    kernel equals the absorbed math over the whole gathered latent on
    every valid query row (latent width 32 takes the page BlockSpecs,
    128 the by-hand copies)."""
    rope_d, h = 8, 4
    cache, pos_tbl, tables = make_paged_state(
        24, n_pages=40, kvd=lora + rope_d, **WIDE
    )
    if int8:  # MLA quantizes only the latent k plane
        qk, sk = quant.quantize_rows(cache["k"])
        cache = {"k": qk, "k_scale": sk, "v": cache["v"]}
    rng = np.random.default_rng(25)
    q_cat = _rand(rng, (4, s, h, lora + rope_d))
    q_pos = jnp.asarray(WIDE_POS[s], jnp.int32)
    scale = 1.0 / math.sqrt(lora + rope_d)
    lat, _, pos_win = attention.paged_read(
        cache, pos_tbl, tables, dtype=jnp.float32
    )
    logits = jnp.einsum("bshd,btd->bhst", q_cat, lat,
                        preferred_element_type=jnp.float32) * scale
    bias = attention._mask_bias(q_pos, pos_win, None)[:, None, :, :]
    probs = jax.nn.softmax(logits + bias, axis=-1)
    ctx_ref = jnp.einsum("bhst,btl->bshl", probs, lat[..., :lora],
                         preferred_element_type=jnp.float32)
    ctx_k = paged_attn_fused(
        q_cat, cache["k"], None, pos_tbl, tables, q_pos,
        kv_heads=1, softmax_scale=scale, latent_dv=lora,
        k_scale=cache.get("k_scale"), interpret=True,
    )
    valid = np.asarray(q_pos) >= 0
    np.testing.assert_allclose(
        np.asarray(ctx_k)[valid], np.asarray(ctx_ref)[valid],
        atol=1e-5, rtol=1e-5,
    )
    assert np.all(np.isfinite(np.asarray(ctx_k)))


@pytest.mark.parametrize("ps,s", [(16, 1), (16, 3), (4, 2), (256, 1)])
def test_walked_pages_counts_live_blocks(ps, s):
    """The host's count of walked pages is each row's live pages (the
    page of its largest query position and those before it, none for an
    all-padding row, at most the table) rounded up to whole blocks."""
    rng = np.random.default_rng(ps + s)
    p_cnt = 11
    q_pos = rng.integers(-1, ps * p_cnt + 5, (6, s)).astype(np.int32)
    q_pos[0] = -1
    ppb = paged_attn.pages_per_block(ps, p_cnt)
    want = 0
    for row in q_pos:
        top = row.max()
        live = 0 if top < 0 else min(top // ps + 1, p_cnt)
        want += -(-live // ppb) * ppb
    assert paged_attn.walked_pages(q_pos, ps, p_cnt) == want
    np.testing.assert_array_equal(
        np.asarray(paged_attn.live_pages(jnp.asarray(q_pos), ps, p_cnt)),
        paged_attn.live_pages(q_pos, ps, p_cnt),
    )


def test_autotune_paged_attn_kind(monkeypatch):
    """The autotune registry's paged_attn kind: cache entries win where
    they are runnable, the backend heuristic answers otherwise (gather
    off-TPU, fused on TPU), corrupt entries are ignored, and sweeps
    never persist verdicts a different host could be misled by.  The
    persistence env var is cleared so this test can never write a
    no-op-lambda 'winner' into a developer's real autotune cache."""
    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE", raising=False)
    key = ("paged_attn", 4, 8, 16, 64, 0)
    autotune._load_cache()
    on_tpu = jax.default_backend() == "tpu"
    try:
        assert autotune.heuristic_paged_attn_impl("cpu") == "gather"
        assert autotune.heuristic_paged_attn_impl("tpu") == "fused"
        assert autotune.get_paged_attn_impl(4, 8, 16, 64) == (
            autotune.heuristic_paged_attn_impl()
        )
        autotune._CACHE[key] = ("gather",)
        assert autotune.get_paged_attn_impl(4, 8, 16, 64) == "gather"
        # a "fused" verdict is honored only where the compiled kernel
        # runs: replaying a TPU-tuned cache off-TPU must not route
        # "auto" serving through the Pallas interpreter
        autotune._CACHE[key] = ("fused",)
        assert autotune.get_paged_attn_impl(4, 8, 16, 64) == (
            "fused" if on_tpu else autotune.heuristic_paged_attn_impl()
        )
        autotune._CACHE[key] = ("bogus",)  # corrupt entry: fall through
        assert autotune.get_paged_attn_impl(4, 8, 16, 64) == (
            autotune.heuristic_paged_attn_impl()
        )
        autotune._CACHE.pop(key, None)

        # a partial sweep (one impl can't run on this host) must answer
        # from what it timed WITHOUT caching — the key carries no
        # backend, so a CPU-produced entry would pin "gather" on TPU
        def run_partial(impl):
            if impl == "fused":
                raise RuntimeError("no TPU")
            return lambda: 0

        assert autotune.autotune_paged_attn(run_partial, 4, 8, 16, 64) == "gather"
        assert key not in autotune._CACHE
        # a complete sweep caches its winner
        assert autotune.autotune_paged_attn(lambda _: (lambda: 0), 4, 8, 16, 64) in (
            autotune.PAGED_ATTN_IMPLS
        )
        assert key in autotune._CACHE
    finally:
        autotune._CACHE.pop(key, None)


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(0, 2**16),
    lens=st.lists(st.integers(1, 14), min_size=1, max_size=3),
    ps=st.sampled_from([2, 4, 8]),
    int8=st.booleans(),
)
def test_fused_matches_gather_property(seed, lens, ps, int8):
    """Property: over random page tables (null-page padding, shuffled
    non-contiguous assignment, stale garbage in every unwritten slot)
    and random per-request frontiers, the fused kernel equals the
    gather+mha path on every valid query row."""
    kvh, dh = 2, 8
    n_pages = sum(-(-t // ps) for t in lens) + 2
    cache, pos_tbl, tables = make_paged_state(
        seed, n_tokens=tuple(lens), n_pages=n_pages, ps=ps, kvd=kvh * dh,
        int8=int8,
    )
    rng = np.random.default_rng(seed + 1)
    b = len(lens)
    q = _rand(rng, (b, 1, 4, dh))
    # query at a random valid position per request (mid-stream decode)
    q_pos = jnp.asarray(
        [[int(rng.integers(0, t))] for t in lens], jnp.int32
    )
    out_ref = _gather_mha(q, cache, pos_tbl, tables, q_pos, kvh, dh)
    out_k = _fused(q, cache, pos_tbl, tables, q_pos, kvh)
    np.testing.assert_allclose(
        np.asarray(out_k), np.asarray(out_ref), atol=1e-5, rtol=1e-5
    )


@settings(max_examples=15, deadline=None)
@given(
    seed=st.integers(0, 2**16),
    lens=st.lists(st.integers(1, 200), min_size=1, max_size=3),
    ps=st.sampled_from([4, 8, 16]),
    extra=st.integers(0, 12),
    int8=st.booleans(),
    wide=st.booleans(),
)
def test_live_walk_property(seed, lens, ps, extra, int8, wide):
    """Property: over random live lengths in tables up to ``extra`` pages
    wider than the longest request (so rows end mid-page and mid-block,
    and ``P`` falls anywhere against the block), with queries at random
    valid positions, the fused kernel equals the gather+mha path."""
    kvh, dh = (2, 64) if wide else (2, 8)
    n_pages = sum(-(-t // ps) for t in lens) + 2
    cache, pos_tbl, tables = make_paged_state(
        seed, n_tokens=tuple(lens), n_pages=n_pages, ps=ps, kvd=kvh * dh,
        int8=int8,
    )
    tables = jnp.pad(tables, ((0, 0), (0, extra)))
    rng = np.random.default_rng(seed + 1)
    q = _rand(rng, (len(lens), 1, 4, dh))
    q_pos = jnp.asarray([[int(rng.integers(0, t))] for t in lens], jnp.int32)
    out_ref = _gather_mha(q, cache, pos_tbl, tables, q_pos, kvh, dh)
    out_k = _fused(q, cache, pos_tbl, tables, q_pos, kvh)
    np.testing.assert_allclose(
        np.asarray(out_k), np.asarray(out_ref), atol=1e-5, rtol=1e-5
    )
