"""Decoder-only LM covering every assigned non-enc-dec architecture:
dense GQA (granite/qwen/starcoder2), MLA (minicpm3), VLM backbone
(qwen2-vl, M-RoPE + patch-embed stub), MoE (granite-moe / phi3.5-moe),
SSM (mamba2), and hybrid (hymba).

Layers are homogeneous per arch, so parameters are stacked ``[L, ...]``
and the forward pass is a single ``lax.scan`` (+ per-layer remat), which
keeps HLO size flat in depth — essential for the 80-compile dry-run
matrix and standard practice at scale.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import blocks, rope, ssm as ssm_mod
from repro.models.common import (
    BATCH_AXES,
    DATA,
    MODEL,
    dtype_of,
    linear,
    make_embedding,
    make_linear,
    make_norm,
    rmsnorm,
)


def _stack_specs(specs, n_layers):
    """Prepend a (None) layer axis to every PartitionSpec leaf."""
    return jax.tree_util.tree_map(
        lambda s: P(None, *s),
        specs,
        is_leaf=lambda s: isinstance(s, P),
    )


def _init_layer(cfg, key, dtype):
    """One layer's params from its own key (``init_lm`` vmaps this)."""
    if cfg.family == "ssm":
        p, _ = ssm_mod.make_mamba2(key, cfg, dtype)
        n, _ = make_norm(cfg.d_model)
        return {"mixer": p, "ln": n}
    return blocks.make_decoder_block(key, cfg, dtype)[0]


def _init_outer(cfg, k_embed, k_head, dtype):
    """Everything outside the layer stack: ``(params, specs)``."""
    params, specs = {}, {}
    params["embed"], specs["embed"] = make_embedding(
        k_embed, cfg.padded_vocab, cfg.d_model, dtype=dtype
    )
    params["final_norm"], specs["final_norm"] = make_norm(cfg.d_model)
    if not cfg.tie_embeddings:  # tied heads reuse embed
        params["lm_head"], specs["lm_head"] = make_linear(
            k_head, cfg.d_model, cfg.padded_vocab, dtype=dtype, spec=P(DATA, MODEL)
        )
    return params, specs


def init_lm(cfg, key):
    """Returns (params, specs)."""
    dtype = dtype_of(cfg.dtype)
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    params, specs = _init_outer(cfg, k_embed, k_head, dtype)

    if cfg.family == "ssm":
        sp_m = ssm_mod.make_mamba2(jax.random.PRNGKey(0), cfg, dtype)[1]
        sp_n = make_norm(cfg.d_model)[1]
        layer_specs = {"mixer": sp_m, "ln": sp_n}
    else:
        layer_specs = blocks.make_decoder_block(jax.random.PRNGKey(0), cfg, dtype)[1]

    keys = jax.random.split(k_layers, cfg.n_layers)
    params["layers"] = jax.vmap(lambda k: _init_layer(cfg, k, dtype))(keys)
    specs["layers"] = _stack_specs(layer_specs, cfg.n_layers)
    return params, specs


def init_lm_layerwise(cfg, key, transform):
    """``init_lm``'s params with ``transform`` applied layer by layer.

    Each layer is made from its own key — the keys ``init_lm`` splits —
    transformed, and written into a preallocated, donated stack, so only
    one raw layer exists at a time.  Serving uses this to build packed
    weights for a model whose raw stack would not fit the device: the
    result equals ``transform``-ing ``init_lm(cfg, key)[0]`` leaf for
    leaf whenever ``transform`` acts on each layer alone (as
    ``serve.engine.pack_params_for_serving`` does).  Layers are made and
    transformed op by op, as ``init_lm`` is, and not under one ``jit``:
    XLA's fusion may round differently (a quantization scale, a random
    normal) by an ulp.  ``transform`` is applied to the params outside
    the stack too.  Returns params only.
    """
    dtype = dtype_of(cfg.dtype)
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    keys = jax.random.split(k_layers, cfg.n_layers)

    def layer(k):
        return transform(_init_layer(cfg, k, dtype))

    # outside the stack first: the vocab-wide head's transients then
    # have the device to themselves
    params = transform(_init_outer(cfg, k_embed, k_head, dtype)[0])
    shapes = jax.eval_shape(layer, keys[0])
    stack = jax.jit(
        lambda: jax.tree_util.tree_map(
            lambda s: jnp.zeros((cfg.n_layers,) + s.shape, s.dtype), shapes
        )
    )()
    put = jax.jit(
        lambda st, new, i: jax.tree_util.tree_map(
            lambda a, b: jax.lax.dynamic_update_index_in_dim(a, b, i, 0),
            st, new,
        ),
        donate_argnums=0,
    )
    for i in range(cfg.n_layers):
        stack = put(stack, layer(keys[i]), i)
    params["layers"] = stack
    return params


def _remat(fn, cfg):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return jax.checkpoint(fn)


def scan_over_layers(body, carry, xs, cfg):
    """lax.scan over stacked layer params, or an unrolled python loop when
    ``cfg.scan_layers`` is False (dry-run cost extraction — XLA's
    cost_analysis counts a while body once, so unrolled variants provide
    the per-layer costs).  ``xs`` is a pytree stacked on axis 0."""
    if cfg.scan_layers:
        return jax.lax.scan(body, carry, xs)
    ys = []
    for i in range(cfg.n_layers):
        x_i = jax.tree_util.tree_map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a, 0), *ys)
    return carry, stacked


def _rope_cs(cfg, positions, pos3=None):
    """Hoist cos/sin out of the layer scan (shared by all layers)."""
    dh = cfg.head_dim()
    if cfg.m_rope_sections is not None and pos3 is not None:
        return rope.mrope_cos_sin(pos3, dh, cfg.rope_theta, cfg.m_rope_sections)
    return rope.rope_cos_sin(positions, dh, cfg.rope_theta)


def _embed(params, tokens, cfg):
    x = jnp.take(params["embed"]["w"], tokens, axis=0)
    return x


def _head(params, x, cfg):
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        w = params["embed"]["w"]
        return jnp.einsum("bsd,vd->bsv", x, w.astype(x.dtype))
    # Thread the sparsity config for its *quantization* knobs only
    # (dap_input=False: the head input is never DAP-pruned).  Without it
    # a packed-int8 lm_head fell back to a per-TENSOR dynamic activation
    # scale — one amax shared across the batch — so a row's logits
    # depended on what it was batched with (padding rows included),
    # breaking the serve engine's per-row batch-invariance contract at
    # the very last matmul.
    return linear(params["lm_head"], x, sparsity=cfg.sparsity, dap_input=False)


def forward(
    params,
    tokens: jax.Array,  # [B, S]
    cfg,
    *,
    positions: Optional[jax.Array] = None,  # [B, S]
    pos3: Optional[jax.Array] = None,  # [3, B, S] (M-RoPE / VLM)
    patch_embeds: Optional[jax.Array] = None,  # [B, S_vis, d] (VLM stub)
):
    """Full-sequence forward (training / prefill).  Returns (logits, aux)."""
    b, s = tokens.shape
    x = _embed(params, tokens, cfg)
    if patch_embeds is not None:
        x = jnp.concatenate([patch_embeds.astype(x.dtype), x], axis=1)
        s = x.shape[1]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    rope_cs = None
    if cfg.family != "ssm" and cfg.mla is None:
        rope_cs = _rope_cs(cfg, positions, pos3)

    if cfg.family == "ssm":

        def body(carry, layer_p):
            h = rmsnorm(carry, layer_p["ln"], cfg.norm_eps)
            y, _ = ssm_mod.mamba2_forward(layer_p["mixer"], h, cfg)
            return carry + y, jnp.zeros((), jnp.float32)

    else:

        def body(carry, layer_p):
            y, _, aux = blocks.decoder_block(
                layer_p, carry, cfg, positions, rope_cs=rope_cs
            )
            return y, aux

    x, auxs = scan_over_layers(_remat(body, cfg), x, params["layers"], cfg)
    logits = _head(params, x, cfg)
    return logits, jnp.sum(auxs)


# ------------------------------------------------------------------ serving


def make_cache(cfg, batch: int, max_seq: int):
    """Stacked ring-buffer cache sized for ``max_seq`` (or the window).

    ``cfg.sparsity.kv_dtype="int8"`` stores K/V as int8 with per-token
    f32 scale planes (``k_scale/v_scale [L, B, W]``) — the ring half of
    the int8 KV wire (``models/attention.py``; docs/quantization.md).
    Empty slots hold zeros with scale 1.0, so they dequantize to exact
    zeros.  MLA quantizes only the latent ``k`` plane: its ``v`` is the
    1-wide always-zero dummy, where a scale plane would cost more bytes
    than it saves.
    """
    dtype = dtype_of(cfg.dtype)
    window = max_seq if cfg.sliding_window is None else min(max_seq, cfg.sliding_window)
    cache = {}
    if cfg.family == "ssm":
        return ssm_mod.make_ssm_cache(batch, cfg, cfg.n_layers, dtype)
    kv_int8 = cfg.sparsity.kv_dtype == "int8"
    v_int8 = kv_int8 and cfg.mla is None
    kv_dim = cfg.kv_dim()
    v_dim = 1 if cfg.mla is not None else kv_dim
    cache = {
        "k": jnp.zeros(
            (cfg.n_layers, batch, window, kv_dim),
            jnp.int8 if kv_int8 else dtype,
        ),
        "v": jnp.zeros(
            (cfg.n_layers, batch, window, v_dim),
            jnp.int8 if v_int8 else dtype,
        ),
        "pos": jnp.full((cfg.n_layers, batch, window), -1, jnp.int32),
    }
    if kv_int8:
        cache["k_scale"] = jnp.ones(
            (cfg.n_layers, batch, window), jnp.float32
        )
    if v_int8:
        cache["v_scale"] = jnp.ones(
            (cfg.n_layers, batch, window), jnp.float32
        )
    if cfg.family == "hybrid":
        ssm_cache = ssm_mod.make_ssm_cache(batch, cfg, cfg.n_layers, dtype)
        cache["ssm_state"] = ssm_cache["state"]
        cache["ssm_conv"] = ssm_cache["conv"]
    return cache


def cache_specs(cfg):
    if cfg.family == "ssm":
        return ssm_mod.ssm_cache_specs()
    if cfg.mla is None:
        # GQA ring buffer: WINDOW sharded over `model` (sequence-parallel
        # flash-decode, attention.flash_decode) — kv-head counts rarely
        # divide the model axis (8 or 5 vs 16), and kv-dim sharding makes
        # GSPMD all-gather the cache in f32 every layer (§Perf-A2).
        out = {
            "k": P(None, DATA, MODEL, None),
            "v": P(None, DATA, MODEL, None),
            "pos": P(None, DATA, MODEL),
        }
    else:  # MLA latent cache: shared across heads, contract over latent
        out = {
            "k": P(None, DATA, None, MODEL),
            "v": P(None, DATA, None, None),
            "pos": P(None, DATA, None),
        }
    if cfg.sparsity.kv_dtype == "int8":
        # per-token scale planes shard exactly like the slot positions
        # (MLA's 1-wide dummy v stays native: no v_scale — see make_cache)
        out["k_scale"] = out["pos"]
        if cfg.mla is None:
            out["v_scale"] = out["pos"]
    if cfg.family == "hybrid":
        s = ssm_mod.ssm_cache_specs()
        out["ssm_state"] = s["state"]
        out["ssm_conv"] = s["conv"]
    return out


# ------------------------------------------- cache state export (durability)
#
# The serving engine's crash-consistency snapshots (serve/engine.py) go
# through these three hooks so the KV-plane wire format stays a model-layer
# concern: what a snapshot stores is exactly the device layout — int8 KV
# caches checkpoint at wire size (the S2TA bytes-economy argument applied
# to recovery traffic), and nothing is re-quantized on either side.


def paged_cache_template(cfg, n_pages: int, page_size: int):
    """Abstract (shape/dtype only) paged-cache pytree for ``cfg`` — the
    ``like_tree`` a restorer hands to ``checkpoint.manager.restore``
    without allocating device memory."""
    from repro.serve.paged_cache import make_paged_cache

    return jax.eval_shape(lambda: make_paged_cache(cfg, n_pages, page_size))


def export_decode_state(cache):
    """Device cache pytree -> host numpy pytree, dtype-preserving (int8
    planes stay int8 on disk)."""
    import numpy as np

    return jax.tree_util.tree_map(
        lambda leaf: np.asarray(jax.device_get(leaf)), cache
    )


def restore_decode_state(host_cache):
    """Host numpy pytree -> device pytree (inverse of
    :func:`export_decode_state`)."""
    return jax.tree_util.tree_map(jnp.asarray, host_cache)


def decode_step(params, cache, tokens: jax.Array, pos, cfg):
    """One decode step.  tokens [B, 1]; pos scalar int32 (current position).

    Returns (logits [B, 1, V], new_cache).
    """
    b = tokens.shape[0]
    x = _embed(params, tokens, cfg)
    positions = jnp.full((b, 1), pos, jnp.int32)
    pos3 = None
    if cfg.m_rope_sections is not None:
        pos3 = jnp.broadcast_to(positions[None], (3, b, 1))
    rope_cs = None
    if cfg.family != "ssm" and cfg.mla is None:
        rope_cs = _rope_cs(cfg, positions, pos3)

    if cfg.family == "ssm":

        def body(carry, inp):
            layer_p, cache_layer = inp
            h = rmsnorm(carry, layer_p["ln"], cfg.norm_eps)
            y, new_c = ssm_mod.mamba2_forward(
                layer_p["mixer"], h, cfg, cache_layer=cache_layer
            )
            return carry + y, new_c

        x, new_cache = scan_over_layers(body, x, (params["layers"], cache), cfg)
    else:

        def body(carry, inp):
            layer_p, cache_layer = inp
            y, new_c, _ = blocks.decoder_block(
                layer_p, carry, cfg, positions,
                cache_layer=cache_layer, decode_pos=pos, rope_cs=rope_cs,
            )
            return y, new_c

        x, new_cache = scan_over_layers(body, x, (params["layers"], cache), cfg)
    logits = _head(params, x, cfg)
    return logits, new_cache


def _prepare_pages(cache, scrub_pages, cow_pages):
    """Pre-write page maintenance, in order: scrub freshly allocated
    pages' slot positions, then land copy-on-write duplicates (dst pages
    are fresh, so the copy follows the scrub — and every plane, including
    int8 scale planes and the shared position table, is copied so the
    duplicate is byte-identical to its source).  Null-padded entries
    (page 0 / (0, 0) pairs) are harmless no-ops."""
    pos_tbl = cache["pos"]
    if scrub_pages is not None:
        pos_tbl = pos_tbl.at[scrub_pages].set(-1)
    kv_planes = {name: val for name, val in cache.items() if name != "pos"}
    if cow_pages is not None:
        src, dst = cow_pages[:, 0], cow_pages[:, 1]
        kv_planes = {
            name: val.at[:, dst].set(val[:, src])
            for name, val in kv_planes.items()
        }
        pos_tbl = pos_tbl.at[dst].set(pos_tbl[src])
    return kv_planes, pos_tbl


def paged_step(params, cache, tokens, positions, page_tables, cfg,
               scrub_pages=None, cow_pages=None):
    """One continuous-batching step over the paged KV cache.

    ``tokens/positions [B, S]`` carry a *mixed* batch: each row is an
    independent request at its own absolute positions — a chunked-prefill
    slice, a single decode token, or padding (position -1).  ``cache`` is
    a paged cache (serve/paged_cache.make_paged_cache): per-layer k/v
    page pools plus one shared slot-position table; ``page_tables
    [B, P]`` maps each row's logical positions onto its pages (padded
    with the null page).  One jitted call serves every row regardless of
    sequence position or physical page placement — the compute half of
    continuous batching (serve/scheduler.py drives it).

    ``scrub_pages`` (fixed-width int32, null-page-padded) lists pages
    freshly allocated this step: their slot positions are invalidated
    before anything else, so a page recycled from a finished request
    can never leak stale entries that alias the new owner's logical
    positions (scrubbing the null page is a harmless no-op).

    ``cow_pages`` (fixed-width int32 ``[W, 2]``, (0, 0)-padded) lists
    copy-on-write ``(src, dst)`` page pairs from the scheduler: before
    this step's writes, every KV plane and the slot-position row of
    ``src`` is copied into ``dst`` — the step then writes the divergent
    token into ``dst`` through the (already rewritten) page table while
    ``src`` stays byte-identical for its other sharers (shared-prefix
    caching, docs/serving.md).

    Per-layer attention runs either the gather path (``paged_read`` +
    ``mha``) or the fused Pallas page-table-walk kernel
    (``kernels/paged_attn.py``), selected by
    ``cfg.sparsity.paged_attn`` — the serving engine threads
    ``ServeConfig.paged_attn`` into the effective config, so one jitted
    ``paged_step`` serves both implementations (docs/serving.md).

    Returns (logits [B, S, V], new_cache).  Rows are masked per-position
    (k_pos <= q_pos over gathered slot positions), so padding emits
    garbage logits that callers must not sample from (the scheduler
    samples only at each row's last valid index).
    """
    if cfg.family in ("ssm", "hybrid"):
        raise ValueError(
            f"paged_step unsupported for recurrent family {cfg.family!r}: "
            "only attention state pages (see serve/scheduler.py)"
        )
    from repro.models import attention

    b, s = tokens.shape
    x = _embed(params, tokens, cfg)
    pos3 = None
    if cfg.m_rope_sections is not None:
        pos3 = jnp.broadcast_to(positions[None], (3, b, s))
    rope_cs = None
    if cfg.mla is None:
        rope_cs = _rope_cs(cfg, positions, pos3)

    # Scrub + CoW maintenance, then one shared slot-position write for
    # the whole stack (every layer stores the same token positions);
    # layers read the updated table so this step's tokens are visible to
    # intra-chunk causal attention.
    kv_planes, pos_tbl = _prepare_pages(cache, scrub_pages, cow_pages)
    new_pos_tbl = attention.paged_update_pos(pos_tbl, positions, page_tables)

    def body(carry, inp):
        layer_p, kv = inp
        y, new_c, _ = blocks.decoder_block(
            layer_p, carry, cfg, positions,
            cache_layer={**kv, "pos": new_pos_tbl},
            page_tables=page_tables, rope_cs=rope_cs,
        )
        return y, new_c

    # every per-layer plane (k/v and, under the int8 KV wire, the
    # k_scale/v_scale planes) scans; the shared pos table is carried once
    x, new_kv = scan_over_layers(body, x, (params["layers"], kv_planes), cfg)
    logits = _head(params, x, cfg)
    return logits, {**new_kv, "pos": new_pos_tbl}


def paged_decode_loop(params, cache, tokens, positions, page_tables,
                      n_steps, cfg, *, max_steps,
                      scrub_pages=None, cow_pages=None, sampling=None):
    """Fused multi-token decode over the paged KV cache.

    Runs up to ``max_steps`` (static buffer width) decode iterations of
    :func:`paged_step` *inside one jitted dispatch* — an on-device
    ``fori_loop`` whose trip count ``n_steps`` is a **traced** scalar, so
    one compiled trace serves every run length.  Sampling is fused into
    the loop body (the shared seeded sampler in ``core/sampling.py`` over
    the unpadded vocab — plain greedy argmax when ``sampling`` is None or
    every temperature is 0, exactly the engine's ``_sample_at`` at chunk
    index 0), and each sampled token is fed back as the next iteration's
    input.  This is what makes continuous batching fast: a decode-only
    batch pays ONE Python→XLA dispatch per run instead of one per token
    (serve/scheduler.py plans the runs, ``benchmarks/serve_bench.py``
    measures the win).

    ``tokens [B, 1]`` holds each row's last sampled token; ``positions
    [B]`` its first write position (-1 marks an idle row: it keeps
    writing to the null page at position -1 and its outputs are garbage
    the scheduler never reads).  ``sampling`` is an optional
    ``(temps [B] f32, top_ks [B] i32, top_ps [B] f32, seeds [B] u32)``
    tuple of per-row sampling params; PRNG keys are derived from
    ``(seed, fed-stream position)`` — the loop's ``pos`` carry — so
    sampled tokens are independent of batch slot, run length, and
    scheduler iteration (core/sampling.py).  Scrub/CoW maintenance
    covers the WHOLE run (the scheduler pre-allocates every page the run
    will touch), so it is applied once up front, not per iteration.

    Returns (sampled [B, max_steps] int32, bad_at [B] int32, new_cache);
    sampled entries past ``n_steps`` are zeros.  ``bad_at`` is the in-loop
    numerical watchdog: per row, the FIRST loop index whose RAW
    (pre-sampling) logits contained a non-finite value (``max_steps``
    when the whole run was clean) — the scheduler quarantines poisoned
    rows and keeps only their pre-fault tokens (serve/scheduler.py
    ``commit_run``).
    """
    if cfg.family in ("ssm", "hybrid"):
        raise ValueError(
            f"paged_decode_loop unsupported for recurrent family "
            f"{cfg.family!r}: only attention state pages"
        )
    from repro.core import sampling as sampling_mod

    kv_planes, pos_tbl = _prepare_pages(cache, scrub_pages, cow_pages)
    cache = {**kv_planes, "pos": pos_tbl}
    b = tokens.shape[0]
    v = cfg.vocab  # slice off vocab padding before sampling

    def body(i, carry):
        cache, toks, pos, out, bad_at = carry
        logits, cache = paged_step(
            params, cache, toks, pos[:, None], page_tables, cfg
        )
        row = logits[:, 0, :v]
        if sampling is None:
            nxt = jnp.argmax(row, axis=-1).astype(jnp.int32)
        else:
            temps, top_ks, top_ps, seeds = sampling
            # keyed on the pre-increment pos carry: the fed-stream
            # position of the token whose logits `row` holds
            nxt = sampling_mod.sample_tokens(
                row, temps, top_ks, top_ps, seeds, pos
            )
        out = jax.lax.dynamic_update_slice(out, nxt[:, None], (0, i))
        # Idle rows (pos < 0) must keep feeding the SAME (token 0, -1)
        # padding the host-driven mixed step feeds, not their own garbage
        # argmax — every iteration's batch then matches the one-call-per-
        # token schedule input-for-input, keeping runs byte-exact.
        active = pos >= 0
        # watchdog: record the first iteration with non-finite logits on
        # an active row (earlier marks win; idle rows are never flagged)
        bad = active & ~jnp.all(jnp.isfinite(row), axis=-1)
        bad_at = jnp.where(bad & (bad_at == max_steps), i, bad_at)
        nxt = jnp.where(active, nxt, 0)
        pos = jnp.where(active, pos + 1, pos)
        return cache, nxt[:, None], pos, out, bad_at

    out0 = jnp.zeros((b, max_steps), jnp.int32)
    bad0 = jnp.full((b,), max_steps, jnp.int32)
    cache, _, _, out, bad_at = jax.lax.fori_loop(
        0, n_steps, body, (cache, tokens, positions, out0, bad0)
    )
    return out, bad_at, cache


def paged_verify(params, cache, tokens, positions, page_tables, cfg,
                 sampling=None):
    """Single-pass speculative-decode verification over the paged cache.

    ``tokens/positions [B, S]`` carry, per row, the *candidate fed
    stream* of one speculation window: the row's last committed token
    followed by its draft proposals, at consecutive absolute positions
    (−1-padded past the window, like any mixed step).  One
    :func:`paged_step` call recomputes every window position under the
    TARGET config — overwriting whatever draft-config KV the proposal
    loop left at those slots (each layer writes its window K/V before
    attending, so the gathered context is target-computed end to end;
    this is exactly the chunked-prefill mechanics the byte-exactness
    suite already pins) — then samples a token at EVERY window index
    with the shared seeded sampler keyed on that index's own fed-stream
    position.  Index ``j`` therefore yields precisely the token solo
    target decode would emit after the row's committed stream extended
    by proposals ``d_1..d_j`` — the engine's acceptance rule keeps the
    longest prefix where those proposals match (serve/engine.py).

    ``sampling`` is the per-row ``(temps, top_ks, top_ps, seeds)``
    tuple (None = all-greedy argmax).  Returns ``(sampled [B, S] int32,
    ok [B, S] bool, new_cache)``; ``ok`` is the numerical watchdog —
    per index, whether the raw pre-sampling logits were all finite.
    """
    from repro.core import sampling as sampling_mod

    b, s = tokens.shape
    v = cfg.vocab  # slice off vocab padding before sampling
    logits, cache = paged_step(
        params, cache, tokens, positions, page_tables, cfg
    )
    rows = logits[:, :, :v].reshape(b * s, v)
    if sampling is None:
        tok = jnp.argmax(rows, axis=-1).astype(jnp.int32)
    else:
        temps, top_ks, top_ps, seeds = sampling
        rep = lambda a: jnp.repeat(a, s)  # noqa: E731
        tok = sampling_mod.sample_tokens(
            rows, rep(temps), rep(top_ks), rep(top_ps), rep(seeds),
            positions.reshape(-1),
        )
    ok = jnp.all(jnp.isfinite(rows), axis=-1)
    return tok.reshape(b, s), ok.reshape(b, s), cache


def prefill(params, tokens, cfg, cache=None):
    """Prefill: forward pass; if ``cache`` given, also fills it and returns
    (logits, cache) — logits only otherwise.

    **Single-pass**: one scan over the layer stack emits both the logits
    and the filled cache.  Each attention layer computes its full-sequence
    attention *and* writes its own (already projected, already RoPE'd)
    K/V into the ring in the same trace (``attention.fill_ring``) — the
    seed-era design ran the stack twice (forward for logits, then a
    K/V-recompute scan), doubling batched-prefill FLOPs.

    Exactness: the ring ends up bit-identical to what per-token stepping
    writes (same projections through the same DBB-aware linear path), so
    batched prefill stays token-exact vs stepped decode.  SSM keeps its
    conv-tail/zero-state fill; hybrid fills only the attention ring (the
    recurrent state passes through untouched — no exact one-shot fill
    yet) — both families are served stepped by the engine anyway.
    """
    if cache is None:
        return forward(params, tokens, cfg)[0]
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = _embed(params, tokens, cfg)
    rope_cs = None
    if cfg.family != "ssm" and cfg.mla is None:
        rope_cs = _rope_cs(cfg, positions)

    if cfg.family == "ssm":

        def body(carry, inp):
            layer_p, cache_layer = inp
            h = rmsnorm(carry, layer_p["ln"], cfg.norm_eps)
            y, _ = ssm_mod.mamba2_forward(layer_p["mixer"], h, cfg)
            # state fill for SSM prefill uses the chunked path's final state;
            # engines re-run decode for exactness. Keep conv tail + zero state.
            return carry + y, dict(cache_layer)

    else:  # attention families (incl. hybrid): the block fills its own
        # cache in-pass (hybrid: the attention ring only — the SSM state
        # passes through untouched; engines step hybrids for exactness)

        def body(carry, inp):
            layer_p, cache_layer = inp
            y, new_c, _ = blocks.decoder_block(
                layer_p, carry, cfg, positions,
                cache_layer=cache_layer, rope_cs=rope_cs,
            )
            return y, new_c

    x, new_cache = scan_over_layers(body, x, (params["layers"], cache), cfg)
    logits = _head(params, x, cfg)
    return logits, new_cache
