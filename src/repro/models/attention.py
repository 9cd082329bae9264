"""Attention: GQA (with sliding window / ring-buffer KV cache), MLA
(materialized for train/prefill, absorbed for decode), and cross-attention
for the enc-dec family.  All shapes [B, S, H, D]; softmax in float32.

The KV cache is a unified ring buffer: ``k/v [B, W, KV*D]`` plus absolute
slot positions ``pos [B, W] int32`` (-1 ⇒ empty).  Full-attention caches
use ``W = max_seq`` (slot == position); windowed caches use ``W = window``
(slot == position % W).  Validity/causality/window masking all derive from
the slot-position array, so one code path serves every arch.

INT8 KV wire (``SparsityConfig.kv_dtype="int8"``): caches whose dict
carries ``k_scale``/``v_scale`` planes store int8 values quantized at
write time with **per-token symmetric scales** (one f32 scale per cached
row; ``core.quant.quantize_rows``) and dequantize at the read boundary —
:func:`ring_window` for the ring, :func:`paged_read` for pages — so
:func:`mha` and the MLA-absorbed path never see the wire format.  The
write sites (:func:`fill_ring`, :func:`_update_ring`,
:func:`paged_update`) quantize row-locally, which keeps a token's stored
bytes independent of its co-batch (the batch-invariance argument of
``docs/quantization.md``).

Paged attention has two interchangeable implementations selected by
``SparsityConfig.paged_attn`` (see :func:`_paged_attn_impl`): the
**gather** path (:func:`paged_read` + :func:`mha` / absorbed MLA) that
materializes each request's logical window, and the **fused** Pallas
kernel (``repro.kernels.paged_attn``) that walks the page table
in-kernel with online softmax and int8 dequant fused into the page load
— same masking invariants, no materialized window (docs/serving.md).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import quant
from repro.models import common, rope
from repro.models.common import DATA, MODEL, linear, make_linear, make_norm, rmsnorm

NEG_INF = -1e30


# ----------------------------------------------------------------- KV cache


def make_kv_cache(batch: int, window: int, kv_dim: int, n_layers: int, dtype):
    """Stacked-over-layers ring-buffer cache (scan xs layout).

    Model-aware construction (int8 KV planes, MLA's native dummy v,
    hybrid state) lives in :func:`repro.models.lm.make_cache` — this
    helper stays the bare symmetric ring.
    """
    return {
        "k": jnp.zeros((n_layers, batch, window, kv_dim), dtype),
        "v": jnp.zeros((n_layers, batch, window, kv_dim), dtype),
        "pos": jnp.full((n_layers, batch, window), -1, jnp.int32),
    }


def kv_is_int8(cache_layer) -> bool:
    """True when the cache dict stores the int8 KV wire (scale planes)."""
    return "k_scale" in cache_layer


def quantize_kv(x: jax.Array):
    """Write-side KV quantization: one symmetric scale per token row."""
    return quant.quantize_rows(x)


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype):
    """Read-side KV dequantization (inverse of :func:`quantize_kv`)."""
    return quant.dequantize_rows(q, scale, dtype=dtype)


def kv_roundtrip(x: jax.Array, dtype=None):
    """``dequantize(quantize(x))`` per token row — what a cache write
    followed by a cache read returns.  Prefill attends over this under
    the int8 KV wire so one-shot prefill sees exactly the K/V that
    stepped decode will read back (tokens stay parity with stepping)."""
    q, s = quantize_kv(x)
    return dequantize_kv(q, s, dtype or x.dtype)


def ring_window(cache_layer, dtype):
    """The ring cache's read boundary: ``(k [B, W, Dk], v [B, W, Dv])``
    in compute ``dtype`` — each plane dequantized iff it carries a scale
    plane, passed through unchanged otherwise (MLA caches quantize only
    the latent ``k``; the 1-wide dummy ``v`` stays native).  Everything
    above this call (:func:`mha`, :func:`_mla_absorbed`) is wire-format
    agnostic."""
    k, v = cache_layer["k"], cache_layer["v"]
    if "k_scale" in cache_layer:
        k = dequantize_kv(k, cache_layer["k_scale"], dtype)
    if "v_scale" in cache_layer:
        v = dequantize_kv(v, cache_layer["v_scale"], dtype)
    return k, v


def _update_ring(cache_layer, new_k, new_v, pos: jax.Array, window: int):
    """Insert one step (S_new == 1) at slot pos % window.  ``pos`` scalar.

    Under the int8 KV wire the new row quantizes here — at write time —
    and its per-token scale lands in the ``k_scale``/``v_scale`` plane.
    """
    b = new_k.shape[0]
    slot = jnp.mod(pos, window)
    out = {}
    for name, new in (("k", new_k), ("v", new_v)):
        sname = name + "_scale"
        if sname in cache_layer:
            new, sc = quantize_kv(new)
            out[sname] = jax.lax.dynamic_update_slice(
                cache_layer[sname], sc, (0, slot)
            )
        out[name] = jax.lax.dynamic_update_slice(
            cache_layer[name], new, (0, slot, 0)
        )
    out["pos"] = jax.lax.dynamic_update_slice(
        cache_layer["pos"],
        jnp.full((b, 1), pos, jnp.int32),
        (0, slot),
    )
    return out


def fill_ring(cache_layer, new_k, new_v, s: int, quantized=None):
    """Write a whole prompt (absolute positions ``0..s-1``) into the ring.

    The prefill-side counterpart of :func:`_update_ring`: keeps the last
    ``min(window, s)`` tokens at slots ``pos % window`` — exactly the
    state per-token stepping would have left behind (same per-token
    quantization under the int8 KV wire).  ``new_k/new_v`` are
    ``[B, S, KVD]`` (already RoPE'd where applicable).

    ``quantized`` optionally maps a plane name to its precomputed
    ``(q, scale)`` pair: prefill quantizes each plane ONCE, attends over
    its dequantization, and hands the same pair here instead of paying a
    second quantization pass (bit-identical either way).
    """
    window = cache_layer["k"].shape[1]
    b = new_k.shape[0]
    take = min(window, s)
    sel = jnp.arange(s - take, s)
    slots = jnp.mod(sel, window)
    out = {}
    for name, new in (("k", new_k), ("v", new_v)):
        sname = name + "_scale"
        if sname in cache_layer:
            if quantized is not None and name in quantized:
                new, sc = quantized[name]
            else:
                new, sc = quantize_kv(new)
            out[sname] = cache_layer[sname].at[:, slots].set(sc[:, sel])
        out[name] = cache_layer[name].at[:, slots].set(new[:, sel])
    out["pos"] = cache_layer["pos"].at[:, slots].set(
        jnp.broadcast_to(sel, (b, take)).astype(jnp.int32)
    )
    return out


# ----------------------------------------------------------- paged KV cache
#
# Paged counterpart of the ring above (serve/paged_cache.py): per-layer
# k/v live in fixed-size pages [N_pages, PS, D], a request's logical
# position p maps to (page_table[p // PS], p % PS), and page 0 is the
# null page — never allocated, pads every table, absorbs padding writes
# with pos = -1 so gathers stay uniform and masking derives from the
# position array exactly like the ring.

NULL_PAGE = 0


def _paged_flat_idx(positions, page_tables, page_size: int):
    """[B, S] absolute positions (-1 = padding) -> flat page-pool indices.

    Padding tokens are routed to (null page, slot 0); their pos writes
    carry -1 (see paged_update_pos) so reads mask them.
    """
    valid = positions >= 0
    p_safe = jnp.maximum(positions, 0)
    logical = jnp.minimum(p_safe // page_size, page_tables.shape[1] - 1)
    page = jnp.take_along_axis(page_tables, logical, axis=1)
    page = jnp.where(valid, page, NULL_PAGE)
    slot = jnp.where(valid, p_safe % page_size, 0)
    return (page * page_size + slot).reshape(-1), valid


def paged_update(cache_layer, new_k, new_v, positions, page_tables):
    """Scatter a [B, S, D] chunk of new K/V into non-contiguous pages.

    ``cache_layer`` holds ``k/v [N_pages, PS, D*]`` and — under the int8
    KV wire — ``k_scale/v_scale [N_pages, PS]`` planes; positions
    [B, S]; page_tables [B, P].  Rows at different sequence positions
    write to different pages in the same jitted step — the write half of
    continuous batching.  Int8 caches quantize each new token row here
    (write time), scattering values and per-token scales to the same
    flat slot, so padding rows land on the null page like every other
    write.  Returns the updated planes (``pos`` excluded — the shared
    slot table has its own update, :func:`paged_update_pos`).
    """
    ps = cache_layer["k"].shape[1]
    flat, _ = _paged_flat_idx(positions, page_tables, ps)
    out = {}
    for name, new in (("k", new_k), ("v", new_v)):
        c = cache_layer[name]
        sname = name + "_scale"
        if sname in cache_layer:
            new, sc = quantize_kv(new)
            sf = cache_layer[sname].reshape(-1)
            out[sname] = sf.at[flat].set(sc.reshape(-1)).reshape(
                cache_layer[sname].shape
            )
        cf = c.reshape(-1, c.shape[-1])
        cf = cf.at[flat].set(new.reshape(-1, new.shape[-1]).astype(cf.dtype))
        out[name] = cf.reshape(c.shape)
    return out


def paged_update_pos(pos_tbl, positions, page_tables):
    """Record the step's token positions in the shared [N_pages, PS] slot
    table.  Padding writes land on the null page with -1, preserving the
    "null page is always masked" invariant."""
    ps = pos_tbl.shape[1]
    flat, valid = _paged_flat_idx(positions, page_tables, ps)
    vals = jnp.where(valid, positions, -1).reshape(-1).astype(jnp.int32)
    return pos_tbl.reshape(-1).at[flat].set(vals).reshape(pos_tbl.shape)


def paged_read(cache_layer, pos_tbl, page_tables, dtype=None):
    """Gather each request's pages into a contiguous logical window.

    Returns (k [B, P*PS, Dk], v [B, P*PS, Dv], pos [B, P*PS]) — the same
    (values, slot-positions) interface the ring presents, so `mha`'s
    position-derived masking needs no paged special case.  This is the
    paged cache's read boundary: the window is delivered in the compute
    ``dtype`` — int8 planes dequantize to it (gathered values × gathered
    per-token scales) and native planes are cast (a no-op when the
    caller passes the model compute dtype, which every model path does:
    a bf16 config must not silently upcast its gathered window to f32
    and double the materialized bytes).  ``dtype=None`` keeps the
    historical f32 default for standalone/bench/test use.  Stale
    values/scales on recycled pages are harmless — masking derives from
    the (scrubbed) position table, and dequantized garbage is finite, so
    its softmax terms are exactly zero.

    The gather itself is one of two paged-attention implementations: the
    fused Pallas kernel (``kernels/paged_attn.py``) walks the page table
    in-kernel and never materializes this window — selection happens in
    the forward passes below via ``SparsityConfig.paged_attn``.
    """
    b, p = page_tables.shape
    ps = cache_layer["k"].shape[1]
    if dtype is None:
        dtype = jnp.float32

    def read(name):
        c = cache_layer[name]
        win = c[page_tables].reshape(b, p * ps, c.shape[-1])
        sname = name + "_scale"
        if sname in cache_layer:
            s_win = cache_layer[sname][page_tables].reshape(b, p * ps)
            win = dequantize_kv(win, s_win, dtype)
        else:
            win = win.astype(dtype)
        return win

    pos_win = pos_tbl[page_tables].reshape(b, p * ps)
    return read("k"), read("v"), pos_win


def _paged_attn_impl(
    sp, b: int, sg: int, ps: int, dk: int, kv_heads: int = 1
) -> str:
    """Resolve the paged-attention implementation for this call site:
    the explicit knob (``SparsityConfig.paged_attn``, threaded from
    ``ServeConfig.paged_attn``) wins; ``"auto"`` takes the gather path
    where the compiled kernel has no block for the head width
    (``paged_attn.supports``) and otherwise consults ``kernels/autotune``
    (benchmark cache → backend heuristic — fused on TPU, gather
    elsewhere; docs/serving.md has the fallback rules)."""
    mode = getattr(sp, "paged_attn", "auto") if sp is not None else "auto"
    if mode != "auto":
        return mode
    from repro.kernels import autotune, paged_attn

    if not paged_attn.supports(kv_heads, dk):
        return "gather"
    return autotune.get_paged_attn_impl(b, sg, ps, dk)


# ------------------------------------------------------------ core attention


def _mask_bias(q_pos, k_pos, window: Optional[int]):
    """[B, S, T] float32 bias from absolute positions (-1 k_pos ⇒ invalid)."""
    valid = (k_pos[:, None, :] >= 0) & (k_pos[:, None, :] <= q_pos[:, :, None])
    if window is not None:
        valid &= k_pos[:, None, :] > (q_pos[:, :, None] - window)
    return jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)


def mha(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,  # [B, T, KV, D]
    v: jax.Array,  # [B, T, KV, Dv]
    q_pos: jax.Array,  # [B, S]
    k_pos: jax.Array,  # [B, T]
    *,
    window: Optional[int] = None,
    chunk: Optional[int] = None,
    softmax_scale: Optional[float] = None,
) -> jax.Array:
    """Grouped-query attention with position-derived causal/window masking.

    Never materializes repeated KV heads; query-chunked (scan) above
    ``chunk`` to bound the [S, T] logits working set (flash-style).
    """
    b, s, h, d = q.shape
    t, kv = k.shape[1], k.shape[2]
    g = h // kv
    scale = softmax_scale or 1.0 / math.sqrt(d)

    def block(qc, qp):  # qc [B, Sc, H, D] -> [B, Sc, H, Dv]
        sc = qc.shape[1]
        qg = qc.reshape(b, sc, kv, g, d)
        logits = jnp.einsum(
            "bskgd,btkd->bkgst", qg, k, preferred_element_type=jnp.float32
        ) * scale
        bias = _mask_bias(qp, k_pos, window)[:, None, None, :, :]
        logits = logits + bias
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum(
            "bkgst,btke->bskge", probs.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        )
        return out.reshape(b, sc, h, v.shape[-1]).astype(q.dtype)

    if chunk is None or s <= chunk or s % chunk != 0:
        return block(q, q_pos)

    nc = s // chunk
    qs = q.reshape(b, nc, chunk, h, d).transpose(1, 0, 2, 3, 4)
    ps = q_pos.reshape(b, nc, chunk).transpose(1, 0, 2)
    outs = jax.lax.map(lambda args: block(*args), (qs, ps))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, s, h, v.shape[-1])


# ------------------------------------------------- flash-decode (seq-parallel)


def flash_decode(q, cache_layer, new_k, new_v, decode_pos, window_mask, ctx):
    """Sequence-parallel decode attention (§Perf-A2).

    The KV cache window is sharded over the ``model`` axis (in_spec
    ``P(batch, model, None)``); each shard updates its ring slot if it
    owns it, computes partial attention over its local slots, and the
    partial softmax statistics are merged with a logsumexp correction via
    three tiny psums ([B,H]-sized) — instead of GSPMD's fallback of
    all-gathering the whole cache in f32 (measured 21 GB/step on
    qwen1.5-110b decode_32k).  Numerically identical to full attention.

    q [B,1,H,D]; cache k/v [B,W,KVD]; new_k/new_v [B,1,KVD];
    returns (out [B,1,H,Dv], new cache dict).
    """
    from jax.sharding import PartitionSpec as P

    ea, ba = ctx.expert_axis, ctx.batch_axes
    mesh = ctx.mesh
    n_shards = dict(zip(mesh.axis_names, mesh.devices.shape))[ea]
    b, w, kvd = cache_layer["k"].shape
    h, d = q.shape[2], q.shape[3]
    dv = cache_layer["v"].shape[-1] // (kvd // d) if kvd % d == 0 else None
    kv = kvd // d
    g = h // kv
    w_l = w // n_shards
    scale = 1.0 / math.sqrt(d)

    def local_fn(q_l, k_c, v_c, pos_c, nk, nv):
        # shard-local ring update
        idx = jax.lax.axis_index(ea)
        slot = jnp.mod(decode_pos, w)
        owner = slot // w_l
        lslot = jnp.mod(slot, w_l)
        is_mine = owner == idx
        k_upd = jax.lax.dynamic_update_slice(k_c, nk, (0, lslot, 0))
        v_upd = jax.lax.dynamic_update_slice(v_c, nv, (0, lslot, 0))
        p_upd = jax.lax.dynamic_update_slice(
            pos_c, jnp.full((q_l.shape[0], 1), decode_pos, jnp.int32), (0, lslot)
        )
        k_c = jnp.where(is_mine, k_upd, k_c)
        v_c = jnp.where(is_mine, v_upd, v_c)
        pos_c = jnp.where(is_mine, p_upd, pos_c)

        bl = q_l.shape[0]
        kk = k_c.reshape(bl, w_l, kv, d)
        vv = v_c.reshape(bl, w_l, kv, v_c.shape[-1] // kv)
        qg = q_l.reshape(bl, 1, kv, g, d)
        logits = jnp.einsum(
            "bskgd,btkd->bkgst", qg, kk, preferred_element_type=jnp.float32
        ) * scale  # [B,KV,G,1,W_l]
        qpos = jnp.full((bl, 1), decode_pos, jnp.int32)
        bias = _mask_bias(qpos, pos_c, window_mask)[:, None, None, :, :]
        logits = logits + bias
        m_loc = jnp.max(logits, axis=-1, keepdims=True)  # [B,KV,G,1,1]
        m_glob = jax.lax.pmax(m_loc, ea)
        p = jnp.exp(logits - m_glob)
        l_loc = jnp.sum(p, axis=-1, keepdims=True)
        o_loc = jnp.einsum(
            "bkgst,btke->bskge", p.astype(vv.dtype), vv,
            preferred_element_type=jnp.float32,
        )  # [B,1,KV,G,Dv]
        l_glob = jax.lax.psum(l_loc, ea)
        o_glob = jax.lax.psum(o_loc, ea)
        out = o_glob / jnp.maximum(
            l_glob[:, :, :, :, 0][..., None].transpose(0, 3, 1, 2, 4), 1e-30
        )
        out = out.reshape(bl, 1, h, -1).astype(q_l.dtype)
        return out, k_c, v_c, pos_c

    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            P(ba, None, None, None),  # q (replicated over model)
            P(ba, ea, None),          # cache k: window-sharded
            P(ba, ea, None),          # cache v
            P(ba, ea),                # cache pos
            P(ba, None, None),        # new k
            P(ba, None, None),        # new v
        ),
        out_specs=(
            P(ba, None, None, None),
            P(ba, ea, None),
            P(ba, ea, None),
            P(ba, ea),
        ),
        check_vma=False,
    )
    out, k_c, v_c, pos_c = fn(
        q, cache_layer["k"], cache_layer["v"], cache_layer["pos"], new_k, new_v
    )
    return out, {"k": k_c, "v": v_c, "pos": pos_c}


# ------------------------------------------------------------------- GQA


def make_gqa(key, cfg, dtype):
    d, h, kvh, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim()
    ks = jax.random.split(key, 4)
    params, specs = {}, {}
    params["wq"], specs["wq"] = make_linear(
        ks[0], d, h * dh, bias=cfg.qkv_bias, dtype=dtype, spec=P(DATA, MODEL)
    )
    params["wk"], specs["wk"] = make_linear(
        ks[1], d, kvh * dh, bias=cfg.qkv_bias, dtype=dtype, spec=P(DATA, MODEL)
    )
    params["wv"], specs["wv"] = make_linear(
        ks[2], d, kvh * dh, bias=cfg.qkv_bias, dtype=dtype, spec=P(DATA, MODEL)
    )
    params["wo"], specs["wo"] = make_linear(
        ks[3], h * dh, d, dtype=dtype, spec=P(MODEL, DATA)
    )
    return params, specs


def gqa_forward(
    p,
    x: jax.Array,  # [B, S, d]
    cfg,
    positions: jax.Array,  # [B, S]
    *,
    layer_idx=None,
    cache_layer=None,  # ring-buffer dict or None
    decode_pos: Optional[jax.Array] = None,  # scalar step for decode
    rope_cs=None,  # optional precomputed (cos, sin) (M-RoPE)
    causal: bool = True,
    page_tables: Optional[jax.Array] = None,  # [B, P] -> paged cache mode
):
    b, s, d = x.shape
    h, kvh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim()
    sp, li = cfg.sparsity, layer_idx
    # One DAP+pack shared by all three projections (packed serving); the
    # dense/training path passes x through unchanged.
    xin = common.maybe_pack_input(x, (p["wq"], p["wk"], p["wv"]), sp, li)
    q = linear(p["wq"], xin, sparsity=sp, layer_idx=li).reshape(b, s, h, dh)
    k = linear(p["wk"], xin, sparsity=sp, layer_idx=li).reshape(b, s, kvh, dh)
    v = linear(p["wv"], xin, sparsity=sp, layer_idx=li).reshape(b, s, kvh, dh)
    if rope_cs is None:
        cos, sin = rope.rope_cos_sin(positions, dh, cfg.rope_theta)
    else:
        cos, sin = rope_cs
    q = rope.apply_rope(q, cos, sin)
    k = rope.apply_rope(k, cos, sin)

    if page_tables is not None:
        # Paged cache: per-ROW positions (requests at different sequence
        # offsets share one step), write-then-attend over non-contiguous
        # pages.  cache_layer["pos"] must already hold this step's
        # positions (lm.paged_step writes the shared table once, before
        # the layer scan).  Two implementations share the write half:
        # "fused" walks the page table in-kernel (kernels/paged_attn.py,
        # online softmax + fused int8 dequant — the [B, P*PS, D] window
        # is never materialized); "gather" materializes it via
        # paged_read and reuses mha.
        new_kv = paged_update(
            cache_layer,
            k.reshape(b, s, kvh * dh), v.reshape(b, s, kvh * dh),
            positions, page_tables,
        )
        ps_sz = cache_layer["k"].shape[1]
        if _paged_attn_impl(sp, b, s * (h // kvh), ps_sz, dh, kvh) == "fused":
            from repro.kernels import paged_attn as paged_attn_k

            out = paged_attn_k.paged_attn_cache_layer(
                q, new_kv, cache_layer["pos"], page_tables, positions,
                kv_heads=kvh, window=cfg.sliding_window, out_dtype=x.dtype,
            )
        else:
            k_win, v_win, pos_win = paged_read(
                new_kv, cache_layer["pos"], page_tables, dtype=x.dtype
            )
            t = k_win.shape[1]
            out = mha(
                q,
                k_win.reshape(b, t, kvh, dh),
                v_win.reshape(b, t, kvh, dh),
                positions, pos_win,
                window=cfg.sliding_window, chunk=None,
            )
        y = linear(p["wo"], out.reshape(b, s, h * dh), sparsity=sp, layer_idx=li)
        return y, new_kv

    if cache_layer is not None and decode_pos is None:
        # Single-pass prefill: full-sequence attention over the fresh K/V
        # (identical math to the cache-less path below) while the same
        # projections fill the ring — the layer stack runs ONCE per
        # prompt, no K/V-recompute second pass (see lm.prefill).
        k_flat = k.reshape(b, s, kvh * dh)
        v_flat = v.reshape(b, s, kvh * dh)
        pre = None
        if kv_is_int8(cache_layer):
            # quantize ONCE: the ring stores these planes, and attention
            # runs over their dequantization, so prefill sees exactly the
            # K/V that stepped decode reads back
            qk, sk = quantize_kv(k_flat)
            qv, sv = quantize_kv(v_flat)
            pre = {"k": (qk, sk), "v": (qv, sv)}
            k = dequantize_kv(qk, sk, x.dtype).reshape(b, s, kvh, dh)
            v = dequantize_kv(qv, sv, x.dtype).reshape(b, s, kvh, dh)
        new_cache = fill_ring(cache_layer, k_flat, v_flat, s, quantized=pre)
        out = mha(
            q, k, v, positions, positions,
            window=cfg.sliding_window,
            chunk=cfg.attn_chunk if s > cfg.attn_chunk else None,
        )
        y = linear(p["wo"], out.reshape(b, s, h * dh), sparsity=sp, layer_idx=li)
        return y, new_cache

    if cache_layer is not None:
        window = cache_layer["k"].shape[1]
        from repro.sharding import context as dist_ctx

        ctx = dist_ctx.get_context()
        # flash_decode shards the full-precision ring over the model axis;
        # the int8 KV wire takes the plain ring path (sharded int8 window
        # merging is not implemented — see docs/quantization.md)
        if ctx is not None and s == 1 and not kv_is_int8(cache_layer):
            sizes = dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape))
            n_sh = sizes[ctx.expert_axis]
            n_batch = 1
            for a in ctx.batch_axes:
                n_batch *= sizes.get(a, 1)
            if window % n_sh == 0 and window >= n_sh and b % n_batch == 0:
                out, new_cache = flash_decode(
                    q,
                    cache_layer,
                    k.reshape(b, s, kvh * dh),
                    v.reshape(b, s, kvh * dh),
                    decode_pos,
                    cfg.sliding_window,
                    ctx,
                )
                y = linear(p["wo"], out.reshape(b, s, h * dh),
                           sparsity=sp, layer_idx=li)
                return y, new_cache
        new_cache = _update_ring(
            cache_layer,
            k.reshape(b, s, kvh * dh),
            v.reshape(b, s, kvh * dh),
            decode_pos,
            window,
        )
        kk, vv = ring_window(new_cache, x.dtype)
        kk = kk.reshape(b, window, kvh, dh)
        vv = vv.reshape(b, window, kvh, dh)
        out = mha(
            q, kk, vv, positions, new_cache["pos"],
            window=cfg.sliding_window, chunk=None,
        )
        return linear(p["wo"], out.reshape(b, s, h * dh), sparsity=sp, layer_idx=li), new_cache

    k_pos = positions if causal else jnp.zeros_like(positions)
    out = mha(
        q, k, v, positions, k_pos,
        window=cfg.sliding_window if causal else None,
        chunk=cfg.attn_chunk if s > cfg.attn_chunk else None,
    )
    return linear(p["wo"], out.reshape(b, s, h * dh), sparsity=sp, layer_idx=li), None


# ------------------------------------------------------------------- MLA


def make_mla(key, cfg, dtype):
    m, d, h = cfg.mla, cfg.d_model, cfg.n_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    params, specs = {}, {}
    params["q_down"], specs["q_down"] = make_linear(ks[0], d, m.q_lora_rank, dtype=dtype, spec=P(DATA, None))
    params["q_norm"], specs["q_norm"] = make_norm(m.q_lora_rank)
    params["q_up"], specs["q_up"] = make_linear(ks[1], m.q_lora_rank, h * qk, dtype=dtype, spec=P(None, MODEL))
    params["kv_down"], specs["kv_down"] = make_linear(
        ks[2], d, m.kv_lora_rank + m.qk_rope_head_dim, dtype=dtype, spec=P(DATA, None)
    )
    params["kv_norm"], specs["kv_norm"] = make_norm(m.kv_lora_rank)
    params["kv_up"], specs["kv_up"] = make_linear(
        ks[3], m.kv_lora_rank, h * (m.qk_nope_head_dim + m.v_head_dim), dtype=dtype, spec=P(None, MODEL)
    )
    params["wo"], specs["wo"] = make_linear(ks[4], h * m.v_head_dim, d, dtype=dtype, spec=P(MODEL, DATA))
    return params, specs


def _mla_absorb_q(q_nope, w_kv_up, m, out_dtype):
    """Absorb q through the k half of ``kv_up`` per head
    (``[B, S, H, lora]``) — the score-side leg shared by the gathered
    and fused absorbed paths."""
    wk = w_kv_up[..., : m.qk_nope_head_dim]  # [lora, H, nope]
    return jnp.einsum(
        "bshn,lhn->bshl", q_nope, wk.astype(q_nope.dtype),
        preferred_element_type=jnp.float32,
    ).astype(out_dtype)


def _mla_up_project(ctx, w_kv_up, m, out_dtype):
    """Project the latent context through the v half of ``kv_up``
    (``[B, S, H, dv]``) — the output leg shared by both absorbed paths."""
    wv = w_kv_up[..., m.qk_nope_head_dim :]  # [lora, H, dv]
    return jnp.einsum(
        "bshl,lhv->bshv", ctx.astype(out_dtype), wv.astype(out_dtype),
        preferred_element_type=jnp.float32,
    ).astype(out_dtype)


def _mla_absorbed(q_nope, q_rope, lat, q_pos, k_pos, w_kv_up, m, scale, out_dtype):
    """Absorbed-form MLA attention over a latent window.

    ``lat [B, T, lora+rope]`` is the (c_kv ‖ k_rope) latent — from the
    ring or gathered from pages — with slot positions ``k_pos [B, T]``.
    q is absorbed through kv_up per head, so the latent cache is never
    expanded.  bf16 operands with f32 accumulation — never materializes
    an f32 copy of the latent cache (that would double decode HBM
    traffic).  Returns [B, S, H, dv].
    """
    lora = m.kv_lora_rank
    c_all = lat[..., :lora]
    kr_all = lat[..., lora:]
    q_abs = _mla_absorb_q(q_nope, w_kv_up, m, out_dtype)
    logits = (
        jnp.einsum("bshl,btl->bhst", q_abs, c_all,
                   preferred_element_type=jnp.float32)
        + jnp.einsum("bshr,btr->bhst", q_rope, kr_all,
                     preferred_element_type=jnp.float32)
    ) * scale
    bias = _mask_bias(q_pos, k_pos, None)[:, None, :, :]
    probs = jax.nn.softmax(logits + bias, axis=-1)
    ctx = jnp.einsum(
        "bhst,btl->bshl", probs.astype(c_all.dtype), c_all,
        preferred_element_type=jnp.float32,
    )
    return _mla_up_project(ctx, w_kv_up, m, out_dtype)


def _mla_absorbed_fused(
    q_nope, q_rope, cache_layer, pos_tbl, page_tables, q_pos,
    w_kv_up, m, scale, out_dtype,
):
    """Absorbed-form MLA through the fused paged kernel.

    Same math as :func:`_mla_absorbed` over a paged latent cache, but
    the latent window is never gathered: q absorbs through ``kv_up`` per
    head, the ``(q_abs ‖ q_rope)`` concat scores against the raw
    ``(c_kv ‖ k_rope)`` latent pages streamed in-kernel (``kv_heads=1``
    — the latent is shared across heads), and the context contraction
    reuses the **latent prefix of the same k page** as v
    (``latent_dv``), so MLA's 1-wide dummy v pages are never touched.
    """
    from repro.kernels import paged_attn as paged_attn_k

    lora = m.kv_lora_rank
    q_abs = _mla_absorb_q(q_nope, w_kv_up, m, out_dtype)
    q_cat = jnp.concatenate([q_abs, q_rope.astype(out_dtype)], axis=-1)
    ctx = paged_attn_k.paged_attn_cache_layer(
        q_cat, cache_layer, pos_tbl, page_tables, q_pos,
        kv_heads=1, softmax_scale=scale, latent_dv=lora, out_dtype=out_dtype,
    )  # [B, S, H, lora]
    return _mla_up_project(ctx, w_kv_up, m, out_dtype)


def mla_forward(
    p,
    x: jax.Array,
    cfg,
    positions: jax.Array,
    *,
    layer_idx=None,
    cache_layer=None,
    decode_pos=None,
    page_tables: Optional[jax.Array] = None,
):
    """MLA.  Cache stores the *latent* (c_kv ‖ k_rope) — the paper-faithful
    MLA memory win.  Prefill/train materializes per-head K/V; decode uses
    the absorbed form (q absorbed through kv_up) to avoid expanding the
    cache."""
    m = cfg.mla
    b, s, d = x.shape
    h = cfg.n_heads
    sp, li = cfg.sparsity, layer_idx
    qk_rope, qk_nope, dv = m.qk_rope_head_dim, m.qk_nope_head_dim, m.v_head_dim
    scale = 1.0 / math.sqrt(qk_nope + qk_rope)

    # Both down-projections read the residual stream: share one DAP+pack.
    xin = common.maybe_pack_input(x, (p["q_down"], p["kv_down"]), sp, li)
    cq = rmsnorm(linear(p["q_down"], xin, sparsity=sp, layer_idx=li), p["q_norm"])
    q = linear(p["q_up"], cq, sparsity=sp, layer_idx=li).reshape(b, s, h, qk_nope + qk_rope)
    q_nope, q_rope = q[..., :qk_nope], q[..., qk_nope:]
    cos, sin = rope.rope_cos_sin(positions, qk_rope, cfg.rope_theta)
    q_rope = rope.apply_rope(q_rope, cos, sin)

    kv = linear(p["kv_down"], xin, sparsity=sp, layer_idx=li)
    c_kv = rmsnorm(kv[..., : m.kv_lora_rank], p["kv_norm"])
    k_rope = kv[..., m.kv_lora_rank :][:, :, None, :]  # 1 shared head
    k_rope = rope.apply_rope(k_rope, cos, sin)[:, :, 0, :]

    w_kv_up = p["kv_up"]["w"].reshape(m.kv_lora_rank, h, qk_nope + dv)

    if page_tables is not None:
        # Paged latent cache: write (c_kv ‖ k_rope) into this step's page
        # slots, then attend absorbed over non-contiguous pages — the
        # same math stepped decode runs, but with per-row positions
        # (v pages are the ring's 1-wide dummy).  "fused" streams the
        # latent pages through the in-kernel page-table walk; "gather"
        # materializes the latent window via paged_read first.
        latent = jnp.concatenate([c_kv, k_rope], axis=-1)
        new_kv = paged_update(
            cache_layer,
            latent, jnp.zeros((b, s, 1), latent.dtype),
            positions, page_tables,
        )
        ps_sz = cache_layer["k"].shape[1]
        lat_d = m.kv_lora_rank + qk_rope
        if _paged_attn_impl(sp, b, s * h, ps_sz, lat_d) == "fused":
            out = _mla_absorbed_fused(
                q_nope, q_rope, new_kv, cache_layer["pos"], page_tables,
                positions, w_kv_up, m, scale, x.dtype,
            )
        else:
            lat, _, pos_win = paged_read(
                new_kv, cache_layer["pos"], page_tables, dtype=x.dtype
            )
            out = _mla_absorbed(
                q_nope, q_rope, lat, positions, pos_win, w_kv_up, m, scale,
                x.dtype,
            )
        y = linear(p["wo"], out.reshape(b, s, h * dv), sparsity=sp, layer_idx=li)
        return y, new_kv

    if cache_layer is not None and decode_pos is None:
        # Single-pass prefill: materialized attention (below) + latent
        # ring fill in the same trace — the cache stores (c_kv ‖ k_rope),
        # exactly what per-token absorbed decode would have written.
        latent = jnp.concatenate([c_kv, k_rope], axis=-1)
        pre = None
        if kv_is_int8(cache_layer):
            # quantize the latent ONCE: the ring stores it, and the
            # materialized attention below reads its dequantization —
            # prefill and stepped decode then see the same bytes
            ql, sl = quantize_kv(latent)
            pre = {"k": (ql, sl)}
            lat_rt = dequantize_kv(ql, sl, x.dtype)
            c_kv = lat_rt[..., : m.kv_lora_rank]
            k_rope = lat_rt[..., m.kv_lora_rank :]
        new_cache = fill_ring(
            cache_layer, latent, jnp.zeros((b, s, 1), latent.dtype), s,
            quantized=pre,
        )
        cache_layer = None  # fall through to the materialized path
    else:
        new_cache = None

    if cache_layer is not None:
        window = cache_layer["k"].shape[1]
        latent = jnp.concatenate([c_kv, k_rope], axis=-1)  # [B, S, lora+rope]
        new_cache = _update_ring(
            cache_layer, latent, jnp.zeros((b, s, 1), latent.dtype), decode_pos, window
        )
        # absorbed scores over the ring window (shared with the paged
        # path); ring_window dequantizes the latent under the int8 wire
        lat_win, _ = ring_window(new_cache, x.dtype)
        out = _mla_absorbed(
            q_nope, q_rope, lat_win, positions, new_cache["pos"],
            w_kv_up, m, scale, x.dtype,
        )
        y = linear(p["wo"], out.reshape(b, s, h * dv), sparsity=sp, layer_idx=li)
        return y, new_cache

    kv_up = jnp.einsum("btl,lhe->bthe", c_kv, w_kv_up.astype(c_kv.dtype))
    k_nope, v = kv_up[..., :qk_nope], kv_up[..., qk_nope:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, qk_rope))], axis=-1
    )
    qq = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = mha(
        qq, k, v, positions, positions,
        chunk=cfg.attn_chunk if s > cfg.attn_chunk else None,
        softmax_scale=scale,
    )
    y = linear(p["wo"], out.reshape(b, s, h * dv), sparsity=sp, layer_idx=li)
    return y, new_cache


# --------------------------------------------------------------- cross-attn


def make_cross_attn(key, cfg, dtype):
    d, h, dh = cfg.d_model, cfg.n_heads, cfg.head_dim()
    ks = jax.random.split(key, 4)
    params, specs = {}, {}
    params["wq"], specs["wq"] = make_linear(ks[0], d, h * dh, dtype=dtype, spec=P(DATA, MODEL))
    params["wk"], specs["wk"] = make_linear(ks[1], d, h * dh, dtype=dtype, spec=P(DATA, MODEL))
    params["wv"], specs["wv"] = make_linear(ks[2], d, h * dh, dtype=dtype, spec=P(DATA, MODEL))
    params["wo"], specs["wo"] = make_linear(ks[3], h * dh, d, dtype=dtype, spec=P(MODEL, DATA))
    return params, specs


def cross_attn_forward(p, x, enc_kv, cfg, *, layer_idx=None):
    """x [B, S, d] attends to encoder output [B, T, d] (no mask)."""
    b, s, d = x.shape
    t = enc_kv.shape[1]
    h, dh = cfg.n_heads, cfg.head_dim()
    sp, li = cfg.sparsity, layer_idx
    kvin = common.maybe_pack_input(enc_kv, (p["wk"], p["wv"]), sp, li)
    q = linear(p["wq"], x, sparsity=sp, layer_idx=li).reshape(b, s, h, dh)
    k = linear(p["wk"], kvin, sparsity=sp, layer_idx=li).reshape(b, t, h, dh)
    v = linear(p["wv"], kvin, sparsity=sp, layer_idx=li).reshape(b, t, h, dh)
    qp = jnp.zeros((b, s), jnp.int32)
    kp = jnp.zeros((b, t), jnp.int32)
    out = mha(q, k, v, qp, kp, window=None, chunk=None)
    return linear(p["wo"], out.reshape(b, s, h * dh), sparsity=sp, layer_idx=li)
