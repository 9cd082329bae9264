"""Pallas TPU kernel: fused paged-decode attention with an in-kernel
page-table walk, online softmax, and fused int8-KV dequantization.

The gather path (``attention.paged_read`` + ``mha``) materializes every
request's logical window ``[B, P*page_size, D]`` in HBM each step — the
pages are read once, the dense window is written, and ``mha`` reads it
back (plus, under the int8 KV wire, the dequantized f32 copy).  This
kernel is the decode-path analogue of the DBB matmul kernels: the wire
format (non-contiguous pages, int8 values + per-token scales) streams
straight from HBM into VMEM and the dense intermediate never exists.

Mechanics (PagedAttention-style block tables × FlashAttention-2-style
online softmax; see PAPERS.md):

* ``page_tables [B, P] int32`` ride the grid as a **scalar-prefetch**
  operand, so each grid step's BlockSpec index map resolves
  ``tables[b, p]`` *before* the body runs — the DMA engine walks the
  page table, fetching physical page ``tables[b, p]`` directly from the
  page pool.  The null page (id 0) pads every table and is fetched like
  any other page; its slot positions are ``-1`` so masking removes it.
* Grid ``(B, KV_head, P)`` with the page walk innermost (arbitrary
  semantics).  Per (request, kv-head) the kernel keeps flash-style
  running statistics in VMEM scratch — ``acc [S*G, Dv]``, row max ``m``
  and normalizer ``l`` — rescaling by ``exp(m_prev - m_new)`` as pages
  stream through.  ``S*G`` rows cover a chunked-prefill slice (``S``
  query tokens × ``G`` grouped query heads per KV head), so one kernel
  serves mixed decode+prefill batches exactly like the gather path.
* The small per-slot planes — slot positions and the int8 wire's
  per-token scales, ``[N_pages, PS]`` — are gathered per request by XLA
  (``[B, P, PS]``: bytes the size of the page tables, not of the pages),
  because a one-page ``(1, PS)`` block of them breaks Mosaic's (8, 128)
  block rule; each request's ``[P, PS]`` block stays in VMEM and the
  kernel picks page ``p``'s row.  A page's K/V block is ``[PS, Dk]`` at
  lane offset ``kv_head * Dk`` (see :func:`supports`).
* Causal/window masking derives **only** from the gathered slot
  positions (``pos_tbl[tables[b, p]]``), exactly like ``mha``'s
  ``_mask_bias``: ``-1`` slots (empty, null-page, recycled-then-
  scrubbed) are invalid, ``k_pos <= q_pos`` is causality, and an
  optional sliding window bounds the lookback.  Stale values on a
  recycled page are finite garbage whose softmax terms are exactly zero
  (masked logits sit at ``NEG_INF``; if such a page streams before any
  valid key, the running stats rescale by ``exp(NEG_INF - m)`` == 0 on
  the first valid page, flushing the garbage) — the same invariant the
  gather path documents.
* Int8-KV caches (``k_scale``/``v_scale`` planes) dequantize **inside
  the page load**: the int8 tile and its page's row of per-token scales
  arrive in VMEM, the row becomes a column there (``_scale_col``) and
  the f32 multiply happens there, mirroring
  ``quant.dequantize_rows`` elementwise so the kernel sees exactly the
  values the gather path would have materialized.
* MLA's absorbed decode reuses the same kernel with ``kv_heads=1`` and
  ``latent_dv``: the page holds the ``(c_kv ‖ k_rope)`` latent, queries
  are the absorbed ``(q·W_kv_up ‖ q_rope)`` concat, and **v is the
  first ``latent_dv`` features of the (dequantized) k tile** — no
  second page stream for the 1-wide dummy v.

Numerics: logits/softmax statistics in f32 like ``mha``; the online
rescaling regroups the softmax sums per page, so float wires match the
gather path to fp-rounding (~1e-7 rel on f32 — tolerance discussion in
``docs/perf.md``) rather than bit-for-bit.  Validated in interpret mode
against both the gather path and the jnp online-softmax oracle
(``ref.paged_attn_ref``) in ``tests/test_paged_attn.py``, and compiled
ahead of time for a described v5e chip in ``tests/test_tpu_compile.py``;
interpret mode doubles as the CPU fallback so the fused wiring runs
everywhere.
Implementation selection (gather vs fused) lives in
``kernels/autotune.py`` (kind ``paged_attn``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # matches models/attention.py (finite: masked-logit math
#                  must stay NaN-free through exp/rescale)


def _page_row(ref, p):
    """Row ``p`` of a per-request ``[P, PS]`` table block -> ``[1, PS]``."""
    return ref[pl.ds(p, 1), :]


def _scale_col(row):
    """``[1, PS]`` per-token scales -> the ``[PS, 1]`` column that scales
    a page's rows.  A masked lane reduction with one non-zero term per
    row, so exact (Mosaic transposes no ``(1, PS)`` vector)."""
    ps = row.shape[-1]
    r = jax.lax.broadcasted_iota(jnp.int32, (ps, ps), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (ps, ps), 1)
    diag = jnp.where(r == c, jnp.broadcast_to(row, (ps, ps)), 0.0)
    return jnp.sum(diag, axis=1, keepdims=True)


def _paged_attn_kernel(
    tbl_ref,  # scalar prefetch: page_tables [B, P] int32 (walked by the specs)
    q_ref,  # [SG, Dk] — this (request, kv-head)'s grouped query rows
    qpos_ref,  # [SG, 1] int32 — each query row's position
    kpos_ref,  # [P, PS] int32 — the request's gathered slot positions
    k_ref,  # [PS, Dk] — this page, this kv-head
    *rest,  # [k_scale?], [v?, [v_scale?]], out, acc, m, l
    dv,
    window,
    scale,
    n_pp,
    latent,
    has_ks,
    has_vs,
    cdtype,
):
    del tbl_ref
    i = 0
    ks_ref = v_ref = vs_ref = None
    if has_ks:
        ks_ref = rest[i]
        i += 1
    if not latent:
        v_ref = rest[i]
        i += 1
        if has_vs:
            vs_ref = rest[i]
            i += 1
    o_ref, acc_ref, m_ref, l_ref = rest[i], rest[i + 1], rest[i + 2], rest[i + 3]

    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # page load; int8 pages dequantize HERE (per-token scale column),
    # elementwise-identical to quant.dequantize_rows at the gather
    # boundary, so everything downstream sees the gather path's values
    k = k_ref[...]  # [PS, Dk]
    if ks_ref is not None:
        k = (k.astype(jnp.float32) * _scale_col(_page_row(ks_ref, p))).astype(cdtype)

    q = q_ref[...]  # [SG, Dk]
    logits = (
        jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        * scale
    )  # [SG, PS]

    # masking from gathered slot positions only (mha._mask_bias semantics:
    # -1 ⇒ empty/null/scrubbed, causal, optional sliding window)
    kpos = _page_row(kpos_ref, p)  # [1, PS]
    qp = qpos_ref[...]  # [SG, 1]
    valid = (kpos >= 0) & (kpos <= qp)
    if window is not None:
        valid = valid & (kpos > qp - window)
    logits = logits + jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)

    # flash-style online-softmax update
    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_cur = jnp.max(logits, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    probs = jnp.exp(logits - m_new)
    if latent:
        v = k[:, :dv]  # MLA: v IS the latent prefix of the k page
    else:
        v = v_ref[...]
        if vs_ref is not None:
            v = (v.astype(jnp.float32) * _scale_col(_page_row(vs_ref, p))).astype(
                cdtype
            )
    pv = jnp.dot(probs.astype(v.dtype), v, preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = m_new
    l_ref[...] = alpha * l_prev + jnp.sum(probs, axis=-1, keepdims=True)

    @pl.when(p == n_pp - 1)
    def _flush():
        # l >= 1 for any row with a valid key (its own max attains
        # exp(0)); fully-masked padding rows normalize to the window
        # mean like mha's uniform softmax — garbage either way, and the
        # scheduler never samples them.  The max() is a /0 hedge only.
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = out.astype(o_ref.dtype)


def paged_attn_fused(
    q: jax.Array,  # [B, S, H, Dk] (S = chunk width; decode ⇒ 1)
    k_pages: jax.Array,  # [N_pages, PS, KV*Dk] (or latent [N, PS, Dk])
    v_pages: Optional[jax.Array],  # [N_pages, PS, KV*Dv]; None if latent
    pos_tbl: jax.Array,  # [N_pages, PS] int32 shared slot positions
    page_tables: jax.Array,  # [B, P] int32 (null-page padded)
    q_pos: jax.Array,  # [B, S] int32 (-1 = padding row)
    *,
    kv_heads: int,
    window: Optional[int] = None,
    softmax_scale: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,  # [N_pages, PS] f32 (int8 wire)
    v_scale: Optional[jax.Array] = None,
    latent_dv: Optional[int] = None,  # MLA: v = k_tile[:, :latent_dv]
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Fused paged attention: walks ``page_tables`` in-kernel and returns
    ``[B, S, H, Dv]`` without materializing the ``[B, P*PS, D]`` window.

    Drop-in for ``paged_read`` + ``mha`` (GQA, KV heads never repeated)
    and for the latent gather + ``_mla_absorbed`` score/context part
    (``kv_heads=1`` + ``latent_dv``).  ``interpret=True`` runs the same
    kernel body through the Pallas interpreter — the CPU/CI path.

    Blocks (Mosaic's rule: the last two block dims are multiples of
    (8, 128) or span the array): a page's K/V block is ``[PS, Dk]``
    taken at lane offset ``kv_head * Dk`` of the ``[PS, KV*Dk]`` page,
    so it compiles when ``Dk`` is a multiple of 128 or ``kv_heads == 1``
    (:func:`supports`).  The small per-slot planes (positions, int8
    scales) are gathered per request by XLA, ``[B, P, PS]``, and one
    ``[P, PS]`` block per request rides the grid; the kernel picks page
    ``p``'s row.  Query rows carry their positions as an ``[SG, 1]``
    column.
    """
    b, s, h, dk = q.shape
    assert h % kv_heads == 0, (h, kv_heads)
    g = h // kv_heads
    sg = s * g
    p_cnt = page_tables.shape[1]
    latent = latent_dv is not None
    assert k_pages.shape[-1] == kv_heads * dk, (k_pages.shape, kv_heads, dk)
    if latent:
        dv = latent_dv
        assert kv_heads == 1 and dv <= dk, (kv_heads, dv, dk)
    else:
        assert v_pages.shape[-1] % kv_heads == 0
        dv = v_pages.shape[-1] // kv_heads
    out_dtype = out_dtype or q.dtype
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(dk)
    ps = pos_tbl.shape[1]
    tables = page_tables.astype(jnp.int32)

    # head-major query layout: row r = s*G + g', matching mha's
    # [B, S, KV, G, D] grouping (head h = kv*G + g')
    q_r = q.reshape(b, s, kv_heads, g, dk).transpose(0, 2, 1, 3, 4)
    q_r = q_r.reshape(b, kv_heads, sg, dk)
    q_col = jnp.repeat(q_pos.astype(jnp.int32), g, axis=1)[..., None]

    per_req = pl.BlockSpec((None, p_cnt, ps), lambda bb, hh, pp, tbl: (bb, 0, 0))

    def page_spec(d):
        # index maps receive the scalar-prefetch ref last: the page-table
        # walk happens here, per grid step, before the body runs
        return pl.BlockSpec(
            (None, ps, d), lambda bb, hh, pp, tbl: (tbl[bb, pp], 0, hh)
        )

    in_specs = [
        pl.BlockSpec((None, None, sg, dk), lambda bb, hh, pp, tbl: (bb, hh, 0, 0)),
        pl.BlockSpec((None, sg, 1), lambda bb, hh, pp, tbl: (bb, 0, 0)),
        per_req,
        page_spec(dk),
    ]
    operands = [q_r, q_col, pos_tbl[tables], k_pages]
    if k_scale is not None:
        in_specs.append(per_req)
        operands.append(k_scale[tables])
    if not latent:
        in_specs.append(page_spec(dv))
        operands.append(v_pages)
        if v_scale is not None:
            in_specs.append(per_req)
            operands.append(v_scale[tables])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kv_heads, p_cnt),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (None, None, sg, dv), lambda bb, hh, pp, tbl: (bb, hh, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((sg, dv), jnp.float32),  # acc
            pltpu.VMEM((sg, 1), jnp.float32),  # running row max m
            pltpu.VMEM((sg, 1), jnp.float32),  # running normalizer l
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_attn_kernel,
            dv=dv,
            window=window,
            scale=scale,
            n_pp=p_cnt,
            latent=latent,
            has_ks=k_scale is not None,
            has_vs=v_scale is not None,
            cdtype=q.dtype,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv_heads, sg, dv), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="paged_attn",  # the kernel's name in compiled programs and traces
    )(tables, *operands)
    out = out.reshape(b, kv_heads, s, g, dv).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, s, h, dv)


def supports(kv_heads: int, dk: int) -> bool:
    """Whether the compiled kernel can take a page's per-head K block:
    a lane slice of a multiple of 128, or the whole page row."""
    return kv_heads == 1 or dk % 128 == 0


def paged_attn_cache_layer(
    q: jax.Array,
    cache_layer,  # per-layer paged dict: k/v (+ k_scale/v_scale) planes
    pos_tbl: jax.Array,
    page_tables: jax.Array,
    q_pos: jax.Array,
    *,
    kv_heads: int,
    window: Optional[int] = None,
    softmax_scale: Optional[float] = None,
    latent_dv: Optional[int] = None,
    out_dtype=None,
    interpret="auto",
) -> jax.Array:
    """Cache-dict front end: unpacks the paged planes (int8 wire scale
    planes included) and resolves ``interpret="auto"`` to the Pallas
    interpreter on non-TPU backends — the fallback rule that keeps CPU
    CI running the real kernel body (docs/serving.md)."""
    # chaos hook: a scoped fault injector (serve/faults.py) may force a
    # one-shot trace-time failure here, exercising the engine's logged
    # fallback to the gather path; no-op in production (local import —
    # serve/ depends on kernels/, not the reverse)
    from repro.serve.faults import check_fused

    check_fused()
    if interpret == "auto":
        interpret = jax.default_backend() != "tpu"
    return paged_attn_fused(
        q,
        cache_layer["k"],
        None if latent_dv is not None else cache_layer["v"],
        pos_tbl,
        page_tables,
        q_pos,
        kv_heads=kv_heads,
        window=window,
        softmax_scale=softmax_scale,
        k_scale=cache_layer.get("k_scale"),
        v_scale=None if latent_dv is not None else cache_layer.get("v_scale"),
        latent_dv=latent_dv,
        out_dtype=out_dtype,
        interpret=interpret,
    )
