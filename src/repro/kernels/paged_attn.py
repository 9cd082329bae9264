"""Pallas TPU kernel: fused paged attention with an in-kernel page-table
walk over each row's live pages, online softmax, and fused int8-KV
dequantization.

The gather path (``attention.paged_read`` + ``mha``) materializes every
request's logical window ``[B, P*page_size, D]`` in HBM each step — the
pages are read once, the dense window is written, and ``mha`` reads it
back (plus, under the int8 KV wire, the dequantized f32 copy).  This
kernel is the decode-path analogue of the DBB matmul kernels: the wire
format (non-contiguous pages, int8 values + per-token scales) streams
straight from HBM into VMEM and the dense intermediate never exists.

Mechanics (PagedAttention-style block tables × FlashAttention-2-style
online softmax; see PAPERS.md):

* **Only live pages are walked.**  Position ``t`` lives on logical page
  ``t // page_size`` (``attention._paged_flat_idx``), so every page
  past the page of a row's largest query position holds only keys that
  causality or ``pos = -1`` masks.  The wrapper turns ``q_pos`` into
  ``n_live [B]`` (:func:`live_pages`; 0 for a row whose queries are all
  padding) and passes it beside the page tables as a second
  **scalar-prefetch** operand.  Work follows the live context, not the
  table's width.
* **A block of pages at a time.**  Each row walks its table in blocks
  of :func:`pages_per_block` pages (``BLOCK_TOKENS`` keys), and only
  its live blocks: a block past ``n_live`` issues no copy and runs no
  body, and a row with ``n_live = 0`` does no work.  Each page comes
  whole, ``[PS, KV*Dk]`` — every KV head in one copy — and the body
  loops the KV heads with static lane slices at ``h * Dk``
  (:func:`supports`), keeping flash-style running statistics per head
  in VMEM scratch — ``acc [KV, S*G, Dv]``, row max ``m`` and
  normalizer ``l`` — rescaled by ``exp(m_prev - m_new)`` block by
  block.  ``S*G`` rows cover a chunked-prefill slice (``S`` query
  tokens × ``G`` grouped query heads per KV head), so one kernel serves
  mixed decode+prefill batches exactly like the gather path.
* **Two ways to fetch a block**, chosen from the pools' row width
  (:func:`_copy_by_hand`).  Rows of whole 128-lane tiles (GQA at
  ``Dk % 128 == 0``, the served path): grid ``(B,)``, the pools stay
  in HBM and the kernel loops over the row's live blocks, copying each
  page by hand into a double-buffered VMEM block so the next block
  loads while this one computes (the pattern of JAX's
  ``pallas/ops/tpu/paged_attention``).  Other rows (MLA's 288- or
  576-wide latent), which Mosaic cannot slice out of a pool in HBM:
  grid ``(B, n_blocks)``, ``ppb`` page BlockSpecs a pool resolve the
  block's pages from the table; a step past the row's live blocks maps
  to its last live block, so the pipeline copies nothing, and skips the
  body.
* The small per-slot planes — slot positions and the int8 wire's
  per-token scales, ``[N_pages, PS]`` — are gathered per request by XLA
  and laid out a block to a row, ``[B, n_blocks, ppb*PS]`` (bytes the
  size of the page tables, not of the pages); each request's block of
  rows stays in VMEM and the kernel picks block ``j``'s ``[1, T]`` row.
* Causal/window masking derives **only** from the gathered slot
  positions, exactly like ``mha``'s ``_mask_bias``: ``-1`` slots
  (empty, null-page, recycled-then-scrubbed, the null pages that pad a
  table to whole blocks) are invalid, ``k_pos <= q_pos`` is causality,
  and an optional sliding window bounds the lookback.  Stale values on
  a recycled page are finite garbage whose softmax terms are exactly
  zero (masked logits sit at ``NEG_INF``; if such a block streams
  before any valid key, the running stats rescale by
  ``exp(NEG_INF - m)`` == 0 on the first valid block, flushing the
  garbage) — the same invariant the gather path documents.
* Int8-KV caches (``k_scale``/``v_scale`` planes) dequantize **in
  the load**: the block's ``[1, T]`` scale row becomes a ``[T, 1]``
  column once a block and plane (:func:`_scale_col`), and each head's
  tile is the f32 product rounded to the compute dtype —
  elementwise ``quant.dequantize_rows`` at the gather boundary, so the
  kernel attends over the very K/V values the gather path (and
  one-shot prefill's ``kv_roundtrip``) sees.
* MLA's absorbed decode reuses the same kernel with ``kv_heads=1`` and
  ``latent_dv``: the page holds the ``(c_kv ‖ k_rope)`` latent, queries
  are the absorbed ``(q·W_kv_up ‖ q_rope)`` concat, and **v is the
  first ``latent_dv`` features of the dequantized k tile** — no second
  page stream for the 1-wide dummy v.

Numerics: logits/softmax statistics in f32 like ``mha``; the online
rescaling regroups the softmax sums per block, so float wires match the
gather path to fp-rounding (~1e-7 rel on f32 — tolerance discussion in
``docs/perf.md``) rather than bit-for-bit.  Under a bf16 compute dtype
the PV product keeps the probabilities to ~2^-17 (:func:`_pv`, two
bf16 MXU passes) where the gather path rounds them to bf16 once.  Validated in interpret mode
against both the gather path and the jnp online-softmax oracle
(``ref.paged_attn_ref``, tiled the same way) in
``tests/test_paged_attn.py``, and compiled ahead of time for a
described v5e chip in ``tests/test_tpu_compile.py``; interpret mode
doubles as the CPU fallback so the fused wiring runs everywhere.
Implementation selection (gather vs fused) lives in
``kernels/autotune.py`` (kind ``paged_attn``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # matches models/attention.py (finite: masked-logit math
#                  must stay NaN-free through exp/rescale)

# Scoped VMEM a call may use.  MLA's 64-token chunk scores 2560 query
# rows (40 heads) a block: its query and output blocks, statistics and
# [2560, 128] score temporaries take ~16.4 MiB, over Mosaic's default
# 16 MiB (v5e has 128 MiB of VMEM).
VMEM_LIMIT_BYTES = 32 * 2**20

# Keys a block of pages holds: one MXU tile (128) of key columns per
# head, so a block's scores fill the systolic array's width, and the
# fixed cost of a block (its copies' issue and waits, the softmax
# rescale) is paid once per 128 keys.
BLOCK_TOKENS = 128


def pages_per_block(page_size: int, p_cnt: int) -> int:
    """Pages the kernel fetches and scores together: ``BLOCK_TOKENS``
    keys' worth, no more than the table holds."""
    return max(1, min(p_cnt, BLOCK_TOKENS // page_size))


def live_pages(q_pos, page_size: int, p_cnt: int):
    """``[B, S]`` query positions (-1 = padding) -> ``[B]`` pages of each
    row's table that hold a key some query of the row may see: the page
    of its largest position and every page before it, at most ``p_cnt``;
    0 when every query of the row is padding.  Takes and returns numpy
    or jnp arrays alike (the host counter and the kernel share it)."""
    top = q_pos.max(axis=1)
    return ((top >= 0) * (top // page_size + 1)).clip(max=p_cnt)


def walked_pages(q_pos, page_size: int, p_cnt: int) -> int:
    """Pages the kernel walks for ``[B, S]`` query positions, per layer:
    each row's :func:`live_pages` rounded up to whole blocks, summed."""
    ppb = pages_per_block(page_size, p_cnt)
    live = live_pages(np.asarray(q_pos), page_size, p_cnt)
    return int((-(-live // ppb)).sum()) * ppb


def _scale_col(row):
    """``[1, T]`` per-token scales -> the ``[T, 1]`` column that scales a
    tile's rows.  A masked lane reduction with one non-zero term per
    row, so exact (Mosaic transposes no ``(1, T)`` vector); once a
    block and plane, shared by every KV head."""
    t = row.shape[-1]
    r = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    diag = jnp.where(r == c, jnp.broadcast_to(row, (t, t)), 0.0)
    return jnp.sum(diag, axis=1, keepdims=True)


def _attend_block(j, tile_k, tile_v, q_ref, qpos_ref, kpos_ref, ks_ref,
                  vs_ref, acc_ref, m_ref, l_ref, *, kv_heads, dk, dv, window,
                  scale, latent):
    """Block ``j`` of one request's table into every KV head's running
    softmax statistics.  ``tile_k(lo, width, col)`` / ``tile_v(...)``
    give the block's ``[T, width]`` keys / values at lane ``lo`` in the
    compute dtype, their rows scaled by the ``[T, 1]`` column ``col``
    first when it is given (the int8 wire)."""
    # masking from gathered slot positions only (mha._mask_bias
    # semantics: -1 ⇒ empty/null/scrubbed, causal, optional window)
    kpos = kpos_ref[pl.ds(j, 1), :]  # [1, T]
    qp = qpos_ref[...]  # [SG, 1]
    valid = (kpos >= 0) & (kpos <= qp)
    if window is not None:
        valid = valid & (kpos > qp - window)
    bias = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)  # [SG, T]
    ks = None if ks_ref is None else _scale_col(ks_ref[pl.ds(j, 1), :])
    vs = None if vs_ref is None else _scale_col(vs_ref[pl.ds(j, 1), :])

    for h in range(kv_heads):
        k = tile_k(h * dk, dk, ks)  # [T, Dk]
        logits = jax.lax.dot_general(
            q_ref[h], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [SG, T]
        logits = logits * scale + bias

        # flash-style online-softmax update
        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(logits - m_new)
        l_ref[h] = alpha * l_ref[h] + jnp.sum(probs, axis=-1, keepdims=True)
        # MLA: v is the (dequantized) latent prefix of the k tile
        v = k[:, :dv] if latent else tile_v(h * dv, dv, vs)
        acc_ref[h] = acc_ref[h] * alpha + _pv(probs, v)
        m_ref[h] = m_new


def _pv(probs, v):
    """``probs [SG, T]`` (f32) times ``v [T, Dv]``, accumulated in f32.
    A bf16 ``v`` holds its values exactly, so only the probabilities
    need more than one bf16 pass: their bf16 rounding ``hi`` and the
    remainder ``lo`` go through the MXU apiece, leaving the product
    ~2^-17 from f32 instead of the 2^-9 of one rounding (a rounding the
    block's larger running-max groups would otherwise move)."""
    hi = probs.astype(v.dtype)
    pv = jnp.dot(hi, v, preferred_element_type=jnp.float32)
    if v.dtype == jnp.float32:
        return pv
    lo = (probs - hi.astype(jnp.float32)).astype(v.dtype)
    return pv + jnp.dot(lo, v, preferred_element_type=jnp.float32)


def _dequant(tile, col, cdtype):
    """An f32 ``[T, width]`` tile in the compute dtype, its rows first
    scaled by ``col`` on the int8 wire: ``quant.dequantize_rows``'s
    product, rounded where the gather path rounds it."""
    return (tile if col is None else tile * col).astype(cdtype)


def _init_stats(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _flush(o_ref, acc_ref, l_ref):
    # l >= 1 for any row with a valid key (its own max attains exp(0));
    # fully-masked padding rows normalize to the block mean like mha's
    # uniform softmax, and a row with no live page reads 0 — garbage
    # either way, and the scheduler never samples them.  The max() is a
    # /0 hedge only.
    o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _unpack(rest, has_ks, has_vs, latent, n_pool):
    """Split the kernel's trailing refs: ``(ks, vs, k refs, v refs or
    None, the rest)``, ``n_pool`` refs a pool."""
    rest = list(rest)
    ks = rest.pop(0) if has_ks else None
    vs = rest.pop(0) if has_vs else None
    k = [rest.pop(0) for _ in range(n_pool)]
    v = None if latent else [rest.pop(0) for _ in range(n_pool)]
    return ks, vs, k, v, rest


def _dma_kernel(tbl_ref, live_ref, q_ref, qpos_ref, kpos_ref, *rest, ppb,
                has_ks, has_vs, cdtype, **math):
    """Grid ``(B,)``: the row's live blocks in a loop, each block's pages
    copied by hand from the pools in HBM into a double-buffered VMEM
    block — the next block loads while this one computes."""
    ks_ref, vs_ref, (k_hbm,), v_hbm, rest = _unpack(
        rest, has_ks, has_vs, math["latent"], 1)
    if v_hbm is None:
        o_ref, kbuf, sem, acc_ref, m_ref, l_ref = rest
        vbuf = None
    else:
        (v_hbm,) = v_hbm
        o_ref, kbuf, vbuf, sem, acc_ref, m_ref, l_ref = rest

    b = pl.program_id(0)
    n_blk = (live_ref[b] + ppb - 1) // ppb
    row0 = b * (kpos_ref.shape[0] * ppb)  # this row's first table entry

    def copies(j, slot):
        """The async copies of block ``j``'s pages into buffer ``slot``:
        one per page and pool, each the page's whole ``[PS, KV*D]`` row."""
        out = []
        for i in range(ppb):
            page = tbl_ref[row0 + j * ppb + i]
            out.append(pltpu.make_async_copy(
                k_hbm.at[page], kbuf.at[slot, i], sem.at[slot]))
            if v_hbm is not None:
                out.append(pltpu.make_async_copy(
                    v_hbm.at[page], vbuf.at[slot, i], sem.at[slot]))
        return out

    _init_stats(acc_ref, m_ref, l_ref)

    @pl.when(n_blk > 0)
    def _first():
        for c in copies(0, 0):
            c.start()

    def block(j, carry):
        slot = j % 2

        @pl.when(j + 1 < n_blk)
        def _prefetch():
            for c in copies(j + 1, 1 - slot):
                c.start()

        for c in copies(j, slot):
            c.wait()

        def tile(buf):
            def get(lo, width, col):  # [ppb, PS, width] -> [T, width]
                t = buf[slot, :, :, pl.ds(lo, width)]
                return _dequant(t.astype(jnp.float32).reshape(-1, width), col, cdtype)
            return get

        _attend_block(j, tile(kbuf), vbuf if vbuf is None else tile(vbuf), q_ref,
                      qpos_ref, kpos_ref, ks_ref, vs_ref, acc_ref, m_ref,
                      l_ref, **math)
        return carry

    jax.lax.fori_loop(0, n_blk, block, 0)
    _flush(o_ref, acc_ref, l_ref)


def _spec_kernel(tbl_ref, live_ref, q_ref, qpos_ref, kpos_ref, *rest, ppb,
                 has_ks, has_vs, cdtype, **math):
    """Grid ``(B, n_blocks)``: one block a step, its pages fetched by
    ``ppb`` page BlockSpecs a pool.  A step past the row's live blocks
    maps to the last live block (so the pipeline copies nothing) and
    skips the body."""
    del tbl_ref
    ks_ref, vs_ref, k_refs, v_refs, rest = _unpack(
        rest, has_ks, has_vs, math["latent"], ppb)
    o_ref, acc_ref, m_ref, l_ref = rest
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _init_stats(acc_ref, m_ref, l_ref)

    def tile(refs):
        def get(lo, width, col):  # ppb x [PS, width] -> [T, width]
            parts = [r[:, pl.ds(lo, width)].astype(jnp.float32) for r in refs]
            return _dequant(jnp.concatenate(parts, axis=0), col, cdtype)
        return get

    @pl.when(j * ppb < live_ref[b])
    def _block():
        _attend_block(j, tile(k_refs), v_refs if v_refs is None else tile(v_refs),
                      q_ref, qpos_ref, kpos_ref, ks_ref, vs_ref, acc_ref,
                      m_ref, l_ref, **math)

    @pl.when(j == pl.num_programs(1) - 1)
    def _out():
        _flush(o_ref, acc_ref, l_ref)


def _copy_by_hand(pools) -> bool:
    """Whether the kernel copies pages by hand: Mosaic slices a page out
    of a pool in HBM only when its row is a whole number of 128-lane
    tiles (GQA at ``Dk % 128 == 0``).  Other rows (MLA's 288- or
    576-wide latent) come through page BlockSpecs, which span the row."""
    return all(p.shape[-1] % 128 == 0 for p in pools)


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
    """Fused paged attention: walks each row's live pages of
    ``page_tables`` in-kernel and returns ``[B, S, H, Dv]`` without
    materializing the ``[B, P*PS, D]`` window.

    Drop-in for ``paged_read`` + ``mha`` (GQA, KV heads never repeated)
    and for the latent gather + ``_mla_absorbed`` score/context part
    (``kv_heads=1`` + ``latent_dv``).  ``interpret=True`` runs the same
    kernel body through the Pallas interpreter — the CPU/CI path.

    Blocks (Mosaic's rule: the last two block dims are multiples of
    (8, 128) or span the array): the pools are copied a whole page row
    at a time and a head's keys are the lane slice at ``kv_head * Dk``
    of the block, so it compiles when ``Dk`` is a multiple of 128 or
    ``kv_heads == 1`` (:func:`supports`).  Queries, their positions
    (an ``[SG, 1]`` column) and the per-slot planes ride the grid one
    request per step.  The table is padded with the null page to whole
    blocks, so ``P`` need not divide into them.
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
    ppb = pages_per_block(ps, p_cnt)
    n_blk = -(-p_cnt // ppb)
    tables = jnp.pad(  # null page (id 0, every slot -1) to whole blocks
        page_tables.astype(jnp.int32), ((0, 0), (0, n_blk * ppb - p_cnt))
    )
    n_live = live_pages(q_pos.astype(jnp.int32), ps, p_cnt)

    # head-major query layout: row r = s*G + g', matching mha's
    # [B, S, KV, G, D] grouping (head h = kv*G + g')
    q_r = q.reshape(b, s, kv_heads, g, dk).transpose(0, 2, 1, 3, 4)
    q_r = q_r.reshape(b, kv_heads, sg, dk)
    q_col = jnp.repeat(q_pos.astype(jnp.int32), g, axis=1)[..., None]

    def per_block(plane):  # [N_pages, PS] -> [B, n_blk, ppb*PS]
        return plane[tables].reshape(b, n_blk, ppb * ps)

    per_req = pl.BlockSpec((None, n_blk, ppb * ps), lambda bb, *_: (bb, 0, 0))
    in_specs = [
        pl.BlockSpec((None, kv_heads, sg, dk), lambda bb, *_: (bb, 0, 0, 0)),
        pl.BlockSpec((None, sg, 1), lambda bb, *_: (bb, 0, 0)),
        per_req,
    ]
    operands = [q_r, q_col, per_block(pos_tbl)]
    for plane in (k_scale, None if latent else v_scale):
        if plane is not None:
            in_specs.append(per_req)
            operands.append(per_block(plane))
    pools = [k_pages] if latent else [k_pages, v_pages]
    stats = [
        pltpu.VMEM((kv_heads, sg, dv), jnp.float32),  # acc
        pltpu.VMEM((kv_heads, sg, 1), jnp.float32),  # running row max m
        pltpu.VMEM((kv_heads, sg, 1), jnp.float32),  # running normalizer l
    ]
    # the pools themselves are operands, untouched: the kernel fetches
    # their pages
    if _copy_by_hand(pools):
        kernel, grid, semantics = _dma_kernel, (b,), ("parallel",)
        in_specs += [pl.BlockSpec(memory_space=pltpu.HBM)] * len(pools)
        operands += pools
        scratch = [pltpu.VMEM((2, ppb, ps, pool.shape[-1]), pool.dtype)
                   for pool in pools]
        scratch += [pltpu.SemaphoreType.DMA((2,))] + stats  # a DMA sem a slot
    else:
        kernel, grid = _spec_kernel, (b, n_blk)
        semantics = ("parallel", "arbitrary")

        def page_spec(i, width):
            def index(bb, jj, tbl, live):
                last = jnp.maximum((live[bb] + ppb - 1) // ppb - 1, 0)
                page = tbl[(bb * n_blk + jnp.minimum(jj, last)) * ppb + i]
                return page, 0, 0
            return pl.BlockSpec((None, ps, width), index)

        for pool in pools:
            in_specs += [page_spec(i, pool.shape[-1]) for i in range(ppb)]
            operands += [pool] * ppb
        scratch = stats

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (None, kv_heads, sg, dv), lambda bb, *_: (bb, 0, 0, 0)
        ),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(
            kernel,
            ppb=ppb,
            has_ks=k_scale is not None,
            has_vs=v_scale is not None and not latent,
            cdtype=q.dtype,
            kv_heads=kv_heads,
            dk=dk,
            dv=dv,
            window=window,
            scale=scale,
            latent=latent,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv_heads, sg, dv), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
        name="paged_attn",  # the kernel's name in compiled programs and traces
    )(tables.reshape(-1), n_live, *operands)
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
