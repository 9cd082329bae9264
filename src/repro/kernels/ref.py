"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are validated against (interpret
mode on CPU, sweeping shapes/dtypes — see tests/test_kernels.py), and they
double as the shardable ``impl="jnp"`` hot path used for serving on this
host, so their own speed matters.

The decode computes the same ranks as the kernels (``rank(b) =
popcount(mask & (2^b - 1))``, here with XLA's ``population_count``),
applied **directly in the kernel wire layout** — no round-trips through
the ``[..., K]``-major layout of ``dbb.expand_bitmask`` — and resolves
the per-position value lookup with one select per value slot: elementwise
work XLA fuses, where a ``take_along_axis`` gather lowers on a TPU to an
indexed gather whose cost model reads hundreds of GB per decode step.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import dbb, quant
from repro.kernels import epilogue


def _ranks(mask: jax.Array, bz: int):
    """Per block position ``b`` of each uint8 mask: ``(bit_b, rank(b))``
    with ``rank(b) = popcount(mask & (2^b - 1))``; the position axis is
    appended last."""
    m = mask.astype(jnp.int32)[..., None]
    pos = jnp.arange(bz, dtype=jnp.int32)
    return (m >> pos) & 1, jax.lax.population_count(m & ((1 << pos) - 1))


def _select_slots(vals, bits, rank):
    """``dense = bit ? vals[rank] : 0`` as one select per value slot
    (``vals`` is the list of slot planes, each broadcasting against
    ``bits``/``rank``) — elementwise, exact in any dtype."""
    dense = jnp.zeros(bits.shape, vals[0].dtype)
    for j, v in enumerate(vals):
        dense = jnp.where((bits == 1) & (rank == j), v, dense)
    return dense


def decode_w(w_vals: jax.Array, w_mask: jax.Array, cfg: dbb.DBBConfig) -> jax.Array:
    """Wire-format weights -> dense ``[K, N]``, decoded in-layout.

    ``w_vals [K//BZ, NNZ, N]``, ``w_mask [K//BZ, N] uint8``.  Ranks by
    popcount, then one select per NNZ slot — no transposes, no gather.
    """
    kb, nnz, n = w_vals.shape
    bits, rank = _ranks(w_mask, cfg.bz)  # [KB, N, BZ]
    bits, rank = jnp.moveaxis(bits, -1, 1), jnp.moveaxis(rank, -1, 1)
    dense = _select_slots(
        [w_vals[:, j : j + 1, :] for j in range(nnz)], bits, rank
    )  # [KB, BZ, N]
    return dense.reshape(kb * cfg.bz, n)


def decode_a(x_vals: jax.Array, x_mask: jax.Array, cfg: dbb.DBBConfig) -> jax.Array:
    """Wire-format activations ``[..., K//BZ, NNZ]`` -> dense ``[..., K]``.

    Same rank decode with the block axis minor (activation layout);
    equivalent to ``dbb.expand_bitmask``.
    """
    nnz = x_vals.shape[-1]
    bits, rank = _ranks(x_mask, cfg.bz)  # [..., KB, BZ]
    dense = _select_slots(
        [x_vals[..., j : j + 1] for j in range(nnz)], bits, rank
    )
    return dense.reshape(*dense.shape[:-2], dense.shape[-2] * cfg.bz)


def dbb_matmul_ref(
    x: jax.Array,
    w_vals: jax.Array,
    w_mask: jax.Array,
    cfg: dbb.DBBConfig,
    out_dtype=None,
    bias: Optional[jax.Array] = None,
    act: Optional[str] = None,
) -> jax.Array:
    """W-DBB matmul oracle with optional fused epilogue.

    ``x [M, K]`` dense; weights in kernel wire format (see
    :func:`repro.core.dbb.pack_bitmask`) blocked along the reduction dim:
    ``w_vals [K//BZ, NNZ, N]``, ``w_mask [K//BZ, N] uint8``.
    Returns ``act(x @ expand(w) + bias) [M, N]``.
    """
    w_dense = decode_w(w_vals, w_mask, cfg)  # [K, N]
    out_dtype = out_dtype or x.dtype
    y = jnp.dot(x, w_dense.astype(x.dtype), preferred_element_type=jnp.float32)
    y = epilogue.apply_epilogue(y, bias, act)
    return y.astype(out_dtype)


def dbb_matmul_aw_ref(
    x_vals: jax.Array,
    x_mask: jax.Array,
    w_vals: jax.Array,
    w_mask: jax.Array,
    cfg_a: dbb.DBBConfig,
    cfg_w: dbb.DBBConfig,
    out_dtype=None,
    bias: Optional[jax.Array] = None,
    act: Optional[str] = None,
) -> jax.Array:
    """Joint A/W-DBB matmul oracle (S2TA-AW analogue).

    Activations in wire format ``x_vals [M, K//BZ, NNZ_a]``,
    ``x_mask [M, K//BZ] uint8``; weights as in :func:`dbb_matmul_ref`.
    """
    x_dense = decode_a(x_vals, x_mask, cfg_a)  # [M, K]
    return dbb_matmul_ref(
        x_dense, w_vals, w_mask, cfg_w, out_dtype=out_dtype, bias=bias, act=act
    )


# ------------------------------------------------------------- INT8 oracles


def combined_scale(x_scale: jax.Array, w_scale: jax.Array, n: int) -> jax.Array:
    """The dequant scale shared by kernels and oracles — one definition
    so both sides multiply identically and int8 parity stays bit-exact.

    Scalar ``x_scale`` (per-tensor dynamic activations) gives the
    ``[1, N]`` row; per-row ``x_scale [M]`` (per-token dynamic
    activations — the batch-invariant mode, see ``core.sparsity``) gives
    the full ``[M, N]`` outer product — the "column-vector operand in
    the dequant epilogue" cost of per-row scales."""
    ws = w_scale.astype(jnp.float32).reshape(1, n)
    xs = x_scale.astype(jnp.float32)
    if xs.ndim == 0:
        return (xs * ws).reshape(1, n)
    return xs.reshape(-1, 1) * ws


def dbb_matmul_int8_ref(
    x_q: jax.Array,
    x_scale: jax.Array,
    w_vals: jax.Array,
    w_mask: jax.Array,
    w_scale: jax.Array,
    cfg: dbb.DBBConfig,
    out_dtype=jnp.float32,
    bias: Optional[jax.Array] = None,
    act: Optional[str] = None,
) -> jax.Array:
    """Quantized W-DBB matmul oracle — the bit-defined int8 reference.

    ``x_q [M, K] int8`` with per-tensor ``x_scale``; weights in int8 wire
    format (``w_vals [K//BZ, NNZ, N] int8``, ``w_mask``, per-channel
    ``w_scale [N]``).  Accumulates int8×int8 in **int32** (exact, so
    tiled kernel accumulation matches this dense dot bit-for-bit), then
    dequantizes through the shared fused epilogue.
    """
    w_dense = decode_w(w_vals, w_mask, cfg)  # [K, N] int8 (decode is exact)
    acc = jnp.dot(x_q, w_dense, preferred_element_type=jnp.int32)
    scale = combined_scale(x_scale, w_scale, w_dense.shape[-1])
    y = epilogue.apply_dequant_epilogue(acc, scale, bias, act)
    return y.astype(out_dtype)


def dbb_matmul_aw_int8_ref(
    x_vals: jax.Array,
    x_mask: jax.Array,
    x_scale: jax.Array,
    w_vals: jax.Array,
    w_mask: jax.Array,
    w_scale: jax.Array,
    cfg_a: dbb.DBBConfig,
    cfg_w: dbb.DBBConfig,
    out_dtype=jnp.float32,
    bias: Optional[jax.Array] = None,
    act: Optional[str] = None,
) -> jax.Array:
    """Quantized joint A/W-DBB oracle: both operands stream packed int8."""
    x_dense = decode_a(x_vals, x_mask, cfg_a)  # [M, K] int8
    return dbb_matmul_int8_ref(
        x_dense, x_scale, w_vals, w_mask, w_scale, cfg_w,
        out_dtype=out_dtype, bias=bias, act=act,
    )


def pack_weight_int8(w: jax.Array, cfg: dbb.DBBConfig):
    """Dense ``w [K, N]`` -> int8 wire format (prunes if needed).

    Returns ``(w_vals [K//BZ, NNZ, N] int8, w_mask [K//BZ, N] uint8,
    w_scale [N] f32)`` — symmetric per-output-channel scales (each
    column ``n`` quantizes on its own amax, the standard weight scheme).
    """
    # pack w.T so the channel axis leads: [N, KB, NNZ]; scale over (1, 2)
    q, mask, scale = dbb.pack_bitmask_int8(w.T, cfg, scale_axis=(1, 2))
    return jnp.moveaxis(q, 0, -1), jnp.moveaxis(mask, 0, -1), scale


def quantize_act_int8(x: jax.Array, per_row: bool = False):
    """Dense activations -> ``(int8 [..., K], f32 scale)`` with a
    *dynamic* scale (recomputed per call — activations have no stable
    range, unlike weights).  ``per_row=False``: one per-tensor scalar;
    ``per_row=True``: one scale per leading row (per token), shape
    ``[...]`` — each row quantizes independently of what it is batched
    with (see ``core.sparsity.SparsityConfig.act_scale``)."""
    if per_row:
        return quant.quantize(x, axis=-1)
    return quant.quantize(x)


def dap_prune_ref(x: jax.Array, nnz: int, bz: int = dbb.DEFAULT_BZ):
    """DAP oracle: (pruned dense tensor, per-block uint8 bitmask)."""
    cfg = dbb.DBBConfig(nnz, bz)
    pruned = dbb.prune(x, cfg)
    kept = pruned != 0
    kept_b = kept.reshape(*kept.shape[:-1], kept.shape[-1] // bz, bz)
    weights = (2 ** jnp.arange(bz, dtype=jnp.uint32)).astype(jnp.uint32)
    bitmask = jnp.sum(kept_b.astype(jnp.uint32) * weights, axis=-1).astype(jnp.uint8)
    return pruned, bitmask


# ------------------------------------------------------ paged attention


def paged_attn_ref(
    q: jax.Array,  # [B, S, H, Dk]
    k_pages: jax.Array,  # [N, PS, KV*Dk] (latent: [N, PS, Dk], KV == 1)
    v_pages: Optional[jax.Array],  # [N, PS, KV*Dv]; None when latent_dv set
    pos_tbl: jax.Array,  # [N, PS] int32
    page_tables: jax.Array,  # [B, P] int32
    q_pos: jax.Array,  # [B, S] int32
    *,
    kv_heads: int,
    window: Optional[int] = None,
    softmax_scale: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    latent_dv: Optional[int] = None,
    out_dtype=None,
):
    """jnp oracle for :func:`repro.kernels.paged_attn.paged_attn_fused`,
    mirroring the kernel's online-softmax **page tiling**: a ``fori_loop``
    streams one page per step (gathered by id across the batch), applies
    the same position-derived masking, dequantizes int8 pages in the load
    (per-token scale column), and carries the flash-style ``(acc, m, l)``
    statistics — the ``[B, P*PS, D]`` window is never materialized.  This
    is also the shardable/timeable jnp hot path the CPU benchmarks use
    (``kernel_bench.bench_paged_attn``), exactly like the other oracles
    in this module.
    """
    import math

    b, s, h, dk = q.shape
    g = h // kv_heads
    sg = s * g
    n_pages, ps = pos_tbl.shape
    p_cnt = page_tables.shape[1]
    latent = latent_dv is not None
    dv = latent_dv if latent else v_pages.shape[-1] // kv_heads
    out_dtype = out_dtype or q.dtype
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(dk)
    neg_inf = -1e30  # models/attention.NEG_INF (finite: NaN-free rescale)

    cdtype = q.dtype
    q_r = q.reshape(b, s, kv_heads, g, dk).transpose(0, 2, 1, 3, 4)
    q_r = q_r.reshape(b, kv_heads, sg, dk)
    k_r = k_pages.reshape(n_pages, ps, kv_heads, dk)
    v_r = None if latent else v_pages.reshape(n_pages, ps, kv_heads, dv)

    def body(p, carry):
        acc, m, l = carry
        pid = page_tables[:, p]  # [B]
        k_p = k_r[pid]  # [B, PS, KV, Dk]
        if k_scale is not None:
            k_p = (
                k_p.astype(jnp.float32) * k_scale[pid][:, :, None, None]
            ).astype(cdtype)
        logits = (
            jnp.einsum(
                "bkxd,bpkd->bkxp", q_r, k_p,
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # [B, KV, SG, PS]
        kpos = pos_tbl[pid]  # [B, PS]
        valid = (kpos[:, None, :] >= 0) & (kpos[:, None, :] <= q_pos[:, :, None])
        if window is not None:
            valid &= kpos[:, None, :] > (q_pos[:, :, None] - window)
        bias = jnp.where(valid, 0.0, neg_inf).astype(jnp.float32)  # [B, S, PS]
        logits = logits.reshape(b, kv_heads, s, g, ps) + bias[:, None, :, None, :]
        logits = logits.reshape(b, kv_heads, sg, ps)
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        alpha = jnp.exp(m - m_new)
        probs = jnp.exp(logits - m_new)
        if latent:
            v_p = k_p[..., :dv]  # MLA: v is the latent prefix of k
        else:
            v_p = v_r[pid]
            if v_scale is not None:
                v_p = (
                    v_p.astype(jnp.float32) * v_scale[pid][:, :, None, None]
                ).astype(cdtype)
        pv = jnp.einsum(
            "bkxp,bpkv->bkxv", probs.astype(v_p.dtype), v_p,
            preferred_element_type=jnp.float32,
        )
        return (
            acc * alpha + pv,
            m_new,
            alpha * l + jnp.sum(probs, axis=-1, keepdims=True),
        )

    acc = jnp.zeros((b, kv_heads, sg, dv), jnp.float32)
    m = jnp.full((b, kv_heads, sg, 1), neg_inf, jnp.float32)
    l = jnp.zeros((b, kv_heads, sg, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, p_cnt, body, (acc, m, l))
    out = acc / jnp.maximum(l, 1e-30)
    out = out.reshape(b, kv_heads, s, g, dv).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, s, h, dv).astype(out_dtype)


def pack_weight_for_kernel(w: jax.Array, cfg: dbb.DBBConfig):
    """Dense ``w [K, N]`` -> kernel wire format (prunes if needed).

    Returns ``(w_vals [K//BZ, NNZ, N], w_mask [K//BZ, N] uint8)``.
    """
    vals, mask = dbb.pack_bitmask(w.T, cfg)  # [N, KB, NNZ], [N, KB]
    return jnp.moveaxis(vals, 0, -1), jnp.moveaxis(mask, 0, -1)


def pack_act_for_kernel(x: jax.Array, cfg: dbb.DBBConfig):
    """Dense ``x [..., K]`` -> ``(x_vals [..., K//BZ, NNZ], x_mask [..., K//BZ])``."""
    return dbb.pack_bitmask(x, cfg)
