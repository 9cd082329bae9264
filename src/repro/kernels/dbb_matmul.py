"""Pallas TPU kernel: W-DBB structured-sparse matmul (and joint A/W-DBB).

TPU adaptation of the S2TA TPE (paper §6): the per-MAC operand mux of the
DP4M8 datapath (Fig. 6c) becomes an **in-VMEM rank-decode expansion** of the
compressed weight block, followed by a dense MXU matmul on the expanded
tile.  The win on TPU is *HBM bandwidth*: weights stream from HBM in packed
DBB form (``NNZ/BZ`` of the dense bytes + 1-byte bitmask per block-column)
and are expanded once per (K-tile × N-tile), amortized across the whole
M-tile — the software analogue of intra-TPE operand reuse.

Rank decode is fully vectorized: ``dense[b] = bit_b ? values[rank(b)] : 0``
with ``rank(b) = popcount(mask & (2^b - 1))``, computed for every block
position at once by a SWAR popcount (shifts, ands and adds — Mosaic has no
``cumsum``), then NNZ data-independent selects over the value slots — the
Mosaic-friendly form of the DP4M8 mux, not a dynamic gather.  The decode
cost matches the paper's "very low overhead" claim (§6.1).

Wire format (see ``repro.core.dbb.pack_bitmask``):
    w_vals [K//BZ, NNZ, N]  — j-th set bit's value, ascending positions
    w_mask [K//BZ, N] uint8 — bit b set ⇔ block position b is a non-zero

Grid ``(M//TM, N//TN, K//TK)`` with K innermost (arbitrary semantics);
float32 accumulator scratch in VMEM.  Tile sizes come from
``repro.kernels.autotune`` (benchmark cache → MXU-aligned heuristic) unless
passed explicitly.  The optional epilogue (bias add + activation) drains
the accumulator through ``repro.kernels.epilogue`` at the final K step, so
``y = act(x @ expand(w) + b)`` never materializes the pre-activation tensor.

The kernels are validated in ``interpret=True`` mode against the pure-jnp
oracles in ``ref.py``, and compiled ahead of time for a described v5e chip
in ``tests/test_tpu_compile.py``.  Mosaic layout notes:

* every block's last two dims are multiples of (8, 128) or whole axes, so
  the tiles come from ``autotune.heuristic_tiles`` (TK, TN and the A/W
  kernels' TM are lane dims: multiples of 128 or the whole axis);
* the expansion works in 32-bit and assembles the dense tile by collapsing
  ``[KB, BZ, TN] -> [KB*BZ, TN]`` — whole (8, 128) tiles relabelled, with
  the lane dim unchanged;
* the A/W kernels decode the activation in the weight wire's layout
  (transposed by XLA outside the kernel: ``[KB, NNZ, M]``), giving the
  ``[TK, TM]`` tile with K on sublanes, and contract it transposed on the
  MXU — decoding along lanes would need a lane-merging reshape, which
  Mosaic refuses.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import dbb
from repro.kernels import autotune, epilogue, ref

def _popcount8(v):
    """Population count of each int32 lane holding an 8-bit value (SWAR:
    pairs, nibbles, then bytes) — shifts, ands and adds only, which
    Mosaic lowers (it has no ``cumsum``)."""
    v = v - ((v >> 1) & 0x55)
    v = (v & 0x33) + ((v >> 2) & 0x33)
    return (v + (v >> 4)) & 0x0F


def _expand_tile(vals, mask, cfg: dbb.DBBConfig):
    """Expand packed values [R, NNZ, C] + mask [R, C] -> dense [R*BZ, C].

    Rank decode: position ``b`` holds ``values[rank(b)]`` iff bit ``b`` is
    set, where ``rank(b) = popcount(mask & (2^b - 1))`` — computed for all
    BZ positions at once with a SWAR popcount, then resolved by NNZ
    data-independent selects (the DP4M8 mux; exactly one slot matches per
    set bit, so the result is exact in any dtype).  The work runs in a
    32-bit dtype so that collapsing ``[R, BZ, C] -> [R*BZ, C]`` is a pure
    relabelling of (8, 128) tiles (BZ == 8); the caller casts the dense
    tile back to the wire dtype, which is exact.
    """
    assert cfg.bz <= 8, cfg  # uint8 bitmask
    r, nnz, c = vals.shape
    wide = jnp.int32 if jnp.issubdtype(vals.dtype, jnp.integer) else jnp.float32
    m = mask.astype(jnp.int32)[:, None, :]  # [R, 1, C]
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, cfg.bz, 1), 1)
    bits = (m >> pos) & 1  # [R, BZ, C]
    rank = _popcount8(m & ((1 << pos) - 1))  # popcount of the lower bits
    v = vals.astype(wide)
    dense = jnp.zeros((r, cfg.bz, c), wide)
    for j in range(nnz):  # static: NNZ <= BZ
        dense = jnp.where(rank == j, v[:, j : j + 1, :], dense)
    dense = jnp.where(bits == 1, dense, jnp.zeros_like(dense))
    return dense.reshape(r * cfg.bz, c)


def _act_wire_t(x_vals, x_mask):
    """Activation wire ``[M, KB, NNZ]`` + ``[M, KB]`` -> the weight wire's
    layout ``[KB, NNZ, M]`` + ``[KB, M]``, so one rank decode serves both
    operands and yields the activation tile transposed, ``[TK, TM]``, with
    K on sublanes (decoding along lanes would need a lane-merging
    reshape, which Mosaic refuses).  A transpose of the small activation
    operand, done by XLA outside the kernel."""
    return jnp.transpose(x_vals, (1, 2, 0)), x_mask.T


def _dot_tn(a_t, b, acc_dtype):
    """``a_t [K, M]`` transposed times ``b [K, N]`` -> ``[M, N]``."""
    return jax.lax.dot_general(
        a_t, b, (((0,), (0,)), ((), ())), preferred_element_type=acc_dtype
    )


def _flush_epilogue(acc_ref, o_ref, b_ref, act):
    """Drain the f32 accumulator through the (optional) fused epilogue."""
    y = acc_ref[...]
    y = epilogue.apply_epilogue(y, b_ref[...] if b_ref is not None else None, act)
    o_ref[...] = y.astype(o_ref.dtype)


def _dbb_matmul_kernel(x_ref, wv_ref, wm_ref, *rest, cfg, nk, act, has_bias):
    b_ref = rest[0] if has_bias else None
    o_ref, acc_ref = rest[-2], rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w_dense = _expand_tile(wv_ref[...], wm_ref[...], cfg)  # [TK, TN]
    acc_ref[...] += jnp.dot(
        x_ref[...], w_dense.astype(x_ref.dtype), preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        _flush_epilogue(acc_ref, o_ref, b_ref, act)


def _dbb_matmul_aw_kernel(
    xv_ref, xm_ref, wv_ref, wm_ref, *rest, cfg_a, cfg_w, nk, act, has_bias
):
    b_ref = rest[0] if has_bias else None
    o_ref, acc_ref = rest[-2], rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dt = xv_ref.dtype
    x_t = _expand_tile(xv_ref[...], xm_ref[...], cfg_a).astype(dt)  # [TK, TM]
    w_dense = _expand_tile(wv_ref[...], wm_ref[...], cfg_w).astype(dt)  # [TK, TN]
    acc_ref[...] += _dot_tn(x_t, w_dense, jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        _flush_epilogue(acc_ref, o_ref, b_ref, act)


def _resolve_tiles(m, k, n, cfg, tm, tk, tn, kind):
    """Explicit tiles win; otherwise consult the autotune table, then make
    every dim a legal whole-block divisor."""
    atm, atk, atn = autotune.get_tiles(m, k, n, cfg.nnz, cfg.bz, kind=kind)
    tm = autotune.largest_divisor(tm or atm, m, 1)
    tn = autotune.largest_divisor(tn or atn, n, 1)
    # largest_divisor with step=bz yields a whole-block divisor of k
    # (k % bz == 0 is asserted by the callers)
    tk = autotune.largest_divisor(tk or atk, k, cfg.bz)
    return tm, tk, tn


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "tm", "tk", "tn", "out_dtype", "act", "interpret"),
)
def dbb_matmul_pallas(
    x: jax.Array,
    w_vals: jax.Array,
    w_mask: jax.Array,
    *,
    cfg: dbb.DBBConfig,
    bias: Optional[jax.Array] = None,
    act: Optional[str] = None,
    tm: Optional[int] = None,
    tk: Optional[int] = None,
    tn: Optional[int] = None,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """``act(x [M,K] @ expand(w) [K,N] + bias) -> [M,N]`` with W-DBB weights."""
    m, k = x.shape
    kb, nnz, n = w_vals.shape
    assert kb * cfg.bz == k and nnz == cfg.nnz, (x.shape, w_vals.shape, cfg)
    out_dtype = out_dtype or x.dtype
    tm, tk, tn = _resolve_tiles(m, k, n, cfg, tm, tk, tn, "w")
    tkb = tk // cfg.bz
    nk = k // tk
    grid = (m // tm, n // tn, nk)
    in_specs = [
        pl.BlockSpec((tm, tk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((tkb, nnz, tn), lambda i, j, kk: (kk, 0, j)),
        pl.BlockSpec((tkb, tn), lambda i, j, kk: (kk, j)),
    ]
    operands = [x, w_vals, w_mask]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, tn), lambda i, j, kk: (0, j)))
        operands.append(bias.reshape(1, n))
    return pl.pallas_call(
        functools.partial(
            _dbb_matmul_kernel, cfg=cfg, nk=nk, act=act, has_bias=bias is not None
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)


# ------------------------------------------------------------- INT8 kernels
#
# The paper's actual operating point: int8 operands into the MACs, int32
# accumulators (§6, DP4M8).  On TPU that is the MXU's native int8 mode —
# the packed wire carries int8 values (1/4 the bf16-pipeline's value
# bytes at the same NNZ/BZ), the rank-decode expansion stays in int8,
# the dot accumulates in an int32 VMEM scratch, and the final K-step
# flush dequantizes (x_scale × w_scale per output channel) fused with
# bias + activation via ``epilogue.apply_dequant_epilogue`` — one pass
# from accumulator to output dtype, exactly like the TPE output pipeline.
#
# Integer accumulation is associative, so the tiled kernel matches the
# quantized jnp oracle (``ref.dbb_matmul_int8_ref``) *bit-for-bit*.


def _flush_dequant_epilogue(acc_ref, o_ref, s_ref, b_ref, act):
    """Drain the int32 accumulator through the fused dequant epilogue."""
    y = epilogue.apply_dequant_epilogue(
        acc_ref[...], s_ref[...], b_ref[...] if b_ref is not None else None, act
    )
    o_ref[...] = y.astype(o_ref.dtype)


def _dbb_matmul_int8_kernel(x_ref, wv_ref, wm_ref, s_ref, *rest, cfg, nk, act, has_bias):
    b_ref = rest[0] if has_bias else None
    o_ref, acc_ref = rest[-2], rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # rank-decode in int32; the cast back to int8 is exact
    w_dense = _expand_tile(wv_ref[...], wm_ref[...], cfg).astype(jnp.int8)
    acc_ref[...] += jnp.dot(
        x_ref[...], w_dense, preferred_element_type=jnp.int32
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        _flush_dequant_epilogue(acc_ref, o_ref, s_ref, b_ref, act)


def _dbb_matmul_aw_int8_kernel(
    xv_ref, xm_ref, wv_ref, wm_ref, s_ref, *rest, cfg_a, cfg_w, nk, act, has_bias
):
    b_ref = rest[0] if has_bias else None
    o_ref, acc_ref = rest[-2], rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x_t = _expand_tile(xv_ref[...], xm_ref[...], cfg_a).astype(jnp.int8)
    w_dense = _expand_tile(wv_ref[...], wm_ref[...], cfg_w).astype(jnp.int8)
    acc_ref[...] += _dot_tn(x_t, w_dense, jnp.int32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        _flush_dequant_epilogue(acc_ref, o_ref, s_ref, b_ref, act)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "tm", "tk", "tn", "out_dtype", "act", "interpret"),
)
def dbb_matmul_int8_pallas(
    x_q: jax.Array,
    x_scale: jax.Array,
    w_vals: jax.Array,
    w_mask: jax.Array,
    w_scale: jax.Array,
    *,
    cfg: dbb.DBBConfig,
    bias: Optional[jax.Array] = None,
    act: Optional[str] = None,
    tm: Optional[int] = None,
    tk: Optional[int] = None,
    tn: Optional[int] = None,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """INT8 W-DBB matmul: ``act(scale · (x_q @ expand(w_q)) + bias)``.

    ``x_q [M,K] int8`` (per-tensor ``x_scale``), weights in int8 wire
    format with per-channel ``w_scale [N]``.  int32 accumulation; the
    dequant scale row is folded outside the kernel and streamed like the
    bias, so the kernel needs no scalar operands.
    """
    m, k = x_q.shape
    kb, nnz, n = w_vals.shape
    assert x_q.dtype == jnp.int8 and w_vals.dtype == jnp.int8
    assert kb * cfg.bz == k and nnz == cfg.nnz, (x_q.shape, w_vals.shape, cfg)
    tm, tk, tn = _resolve_tiles(m, k, n, cfg, tm, tk, tn, "w_int8")
    tkb = tk // cfg.bz
    nk = k // tk
    grid = (m // tm, n // tn, nk)
    # per-tensor x_scale folds to a [1, N] row streamed like the bias;
    # per-row x_scale [M] folds to the full [M, N] dequant tile (the
    # column-vector-operand cost of batch-invariant per-token scales)
    scale_row = ref.combined_scale(x_scale, w_scale, n)
    scale_spec = (
        pl.BlockSpec((tm, tn), lambda i, j, kk: (i, j))
        if scale_row.shape[0] == m and m > 1
        else pl.BlockSpec((1, tn), lambda i, j, kk: (0, j))
    )
    in_specs = [
        pl.BlockSpec((tm, tk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((tkb, nnz, tn), lambda i, j, kk: (kk, 0, j)),
        pl.BlockSpec((tkb, tn), lambda i, j, kk: (kk, j)),
        scale_spec,
    ]
    operands = [x_q, w_vals, w_mask, scale_row]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, tn), lambda i, j, kk: (0, j)))
        operands.append(bias.reshape(1, n))
    return pl.pallas_call(
        functools.partial(
            _dbb_matmul_int8_kernel, cfg=cfg, nk=nk, act=act,
            has_bias=bias is not None,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg_a", "cfg_w", "tm", "tk", "tn", "out_dtype", "act", "interpret"
    ),
)
def dbb_matmul_aw_int8_pallas(
    x_vals: jax.Array,
    x_mask: jax.Array,
    x_scale: jax.Array,
    w_vals: jax.Array,
    w_mask: jax.Array,
    w_scale: jax.Array,
    *,
    cfg_a: dbb.DBBConfig,
    cfg_w: dbb.DBBConfig,
    bias: Optional[jax.Array] = None,
    act: Optional[str] = None,
    tm: Optional[int] = None,
    tk: Optional[int] = None,
    tn: Optional[int] = None,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """INT8 joint A/W-DBB matmul: both operands stream packed int8
    (S2TA-AW at the paper's precision), int32 accumulation, fused
    dequant+bias+act flush."""
    m, kb_a, nnz_a = x_vals.shape
    kb, nnz_w, n = w_vals.shape
    assert x_vals.dtype == jnp.int8 and w_vals.dtype == jnp.int8
    assert kb_a == kb and nnz_a == cfg_a.nnz and nnz_w == cfg_w.nnz
    k = kb * cfg_w.bz
    tm, tk, tn = _resolve_tiles(m, k, n, cfg_w, tm, tk, tn, "aw_int8")
    tkb = tk // cfg_w.bz
    nk = k // tk
    grid = (m // tm, n // tn, nk)
    scale_row = ref.combined_scale(x_scale, w_scale, n)
    scale_spec = (
        pl.BlockSpec((tm, tn), lambda i, j, kk: (i, j))
        if scale_row.shape[0] == m and m > 1
        else pl.BlockSpec((1, tn), lambda i, j, kk: (0, j))
    )
    in_specs = [
        pl.BlockSpec((tkb, nnz_a, tm), lambda i, j, kk: (kk, 0, i)),
        pl.BlockSpec((tkb, tm), lambda i, j, kk: (kk, i)),
        pl.BlockSpec((tkb, nnz_w, tn), lambda i, j, kk: (kk, 0, j)),
        pl.BlockSpec((tkb, tn), lambda i, j, kk: (kk, j)),
        scale_spec,
    ]
    operands = [*_act_wire_t(x_vals, x_mask), w_vals, w_mask, scale_row]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, tn), lambda i, j, kk: (0, j)))
        operands.append(bias.reshape(1, n))
    return pl.pallas_call(
        functools.partial(
            _dbb_matmul_aw_int8_kernel,
            cfg_a=cfg_a,
            cfg_w=cfg_w,
            nk=nk,
            act=act,
            has_bias=bias is not None,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg_a", "cfg_w", "tm", "tk", "tn", "out_dtype", "act", "interpret"
    ),
)
def dbb_matmul_aw_pallas(
    x_vals: jax.Array,
    x_mask: jax.Array,
    w_vals: jax.Array,
    w_mask: jax.Array,
    *,
    cfg_a: dbb.DBBConfig,
    cfg_w: dbb.DBBConfig,
    bias: Optional[jax.Array] = None,
    act: Optional[str] = None,
    tm: Optional[int] = None,
    tk: Optional[int] = None,
    tn: Optional[int] = None,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Joint A/W-DBB matmul: both operands stream packed (S2TA-AW analogue),
    with the same fused bias+activation epilogue as the W-DBB kernel."""
    m, kb_a, nnz_a = x_vals.shape
    kb, nnz_w, n = w_vals.shape
    assert kb_a == kb and nnz_a == cfg_a.nnz and nnz_w == cfg_w.nnz
    k = kb * cfg_w.bz
    out_dtype = out_dtype or x_vals.dtype
    tm, tk, tn = _resolve_tiles(m, k, n, cfg_w, tm, tk, tn, "aw")
    tkb = tk // cfg_w.bz
    nk = k // tk
    grid = (m // tm, n // tn, nk)
    in_specs = [
        pl.BlockSpec((tkb, nnz_a, tm), lambda i, j, kk: (kk, 0, i)),
        pl.BlockSpec((tkb, tm), lambda i, j, kk: (kk, i)),
        pl.BlockSpec((tkb, nnz_w, tn), lambda i, j, kk: (kk, 0, j)),
        pl.BlockSpec((tkb, tn), lambda i, j, kk: (kk, j)),
    ]
    operands = [*_act_wire_t(x_vals, x_mask), w_vals, w_mask]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, tn), lambda i, j, kk: (0, j)))
        operands.append(bias.reshape(1, n))
    return pl.pallas_call(
        functools.partial(
            _dbb_matmul_aw_kernel,
            cfg_a=cfg_a,
            cfg_w=cfg_w,
            nk=nk,
            act=act,
            has_bias=bias is not None,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)
