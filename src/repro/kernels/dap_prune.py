"""Pallas TPU kernel: Dynamic Activation Pruning (DAP) — paper §5.1/§6.2.

Implements the cascaded magnitude-maxpool array of Fig. 8 as ``NNZ``
iterations of masked block-argmax: each stage selects the largest
remaining |x| per 8-wide channel block and retires it, exactly like the
hardware discounts previous winners.  Outputs the pruned (dense-layout)
tensor and the per-block uint8 positional bitmask ``M``.

The kernel works on the transposed activation ``x.T [K, M]`` (XLA
transposes the small activation operand outside the kernel), so the
channel axis sits on sublanes: a ``[TK, TM]`` f32 tile views as
``[TK/BZ, BZ, TM]`` blocks by relabelling whole (8, 128) tiles (BZ == 8),
and every stage reduces over the second-minor axis.  Splitting the lane
axis instead, ``[TM, TK] -> [TM, TK/BZ, BZ]``, is a reshape Mosaic
refuses.  Grid ``(K//TK, M//TM)``; the stage loop is static (NNZ <= 5
per the paper's hardware cap, §6.2).  Validated in interpret mode
against ``ref.dap_prune_ref``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import dbb
from repro.kernels import autotune


def _dap_kernel(x_ref, o_ref, m_ref, *, nnz, bz):
    xt = x_ref[...].astype(jnp.float32)  # [TK, TM]; exact for bf16/f32
    tk, tm = xt.shape
    xb = xt.reshape(tk // bz, bz, tm)
    mag = jnp.abs(xb)
    kept = jnp.zeros(xb.shape, dtype=jnp.bool_)
    neg = jnp.full_like(mag, -1.0)
    pos = jax.lax.broadcasted_iota(jnp.int32, xb.shape, 1)
    for _ in range(nnz):  # cascade stages (static unroll, <=5)
        cand = jnp.where(kept, neg, mag)
        mx = jnp.max(cand, axis=1, keepdims=True)
        is_max = cand == mx
        # first occurrence wins (comparator-tree tie break toward low index)
        first = jnp.min(jnp.where(is_max, pos, bz), axis=1, keepdims=True)
        winner = (pos == first) & (mx > neg)  # mx==-1 means block exhausted
        kept = kept | winner
    kept = kept & (xb != 0)  # zeros carry no information
    pruned = jnp.where(kept, xb, jnp.zeros_like(xb))
    o_ref[...] = pruned.reshape(tk, tm).astype(o_ref.dtype)
    # int32 bit weights: Mosaic reduces no unsigned integers
    bits = jnp.sum(jnp.where(kept, 1 << pos, 0), axis=1)
    m_ref[...] = bits.astype(m_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("nnz", "bz", "tm", "tk", "interpret")
)
def dap_prune_pallas(
    x: jax.Array,
    *,
    nnz: int,
    bz: int = dbb.DEFAULT_BZ,
    tm: int = 256,
    tk: int = 1024,
    interpret: bool = False,
):
    """DAP over the last axis of ``x [M, K]`` -> (pruned [M, K], mask [M, K//BZ])."""
    m, k = x.shape
    assert k % bz == 0, (k, bz)
    # tm is the lane dim of the transposed tiles; tk // bz whole blocks
    # are the mask block's sublane dim (32 rows: the uint8 tiling)
    tm = autotune.aligned_divisor(tm, m, autotune.LANE)
    tk = autotune.aligned_divisor(tk, k, 4 * autotune.SUBLANE * bz)
    grid = (k // tk, m // tm)
    pruned_t, mask_t = pl.pallas_call(
        functools.partial(_dap_kernel, nnz=nnz, bz=bz),
        grid=grid,
        in_specs=[pl.BlockSpec((tk, tm), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((tk, tm), lambda i, j: (i, j)),
            pl.BlockSpec((tk // bz, tm), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, m), x.dtype),
            jax.ShapeDtypeStruct((k // bz, m), jnp.uint8),
        ],
        interpret=interpret,
    )(x.T)
    return pruned_t.T, mask_t.T
