"""Density Bound Block (DBB) structured sparsity — the paper's core format.

A DBB tensor tiles the *reduction/channel* dimension into blocks of ``BZ``
elements and bounds the number of non-zeros per block to ``NNZ`` (paper §3.1,
Fig. 4/5).  We refer to a configuration as ``NNZ/BZ`` (e.g. 4/8).

This module provides the pure-JAX reference semantics used everywhere in the
framework:

* :func:`topk_block_mask`   — the Top-NNZ magnitude selection per block
  (paper Fig. 8, the DAP maxpool cascade, and the W-DBB pruning criterion).
* :func:`prune`             — apply the mask (dense-in, dense-out).
* :func:`pack` / :func:`unpack` — compressed layout <-> dense layout.  The
  compressed layout stores only ``NNZ`` values per block plus a positional
  index (the paper's bitmask ``M``); shapes are *static*, so the layout is
  jit/pjit friendly.
* :func:`block_density`     — measured per-block NNZ statistics.

Layout convention
-----------------
All functions operate on the **last axis** of the input.  ``x`` with shape
``[..., K]`` and ``K % BZ == 0`` is viewed as ``[..., K//BZ, BZ]`` blocks.
Packed values have shape ``[..., K//BZ, NNZ]`` and packed indices (int8,
position-in-block) have shape ``[..., K//BZ, NNZ]``.  The bitmask form is
``[..., K//BZ]`` uint8 where bit ``b`` set means position ``b`` is non-zero
(valid for BZ <= 8, the paper's block size).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

DEFAULT_BZ = 8  # paper: "a block size of 8 ... good balance" (§6.2)


@dataclasses.dataclass(frozen=True)
class DBBConfig:
    """An ``NNZ/BZ`` density-bound-block configuration.

    ``nnz == bz`` means dense (the "conventional dense mode for unpruned
    models", paper §3.1).
    """

    nnz: int = 4
    bz: int = DEFAULT_BZ

    def __post_init__(self):
        if not (1 <= self.nnz <= self.bz):
            raise ValueError(f"NNZ must be in [1, BZ]; got {self.nnz}/{self.bz}")

    @property
    def is_dense(self) -> bool:
        return self.nnz == self.bz

    @property
    def density(self) -> float:
        return self.nnz / self.bz

    def __str__(self) -> str:  # "4/8" like the paper
        return f"{self.nnz}/{self.bz}"


def _to_blocks(x: jax.Array, bz: int) -> jax.Array:
    k = x.shape[-1]
    if k % bz != 0:
        raise ValueError(f"last dim {k} not divisible by block size {bz}")
    return x.reshape(*x.shape[:-1], k // bz, bz)


def _from_blocks(xb: jax.Array) -> jax.Array:
    return xb.reshape(*xb.shape[:-2], xb.shape[-2] * xb.shape[-1])


def topk_block_mask(x: jax.Array, cfg: DBBConfig) -> jax.Array:
    """Boolean mask keeping the Top-NNZ magnitude elements of each block.

    Implemented exactly like the DAP hardware (paper Fig. 8): a cascade of
    ``NNZ`` magnitude maxpool stages, each discounting previous winners,
    ties broken toward the lower index (first comparator match).

    Deliberately avoids ``top_k``/``sort``: XLA's SPMD partitioner handles
    sort by all-gathering non-sort dimensions, which would turn this
    pointwise-block-local op into a full-tensor collective.  The cascade is
    max/where only — it partitions along every non-block dim for free.
    """
    if cfg.is_dense:
        return jnp.ones(x.shape, dtype=bool)
    xb = _to_blocks(x, cfg.bz)
    mag = jnp.abs(xb)
    pos = jax.lax.broadcasted_iota(jnp.int32, xb.shape, xb.ndim - 1)
    kept = jnp.zeros(xb.shape, dtype=bool)
    neg = jnp.full(mag.shape, -jnp.inf, mag.dtype)
    for _ in range(cfg.nnz):  # static unroll; NNZ <= BZ = 8
        cand = jnp.where(kept, neg, mag)
        mx = jnp.max(cand, axis=-1, keepdims=True)
        first = jnp.min(
            jnp.where(cand == mx, pos, cfg.bz), axis=-1, keepdims=True
        )
        kept = kept | (pos == first)
    return _from_blocks(kept)


def prune(x: jax.Array, cfg: DBBConfig) -> jax.Array:
    """Dense -> dense Top-NNZ-per-block pruning (zeros below the bound)."""
    if cfg.is_dense:
        return x
    return jnp.where(topk_block_mask(x, cfg), x, jnp.zeros_like(x))


@dataclasses.dataclass
class PackedDBB:
    """Compressed DBB tensor: values + per-block position indices.

    ``values``: ``[..., K//BZ, NNZ]`` — same dtype as the dense tensor.
    ``indices``: ``[..., K//BZ, NNZ]`` int8 — position of each value within
    its block (0..BZ-1); always ``NNZ`` *distinct* positions, kept ones
    first in ascending order.  Slots beyond the block's true NNZ hold an
    unused (distinct) position with value 0 (the paper: "blocks that have
    less than NNZ non-zero elements will include one or more zeros in the
    compressed form", §3.1).
    ``cfg``: the NNZ/BZ bound.  ``k``: original dense extent of last axis.
    """

    values: jax.Array
    indices: jax.Array
    cfg: DBBConfig
    k: int

    @property
    def bitmask(self) -> jax.Array:
        """Paper's bitmask ``M``: uint8 per block (BZ<=8), bit b = pos b set."""
        # one-hot over positions, masked by non-zero values, OR'd over slots
        onehot = (
            self.indices[..., None].astype(jnp.int32)
            == jnp.arange(self.cfg.bz, dtype=jnp.int32)
        ) & (self.values != 0)[..., None]  # [..., nblk, NNZ, BZ]
        bits = jnp.any(onehot, axis=-2)  # [..., nblk, BZ]
        weights = (2 ** jnp.arange(self.cfg.bz, dtype=jnp.uint32)).astype(jnp.uint32)
        return jnp.sum(bits.astype(jnp.uint32) * weights, axis=-1).astype(jnp.uint8)

    def compression_ratio(self) -> float:
        """Bytes(dense) / bytes(packed incl. index) for the value dtype."""
        b = jnp.dtype(self.values.dtype).itemsize
        dense = self.cfg.bz * b
        packed = self.cfg.nnz * (b + 1)  # int8 index per kept value
        return dense / packed


def pack(x: jax.Array, cfg: DBBConfig, assume_pruned: bool = False) -> PackedDBB:
    """Dense -> packed.  If not ``assume_pruned``, Top-NNZ prunes first.

    The packed representation is exact iff each block satisfies the bound
    (which :func:`prune` guarantees).
    """
    xb = _to_blocks(x, cfg.bz)
    if assume_pruned:
        # Order by (is_zero, index): nonzeros first, stable by position.
        key = jnp.where(xb != 0, 0, 1) * cfg.bz + jnp.arange(cfg.bz)
    else:
        # Order by (not-in-topk, index) using the DAP mask.
        mask_b = _to_blocks(topk_block_mask(x, cfg), cfg.bz)
        key = jnp.where(mask_b, 0, 1) * cfg.bz + jnp.arange(cfg.bz)
    order = jnp.argsort(key, axis=-1)[..., : cfg.nnz]
    vals = jnp.take_along_axis(xb, order, axis=-1)
    if not assume_pruned:
        mask_sel = jnp.take_along_axis(mask_b, order, axis=-1)
        vals = jnp.where(mask_sel, vals, jnp.zeros_like(vals))
    return PackedDBB(
        values=vals, indices=order.astype(jnp.int8), cfg=cfg, k=x.shape[-1]
    )


def unpack(p: PackedDBB) -> jax.Array:
    """Packed -> dense.  Inverse of :func:`pack` on DBB-compliant tensors.

    Implemented as a one-hot expansion — the software analogue of the
    DP4M8 mux (paper Fig. 6c), vectorized over the block: position j of
    slot s contributes ``values[s] * (indices[s] == j)``.
    """
    onehot = (
        p.indices[..., None].astype(jnp.int32)
        == jnp.arange(p.cfg.bz, dtype=jnp.int32)
    )  # [..., nblk, NNZ, BZ]
    out_b = jnp.sum(
        p.values[..., None].astype(jnp.float32) * onehot.astype(jnp.float32),
        axis=-2,
    ).astype(p.values.dtype)  # [..., nblk, BZ]
    return _from_blocks(out_b)


def pack_bitmask(x: jax.Array, cfg: DBBConfig):
    """Dense -> (values, bitmask) in *rank order* — the kernel wire format.

    Returns ``values [..., K//BZ, NNZ]`` and ``bitmask [..., K//BZ] uint8``
    where bit ``b`` of the mask marks a kept **non-zero** element at block
    position ``b``, and value slot ``j`` holds the ``j``-th set bit's value
    (ascending position).  Unused slots are zero.  This matches the paper's
    Fig. 5 layout and lets hardware (or the Pallas kernel) reconstruct
    position ``b`` as ``bit_b ? values[popcount(mask & (2^b - 1))] : 0``.
    """
    xb = _to_blocks(x, cfg.bz)
    kept = _to_blocks(topk_block_mask(x, cfg), cfg.bz) & (xb != 0)
    pos = jnp.arange(cfg.bz, dtype=jnp.int32)
    weights = (2 ** pos).astype(jnp.uint32)
    bitmask = jnp.sum(kept.astype(jnp.uint32) * weights, axis=-1).astype(jnp.uint8)
    # slot j takes the kept value whose rank (kept positions below it,
    # a popcount) is j: a select and a block sum per slot, exact because
    # one term is non-zero — no sort and no gather, whose index tensors
    # cost more device memory than the weight itself at vocab widths
    low = bitmask.astype(jnp.int32)[..., None] & ((1 << pos) - 1)
    rank = jax.lax.population_count(low)
    zero = jnp.zeros_like(xb)
    vals = jnp.stack(
        [
            jnp.sum(jnp.where(kept & (rank == j), xb, zero), axis=-1)
            for j in range(cfg.nnz)
        ],
        axis=-1,
    )
    return vals, bitmask


def expand_bitmask(values: jax.Array, bitmask: jax.Array, cfg: DBBConfig) -> jax.Array:
    """(values, bitmask) -> dense; inverse of :func:`pack_bitmask`.

    Pure-jnp rank-decode: ``dense[b] = bit_b ? values[rank(b)] : 0`` with
    ``rank(b) = popcount(mask & (2^b - 1))``.
    """
    mask = bitmask.astype(jnp.int32)
    pos = jnp.arange(cfg.bz, dtype=jnp.int32)
    bits = (mask[..., None] >> pos) & 1  # [..., nblk, BZ]
    rank = jnp.cumsum(bits, axis=-1) - bits  # popcount of lower bits
    # gather values by rank, per block
    onehot = rank[..., None] == jnp.arange(cfg.nnz, dtype=jnp.int32)
    gathered = jnp.sum(
        values[..., None, :].astype(jnp.float32) * onehot.astype(jnp.float32),
        axis=-1,
    )
    dense_b = (bits.astype(jnp.float32) * gathered).astype(values.dtype)
    return _from_blocks(dense_b)


def pack_bitmask_int8(x: jax.Array, cfg: DBBConfig, scale_axis=None):
    """Dense -> (int8 values, bitmask, f32 scale) — the INT8 wire format.

    Same rank-order layout as :func:`pack_bitmask`, but the kept values
    are symmetrically quantized (``repro.core.quant``) so the wire
    carries 1 byte per value + 1 mask byte per block — the paper's
    actual INT8 datapath (§6: 8-bit operands, 32-bit accumulators).

    ``scale_axis`` names the *packed-layout* axes the scale is shared
    over (``None`` = per-tensor, the dynamic-activation mode).  Weights
    use per-output-channel scales: pack ``w.T`` so the channel is a
    leading axis, then share the scale over the block/slot axes — see
    ``repro.kernels.ref.pack_weight_int8``.

    The bitmask marks the *pre-quantization* non-zeros; a kept value may
    round to wire 0, which dequantizes to exact 0 — decode stays exact.
    """
    from repro.core import quant  # local: dbb must not hard-depend on quant

    vals, bitmask = pack_bitmask(x, cfg)
    q, scale = quant.quantize(vals, axis=scale_axis)
    return q, bitmask, scale


def expand_bitmask_int8(
    values: jax.Array, bitmask: jax.Array, scale: jax.Array, cfg: DBBConfig,
    scale_axis=None, dtype=jnp.float32,
) -> jax.Array:
    """(int8 values, bitmask, scale) -> dense; inverse of
    :func:`pack_bitmask_int8` up to the quantization grid."""
    from repro.core import quant

    deq = quant.dequantize(values, scale, axis=scale_axis)
    return expand_bitmask(deq, bitmask, cfg).astype(dtype)


def block_density(x: jax.Array, bz: int = DEFAULT_BZ) -> jax.Array:
    """Histogram-ready per-block NNZ counts, shape ``[..., K//BZ]``."""
    xb = _to_blocks(x, bz)
    return jnp.sum(xb != 0, axis=-1)


def satisfies(x: jax.Array, cfg: DBBConfig) -> jax.Array:
    """Scalar bool: every block obeys the NNZ bound."""
    return jnp.all(block_density(x, cfg.bz) <= cfg.nnz)
