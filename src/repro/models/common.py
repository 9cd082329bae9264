"""Shared model building blocks: params+sharding-spec construction, norms,
activations, and the DBB-aware linear layer (where the paper's technique
plugs into every architecture).

Param construction convention
-----------------------------
Every ``make_*`` helper returns ``(params, specs)`` — two parallel pytrees,
the second holding ``jax.sharding.PartitionSpec`` leaves.  Specs express
*intent* (e.g. FSDP over ``data``, tensor-parallel over ``model``); the
launcher sanitizes them against the actual mesh (dropping axes that do not
divide the dim evenly) so a single definition serves every mesh.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import dbb, quant
from repro.core.dap import apply_dap
from repro.core.sparsity import SparsityConfig
from repro.kernels import epilogue, ops

# Logical mesh axis names (see launch/mesh.py).
POD, DATA, MODEL = "pod", "data", "model"
BATCH_AXES = (POD, DATA)  # batch shards over both


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


# --------------------------------------------------------------------- init


def make_linear(
    key,
    d_in: int,
    d_out: int,
    *,
    bias: bool = False,
    dtype=jnp.bfloat16,
    spec: P = P(DATA, MODEL),
    scale: Optional[float] = None,
):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    w = (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)
    params = {"w": w}
    specs = {"w": spec}
    if bias:
        params["b"] = jnp.zeros((d_out,), dtype)
        out_axis = spec[-1] if len(spec) >= 2 else None
        specs["b"] = P(out_axis)
    return params, specs


def make_embedding(key, vocab: int, d: int, *, dtype=jnp.bfloat16):
    w = (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)
    # vocab dims are frequently non-divisible (49155, 50280…): shard d_model.
    return {"w": w}, {"w": P(None, MODEL)}


def make_norm(d: int, *, dtype=jnp.float32, bias: bool = False):
    params = {"scale": jnp.ones((d,), dtype)}
    specs = {"scale": P(None)}
    if bias:
        params["bias"] = jnp.zeros((d,), dtype)
        specs["bias"] = P(None)
    return params, specs


# ---------------------------------------------------- packed activation flow


@dataclasses.dataclass
class PackedAct:
    """A-DBB activation in kernel wire format — the packed hand-off.

    Produced once per consumer group by :func:`maybe_pack_input` (the fused
    ``dap_prune -> pack`` step) and consumed by :func:`linear`'s joint
    A/W-DBB matmul, so DAP'd activations flow between layers *packed*:
    the pruned dense intermediate is never materialized, and sibling
    linears sharing one input (e.g. Q/K/V, gate/up) share one DAP+pack.

    Not a jax pytree on purpose: it lives strictly inside a single traced
    forward pass and never crosses a jit boundary.

    Under the int8 wire (``wire_dtype="int8"`` serving) ``vals`` is int8
    and ``scale`` holds the dynamic dequant scale — a scalar
    (per-tensor) or one scale per token (``SparsityConfig.act_scale ==
    "per_row"``, shape = the leading dims); ``dtype`` still names the
    dense *compute* dtype outputs are produced in.
    """

    vals: jax.Array  # [..., K//BZ, NNZ] (model dtype, or int8 wire)
    mask: jax.Array  # [..., K//BZ] uint8
    cfg: dbb.DBBConfig
    k: int  # dense extent of the packed axis
    dtype: jnp.dtype  # dense dtype (outputs keep it)
    scale: Optional[jax.Array] = None  # f32, scalar or per-row; iff int8


ActOrPacked = Union[jax.Array, PackedAct]


def _active_dap_spec(sp: Optional[SparsityConfig], x, layer_idx, first_layer):
    """The DAP spec :func:`linear` would apply to ``x``, or None."""
    if sp is None or sp.mode != "awdbb":
        return None
    if first_layer and sp.exclude_first_layer:
        return None
    spec = sp.a_spec(layer_idx)
    if spec is None or x.shape[-1] % spec.bz != 0:
        return None
    return spec


def mlp_input_targets(p, act: str) -> tuple:
    """The MLP param dicts that consume the block's residual input."""
    return (p["gate"], p["up"]) if act == "swiglu" else (p["up"],)


def maybe_pack_input(
    x: ActOrPacked,
    targets: Sequence[dict],
    sparsity: Optional[SparsityConfig] = None,
    layer_idx: Optional[int] = None,
    first_layer: bool = False,
) -> ActOrPacked:
    """DAP-prune + pack ``x`` once for a group of packed-weight linears.

    Returns a :class:`PackedAct` when the fused A/W-DBB path applies (A-DBB
    active for this layer and **every** target linear holds wire-format
    weights — i.e. packed serving); otherwise returns ``x`` unchanged and
    each linear falls back to its own dense-path DAP (training keeps the
    straight-through gradient of ``core.dap``).
    """
    if isinstance(x, PackedAct) or not targets:
        return x
    if not all(isinstance(t, dict) and "w_vals" in t for t in targets):
        return x
    spec = _active_dap_spec(sparsity, x, layer_idx, first_layer)
    if spec is None:
        return x
    # scopes name the packer's ops in compiled programs and device traces
    with jax.named_scope("dap_pack"):
        if all("w_scale" in t for t in targets):  # int8 wire end to end
            vals, mask, scale = ops.dap_pack_int8(
                x, spec.nnz, spec.bz,
                act_scale=sparsity.act_scale if sparsity else "per_tensor",
            )
            return PackedAct(vals, mask, spec.cfg, x.shape[-1], x.dtype, scale)
        vals, mask = ops.dap_pack(x, spec.nnz, spec.bz)
    return PackedAct(vals, mask, spec.cfg, x.shape[-1], x.dtype)


# ------------------------------------------------------------------ forward


def _pairwise_sum(x: jax.Array) -> jax.Array:
    """Sum over the last axis (kept as size 1) by adding halves, zero-
    padded to a power of two: one association for every shape and every
    compiled program.  XLA may reorder a ``reduce``, and on a TPU it
    does so by shape, so the same row summed in the mixed step and in
    the decode loop came out different in the last bit — enough to move
    a sampled token and break the serving engine's batch invariance."""
    n = x.shape[-1]
    width = 1 << (n - 1).bit_length()
    if width != n:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - n)])
    while x.shape[-1] > 1:
        half = x.shape[-1] // 2
        x = x[..., :half] + x[..., half:]
    return x


def rmsnorm(x: jax.Array, p, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = _pairwise_sum(xf * xf) / xf.shape[-1]
    out = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return out.astype(x.dtype)


def layernorm(x: jax.Array, p, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    if "bias" in p:
        out = out + p["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


def linear(
    p,
    x: ActOrPacked,
    *,
    sparsity: Optional[SparsityConfig] = None,
    layer_idx: Optional[int] = None,
    dap_input: bool = True,
    first_layer: bool = False,
    act: Optional[str] = None,
) -> jax.Array:
    """DBB-aware linear: ``act(x @ w (+ b))``.

    * ``dense`` / ``wdbb`` training: plain matmul (W-DBB is enforced by the
      trainer's mask, so ``w`` already satisfies the block bound).
    * ``awdbb``: DAP (top-NNZ per 8-block, straight-through grad) on the
      input activations first — paper §5.1/§8.1.
    * serve-packed: ``p`` holds ``w_vals``/``w_mask`` wire-format weights
      (values + bitmask); the matmul streams compressed weights
      (`repro.kernels.ops.dbb_matmul`) with bias+act fused into the
      accumulator epilogue — the memory-roofline attack.
    * packed input: ``x`` may be a :class:`PackedAct` (the fused
      ``dap_prune -> pack`` hand-off); with wire-format weights this runs
      the joint A/W-DBB matmul — both operands stream packed.
    * int8 wire (``p`` holds ``w_scale``): the paper's actual datapath —
      int8 values on the wire, int32 accumulation, dequant (per-channel
      weight scale × dynamic per-tensor activation scale) fused into the
      same epilogue as bias+act.
    """
    sp = sparsity
    if isinstance(x, PackedAct):
        if "w_vals" in p:  # joint A/W-DBB: both operands packed
            with jax.named_scope("dbb_linear"):
                return _linear_aw(p, x, sp, act)
        # Dense weights can't consume the wire format: expand (exact) and
        # continue on the dense path.  DAP is NOT re-applied — packing
        # already pruned.
        vals = x.vals
        if x.scale is not None:
            axis = None if x.scale.ndim == 0 else (-2, -1)
            vals = quant.dequantize(vals, x.scale, axis=axis, dtype=x.dtype)
        x = ops.expand_act(vals, x.mask, x.cfg)
    elif dap_input:
        spec = _active_dap_spec(sp, x, layer_idx, first_layer)
        if spec is not None:
            x = apply_dap(x, spec)

    if "w_vals" in p:  # packed serving weights, dense activations
        with jax.named_scope("dbb_linear"):
            return _linear_w(p, x, sp, act)
    y = jnp.einsum("...k,kn->...n", x, p["w"].astype(x.dtype))
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    # apply_act is dtype-preserving, so the dense path keeps model-dtype
    # numerics (identical to the pre-fusion silu/gelu call sites)
    return epilogue.apply_act(y, act)


def _linear_aw(p, x: PackedAct, sp, act) -> jax.Array:
    """Joint A/W-DBB matmul: a packed activation meets packed weights."""
    cfg_w = dbb.DBBConfig(sp.w_nnz, sp.bz) if sp else dbb.DBBConfig(4, 8)
    lead = x.vals.shape[:-2]
    vals2 = x.vals.reshape((-1,) + x.vals.shape[-2:])
    mask2 = x.mask.reshape((-1,) + x.mask.shape[-1:])
    if "w_scale" in p:  # int8 wire on both operands
        if x.scale is not None:
            # per-row scales carry one value per token: flatten the lead
            # dims alongside the values
            x_scale = x.scale if x.scale.ndim == 0 else x.scale.reshape(-1)
        else:
            # bf16-packed input meets int8 weights (mixed targets):
            # quantize the packed values in place, per-tensor
            vals2, x_scale = quant.quantize(vals2)
        y2 = ops.dbb_matmul_aw_int8(
            vals2, mask2, x_scale,
            p["w_vals"], p["w_mask"], p["w_scale"],
            x.cfg, cfg_w,
            impl="jnp", bias=p.get("b"), act=act, out_dtype=x.dtype,
        )
    else:
        if x.scale is not None:  # int8-packed input, bf16 weights
            vals2 = quant.dequantize(vals2, x.scale, dtype=x.dtype)
        y2 = ops.dbb_matmul_aw(
            vals2, mask2, p["w_vals"], p["w_mask"], x.cfg, cfg_w,
            impl="jnp", bias=p.get("b"), act=act, out_dtype=x.dtype,
        )
    return y2.reshape(lead + y2.shape[-1:])


def _linear_w(p, x: jax.Array, sp, act) -> jax.Array:
    """Packed weights, dense activations."""
    cfg = dbb.DBBConfig(sp.w_nnz, sp.bz) if sp else dbb.DBBConfig(4, 8)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if "w_scale" in p:  # int8 wire: dynamic act quant (sp.act_scale)
        y2 = ops.dbb_matmul_int8(
            x2, p["w_vals"], p["w_mask"], p["w_scale"], cfg,
            impl="jnp", bias=p.get("b"), act=act, out_dtype=x.dtype,
            act_scale=sp.act_scale if sp else "per_tensor",
        )
    else:
        y2 = ops.dbb_matmul(
            x2, p["w_vals"], p["w_mask"], cfg,
            impl="jnp", bias=p.get("b"), act=act, out_dtype=x.dtype,
        )
    return y2.reshape(*lead, y2.shape[-1])


def pack_linear_params(p, sp: SparsityConfig, wire_dtype: str = "native"):
    """Convert a dense linear param dict to packed DBB wire format.

    ``wire_dtype="native"`` keeps the model dtype for the wire values;
    ``"int8"`` quantizes them (symmetric per-output-channel scales,
    ``repro.core.quant``) and adds ``w_scale`` so :func:`linear` runs the
    int8 kernels.  Handles both plain ``[K, N]`` weights and
    layer-stacked ``[L, K, N]`` (scan layout) — the stack dim is
    vmapped, so scanning slices the packed tensors exactly like dense
    ones.
    """
    if wire_dtype not in ("native", "int8"):
        raise ValueError(f"unknown wire_dtype {wire_dtype!r}; native|int8")
    cfg = dbb.DBBConfig(sp.w_nnz, sp.bz)
    w = p["w"]
    pack_one = (
        (lambda wi: ops.pack_weight_int8(wi, cfg))
        if wire_dtype == "int8"
        else (lambda wi: ops.pack_weight(wi, cfg))
    )
    packed = jax.vmap(pack_one)(w) if w.ndim == 3 else pack_one(w)
    out = {"w_vals": packed[0], "w_mask": packed[1]}
    if wire_dtype == "int8":
        out["w_scale"] = packed[2]
    if "b" in p:
        out["b"] = p["b"]
    return out


def silu(x):
    return x * jax.nn.sigmoid(x)


def mlp_forward(p, x: ActOrPacked, *, act: str, sparsity=None, layer_idx=None):
    """Gated (swiglu) or plain (gelu) MLP with DBB hooks on both matmuls.

    The input is DAP-packed **once** and shared by gate+up (callers may
    pass an already-packed ``x`` — see blocks.py), the activation fuses
    into the matmul epilogue, and the hidden tensor is re-packed for the
    down projection — on the packed serving path no pruned dense
    intermediate ever hits memory between the two matmuls.
    """
    kw = dict(sparsity=sparsity, layer_idx=layer_idx)
    xin = maybe_pack_input(x, mlp_input_targets(p, act), sparsity, layer_idx)
    if act == "swiglu":
        g = linear(p["gate"], xin, act="silu", **kw)
        u = linear(p["up"], xin, **kw)
        h = g * u
    else:
        h = linear(p["up"], xin, act="gelu", **kw)
    hin = maybe_pack_input(h, (p["down"],), sparsity, layer_idx)
    return linear(p["down"], hin, **kw)


def make_mlp(key, d: int, f: int, *, act: str, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 3)
    params, specs = {}, {}
    if act == "swiglu":
        params["gate"], specs["gate"] = make_linear(ks[0], d, f, dtype=dtype, spec=P(DATA, MODEL))
        params["up"], specs["up"] = make_linear(ks[1], d, f, dtype=dtype, spec=P(DATA, MODEL))
    else:
        params["up"], specs["up"] = make_linear(ks[1], d, f, dtype=dtype, spec=P(DATA, MODEL))
    params["down"], specs["down"] = make_linear(ks[2], f, d, dtype=dtype, spec=P(MODEL, DATA))
    return params, specs
