"""Mixture-of-Experts FFN with sort-free, group-local capacity dispatch.

Routing runs independently per *token group* (``n_groups`` = number of
data-parallel shards, set by the launcher): tokens never cross groups, so
under pjit every dispatch op partitions along the group dim with zero
collectives.  The only cross-device traffic is the expert einsum boundary
([G, E, C, d] resharding from group-sharded to expert-sharded = the
expert-parallel all-to-all), exactly like production MoE stacks.

Slotting is cumsum-based (no argsort — XLA's SPMD partitioner handles
sort by gathering non-sorted dims, which would replicate the whole
activation tensor): slot(t) = #earlier (token, k) pairs routed to the
same expert; slots >= capacity are dropped (capacity-factor semantics).

DBB hooks: expert matmuls are batched einsums over [E, d, f] weights with
W-DBB masks applied by the trainer; DAP applies once pre-dispatch.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.dap import apply_dap
from repro.models.common import DATA, MODEL, silu


def make_moe(key, cfg, dtype):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe.n_experts
    ks = jax.random.split(key, 4)
    scale = 1.0 / math.sqrt(d)
    params = {
        "router": {
            "w": (jax.random.normal(ks[0], (d, e), jnp.float32) * scale).astype(
                jnp.float32
            )
        },
        "gate": (jax.random.normal(ks[1], (e, d, f), jnp.float32) * scale).astype(dtype),
        "up": (jax.random.normal(ks[2], (e, d, f), jnp.float32) * scale).astype(dtype),
        "down": (
            jax.random.normal(ks[3], (e, f, d), jnp.float32) / math.sqrt(f)
        ).astype(dtype),
    }
    specs = {
        "router": {"w": P(None, None)},
        "gate": P(MODEL, DATA, None),
        "up": P(MODEL, DATA, None),
        "down": P(MODEL, None, DATA),
    }
    return params, specs


def capacity(n_tokens: int, cfg) -> int:
    m = cfg.moe
    c = int(math.ceil(n_tokens * m.top_k / m.n_experts * m.capacity_factor))
    return max(8, ((c + 7) // 8) * 8)  # pad to 8 for tiling


def _dispatch_group(xt, top_e, top_p, e: int, k: int, cap: int):
    """One token group: xt [T, d], top_e/top_p [T, K] -> (buf [E*C, d],
    dest [T*K], keep [T*K], w [T*K])."""
    t = xt.shape[0]
    flat_e = top_e.reshape(t * k)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)  # [T*K, E]
    ranks = jnp.cumsum(onehot, axis=0) - onehot  # earlier same-expert pairs
    slot = jnp.sum(onehot * ranks, axis=-1)  # [T*K]
    keep = slot < cap
    dest = jnp.where(keep, flat_e * cap + slot, e * cap)  # overflow slot
    tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    buf = jnp.zeros((e * cap + 1, xt.shape[1]), xt.dtype)
    buf = buf.at[dest].add(jnp.where(keep[:, None], xt[tok], 0))
    return buf[: e * cap], dest, keep, top_p.reshape(t * k)


def moe_forward(p, x: jax.Array, cfg, *, layer_idx=None, n_groups: int = 1):
    """x [B, S, d] -> (y [B, S, d], aux_loss scalar).

    When a distribution context is active (launchers set it), dispatch
    runs inside an explicit shard_map with a hand-written expert-parallel
    all-to-all — GSPMD replicates the batched dispatch scatter otherwise
    (measured: 52 GB/layer/device of activation all-gathers on
    granite-moe train_4k; see EXPERIMENTS.md §Perf-B).  Without a context
    (single-device tests) the pure-pjit group-local path below runs.

    ``n_groups`` must divide B; routing/dispatch is local to each group.
    """
    from repro.sharding import context as dist_ctx

    ctx = dist_ctx.get_context()
    if ctx is not None:
        return _moe_forward_shard_map(p, x, cfg, ctx, layer_idx=layer_idx)
    m = cfg.moe
    b, s, d = x.shape
    g = max(1, min(n_groups, b))
    while b % g:
        g -= 1
    t = b * s // g  # tokens per group
    e, k = m.n_experts, m.top_k
    sp = cfg.sparsity

    xt = x.reshape(g, t, d)
    if sp is not None and sp.mode == "awdbb":
        spec = sp.a_spec(layer_idx)
        if spec is not None and d % spec.bz == 0:
            xt = apply_dap(xt, spec)

    logits = jnp.einsum("gtd,de->gte", xt.astype(jnp.float32), p["router"]["w"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)  # [G, T, K] (sort dim = E: tiny)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    cap = capacity(t, cfg)
    buf, dest, keep, w_flat = jax.vmap(
        lambda xg, eg, pg: _dispatch_group(xg, eg, pg, e, k, cap)
    )(xt, top_e, top_p)
    buf = buf.reshape(g, e, cap, d)  # group-sharded -> expert-sharded (A2A)

    if cfg.mlp_act == "swiglu":
        gate = jnp.einsum("gecd,edf->gecf", buf, p["gate"].astype(buf.dtype))
        up = jnp.einsum("gecd,edf->gecf", buf, p["up"].astype(buf.dtype))
        h = silu(gate) * up
    else:
        h = jax.nn.gelu(
            jnp.einsum("gecd,edf->gecf", buf, p["up"].astype(buf.dtype)),
            approximate=True,
        )
    out_buf = jnp.einsum("gecf,efd->gecd", h, p["down"].astype(h.dtype))
    out_flat = out_buf.reshape(g, e * cap, d)

    # combine: pure gather (no scatter) back to (token, k) slots
    gathered = jnp.take_along_axis(
        out_flat, jnp.minimum(dest, e * cap - 1)[..., None], axis=1
    )  # [G, T*K, d]
    gathered = jnp.where(keep[..., None], gathered, 0) * w_flat[..., None].astype(
        out_flat.dtype
    )
    y = jnp.sum(gathered.reshape(g, t, k, d), axis=2)

    # switch-style load-balance aux loss
    frac_tokens = jnp.mean(
        jax.nn.one_hot(top_e, e, dtype=jnp.float32).sum(axis=2), axis=(0, 1)
    )
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(frac_tokens / k * frac_probs) * m.router_aux_weight
    return y.reshape(b, s, d).astype(x.dtype), aux


def _moe_forward_shard_map(p, x: jax.Array, cfg, ctx, *, layer_idx=None):
    """Explicit expert parallelism: per-shard local routing + dispatch,
    all-to-all over the expert axis, local expert FFN, reverse all-to-all,
    local combine.  The only cross-device traffic is the dispatched rows
    (2 x capacity x d per direction) — the canonical MoE schedule.
    """
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    b, s, d = x.shape
    e, k = m.n_experts, m.top_k
    sp = cfg.sparsity
    ea = ctx.expert_axis
    ba = ctx.batch_axes
    mesh = ctx.mesh
    n_exp_shards = dict(zip(mesh.axis_names, mesh.devices.shape))[ea]
    e_loc = e // n_exp_shards
    assert e_loc * n_exp_shards == e, (e, n_exp_shards)

    if sp is not None and sp.mode == "awdbb":
        spec = sp.a_spec(layer_idx)
        if spec is not None and d % spec.bz == 0:
            x = apply_dap(x, spec)

    # Shard the sequence dim over the expert axis too, so all 256 devices
    # dispatch *distinct* tokens (x replicated over `model` would make
    # every expert shard compute an identical dispatch and the all-to-all
    # concatenate 16 duplicates — measured 4x redundant FLOPs, §Perf-B).
    seq_split = s % n_exp_shards == 0 and s >= n_exp_shards
    x_seq_axis = ea if seq_split else None

    def local_fn(x_l, router_w, gate, up, down):
        bl, sl = x_l.shape[0], x_l.shape[1]
        t_l = bl * sl
        xt = x_l.reshape(t_l, d)
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router_w)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        cap = capacity(t_l, cfg)
        buf, dest, keep, w_flat = _dispatch_group(xt, top_e, top_p, e, k, cap)
        buf = buf.reshape(e, cap, d)
        # ---- expert-parallel all-to-all: [E, C, d] -> [E_loc, C*S, d]
        buf = jax.lax.all_to_all(buf, ea, split_axis=0, concat_axis=1, tiled=True)
        if cfg.mlp_act == "swiglu":
            g_ = jnp.einsum("ecd,edf->ecf", buf, gate.astype(buf.dtype))
            u_ = jnp.einsum("ecd,edf->ecf", buf, up.astype(buf.dtype))
            h = silu(g_) * u_
        else:
            h = jax.nn.gelu(
                jnp.einsum("ecd,edf->ecf", buf, up.astype(buf.dtype)),
                approximate=True,
            )
        out = jnp.einsum("ecf,efd->ecd", h, down.astype(h.dtype))
        # ---- reverse all-to-all: [E_loc, C*S, d] -> [E, C, d]
        out = jax.lax.all_to_all(out, ea, split_axis=1, concat_axis=0, tiled=True)
        out_flat = out.reshape(e * cap, d)
        gathered = jnp.where(
            keep[:, None], out_flat[jnp.clip(dest, 0, e * cap - 1)], 0
        ) * w_flat[:, None].astype(out_flat.dtype)
        tok = jnp.repeat(jnp.arange(t_l, dtype=jnp.int32), k)
        y_l = jnp.zeros((t_l, d), out_flat.dtype).at[tok].add(gathered)
        frac_tokens = jnp.mean(
            jax.nn.one_hot(top_e, e, dtype=jnp.float32).sum(axis=1), axis=0
        )
        frac_probs = jnp.mean(probs, axis=0)
        aux = e * jnp.sum(frac_tokens / k * frac_probs) * m.router_aux_weight
        aux = jax.lax.pmean(aux, ba)
        aux = jax.lax.pmean(aux, ea)  # uniform across all axes for out_spec P()
        return y_l.reshape(bl, sl, d).astype(x.dtype), aux

    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            P(ba, x_seq_axis, None),     # x: batch- (and seq-) sharded
            P(None, None),               # router: replicated
            P(ea, None, None),           # experts: sharded over expert axis
            P(ea, None, None),
            P(ea, None, None),
        ),
        out_specs=(P(ba, x_seq_axis, None), P()),
        check_vma=False,
    )
    gate = p["gate"] if cfg.mlp_act == "swiglu" else p["up"]
    return fn(x, p["router"]["w"], gate, p["up"], p["down"])
