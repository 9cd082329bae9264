"""Plain reference forward pass of the served configurations.

Imports nothing of the program.  It makes the weights itself from the
seed, with the same random draws the model's published initialisation
uses (normal, scaled by ``1/sqrt(fan_in)``, stored in bfloat16; norm
scales of one), and applies the configuration's wire formats the
straightforward way:

* weights: 4 largest magnitudes of every 8 along the input axis kept
  (ties to the lower index), then symmetric per-output-channel integers
  (``bits`` = 8; the control uses 4);
* activations into every projection but the head: the same 4-of-8
  pruning per token (DAP), then a symmetric per-token integer scale;
* the head: per-token integer activations, no pruning;
* keys and values: one symmetric int8 scale per cached token row;
* integer products accumulate exactly in int32; everything else is
  float32, with each tensor the model stores rounded to bfloat16.

Attention is plain causal softmax attention in float32 over the int8
keys and values read back (``attend``); MLA reads the latent in its
absorbed form.  ``forward_logits`` runs the stack layer by layer over a few
padded sequences at once and returns float32 logits at the rows asked
for, so a 40-layer model fits one chip without its weights ever being
resident together.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

BF16 = jnp.bfloat16
F32 = jnp.float32
SEQ_MULTIPLE = 256  # the reference pads sequences to a multiple of this
QUERY_BLOCK = 256  # query rows a step of ``attend`` scores against every key
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Arch:
    """Widths of one configuration (``model`` of ``configs/<name>.json``)."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    padded_vocab: int
    rope_theta: float
    norm_eps: float
    # MLA widths as sorted (key, value) pairs, hashable so an Arch can be
    # a static jit argument: q_lora_rank, kv_lora_rank, qk_nope_head_dim,
    # qk_rope_head_dim, v_head_dim
    mla_items: Optional[tuple] = None

    @property
    def mla(self) -> Optional[dict]:
        return None if self.mla_items is None else dict(self.mla_items)

    @classmethod
    def from_json(cls, model: dict) -> "Arch":
        keys = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in model.items() if k in keys}
        if model.get("mla"):
            kw["mla_items"] = tuple(sorted(model["mla"].items()))
        return cls(**kw)


@dataclasses.dataclass(frozen=True)
class Precision:
    """Integer widths of the wire: 8/8 as configured, lower for a control."""

    w_bits: int = 8
    a_bits: int = 8


def model_key(seed: int):
    """The PRNG key a run's weights come from (shared with the harness)."""
    key = jax.random.PRNGKey(seed % 2**32)
    if seed >= 2**32:
        key = jax.random.fold_in(key, seed // 2**32)
    return key


# ------------------------------------------------------------ the wire


def _keep_4_of_8(x: jax.Array) -> jax.Array:
    """Mask of the 4 largest magnitudes in each block of 8 along the last
    axis; an element's rank counts the larger ones and the equal ones
    before it."""
    blocks = x.reshape(*x.shape[:-1], x.shape[-1] // 8, 8)
    mag = jnp.abs(blocks.astype(F32))
    a, b = mag[..., :, None], mag[..., None, :]
    idx = jnp.arange(8)
    before = idx[None, :] < idx[:, None]  # [i, j]: j comes before i
    rank = jnp.sum((b > a) | ((b == a) & before), axis=-1)
    return (rank < 4).reshape(x.shape)


def qmax_of(bits: int) -> float:
    return float(2 ** (bits - 1) - 1)


def _int_grid(x: jax.Array, qmax, axis: int):
    """Symmetric integers of ``x`` (float32) sharing one scale along
    ``axis``, on the grid ``[-qmax, qmax]``: ``(q, scale)``, ``q`` held in
    float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    return jnp.clip(jnp.round(x / scale), -qmax, qmax), scale


def pack_weight(w: jax.Array, qmax):
    """bf16 ``[K, N]`` -> (int8 ``[K, N]`` with 4 of every 8 rows of each
    column kept, f32 per-column scale ``[N]``).  ``qmax`` comes in as a
    traced value: the served weights are built op by op, where the
    scale's division is a true one, and a constant divisor would let the
    compiler turn it into a multiplication by its rounded reciprocal."""
    wt = w.T  # blocks run along K
    pruned = jnp.where(_keep_4_of_8(wt), wt.astype(F32), 0.0)
    q, scale = _int_grid(pruned, qmax, axis=-1)
    return q.T.astype(jnp.int8), scale[:, 0]


def quant_act(x: jax.Array, bits: int, prune: bool):
    """bf16 ``[..., K]`` -> (int8, f32 per-token scale ``[...]``)."""
    xf = x.astype(F32)
    if prune:
        xf = jnp.where(_keep_4_of_8(x), xf, 0.0)
    q, scale = _int_grid(xf, qmax_of(bits), axis=-1)
    return q.astype(jnp.int8), scale[..., 0]


def qlinear(x, w, prec: Precision, *, act=None, prune=True, out=BF16):
    """``act(x @ w)`` on the integer wire; ``w = (int8 [K, N], scale [N])``."""
    w_int, w_scale = w
    xq, xs = quant_act(x, prec.a_bits, prune)
    acc = jnp.dot(xq, w_int, preferred_element_type=jnp.int32)
    y = acc.astype(F32) * (xs[..., None] * w_scale)
    if act == "silu":
        y = y * jax.nn.sigmoid(y)
    return y.astype(out)


def kv_roundtrip(x: jax.Array) -> jax.Array:
    """What an int8 KV cache returns for bf16 rows ``[..., D]``, read back
    in bfloat16 as the model computes attention."""
    q, scale = _int_grid(x.astype(F32), qmax_of(8), axis=-1)
    return (q * scale).astype(BF16)


# ------------------------------------------------------------ layers


def attend(q, k, v, scale: float) -> jax.Array:
    """Causal softmax attention in float32: ``q [n, T, H, D]`` over
    ``k [n, T, G, D]`` and ``v [n, T, G, Dv]`` (query head ``h`` reads key
    head ``h // (H // G)``), bf16 ``[n, T, H, Dv]`` out.  ``T`` is a
    multiple of ``QUERY_BLOCK``; the queries go a block at a time so the
    scores of a long sequence never exist whole."""
    n, t, h, d = q.shape
    g, dv = k.shape[2], v.shape[-1]
    qf = q.astype(F32).reshape(n, t, g, h // g, d)
    kf, vf = k.astype(F32), v.astype(F32)
    kpos = jnp.arange(t)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(qf, start, QUERY_BLOCK, axis=1)
        s = jnp.einsum("nbgrd,ntgd->ngrbt", qb, kf, precision=HIGHEST) * scale
        qpos = start + jnp.arange(QUERY_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("ngrbt,ntgd->nbgrd", p, vf, precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(0, t, QUERY_BLOCK))  # [t/B, n, B, g, r, dv]
    out = jnp.moveaxis(out, 0, 1).reshape(n, t, h, dv)
    return out.astype(BF16)



def rmsnorm(x: jax.Array, eps: float) -> jax.Array:
    """RMS norm; the mean square sums by halving the zero-padded row, one
    fixed association for every shape."""
    xf = x.astype(F32)
    sq = xf * xf
    width = 1 << (sq.shape[-1] - 1).bit_length()
    sq = jnp.pad(sq, [(0, 0)] * (sq.ndim - 1) + [(0, width - sq.shape[-1])])
    while sq.shape[-1] > 1:
        sq = sq[..., : sq.shape[-1] // 2] + sq[..., sq.shape[-1] // 2 :]
    var = sq / xf.shape[-1]
    return (xf * jax.lax.rsqrt(var + eps)).astype(BF16)


def rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotate-half RoPE of ``x [n, T, H, D]`` at positions ``pos [n, T]``."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32)[..., None] * inv
    c, s = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., : d // 2].astype(F32), x[..., d // 2 :].astype(F32)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1).astype(BF16)


def _normal(key, shape, scale):
    return (jax.random.normal(key, shape, F32) * scale).astype(BF16)


def _linear_w(key, d_in, d_out, wq):
    return pack_weight(_normal(key, (d_in, d_out), 1.0 / math.sqrt(d_in)), wq)


def gqa_attention(a: Arch, key, h, pos, prec, wq):
    n, t, _ = h.shape
    kq, kk, kv, ko = jax.random.split(key, 4)
    dh, hq, hk = a.head_dim, a.n_heads, a.n_kv_heads
    q = qlinear(h, _linear_w(kq, a.d_model, hq * dh, wq), prec)
    k = qlinear(h, _linear_w(kk, a.d_model, hk * dh, wq), prec)
    v = qlinear(h, _linear_w(kv, a.d_model, hk * dh, wq), prec)
    q = rope(q.reshape(n, t, hq, dh), pos, a.rope_theta)
    k = rope(k.reshape(n, t, hk, dh), pos, a.rope_theta)
    k = kv_roundtrip(k.reshape(n, t, hk * dh)).reshape(n, t, hk, dh)
    v = kv_roundtrip(v).reshape(n, t, hk, dh)
    o = attend(q, k, v, 1.0 / math.sqrt(dh))
    return qlinear(o.reshape(n, t, hq * dh), _linear_w(ko, hq * dh, a.d_model, wq), prec)


def mla_attention(a: Arch, key, h, pos, prec, wq):
    m = a.mla
    n, t, _ = h.shape
    nope, rdim, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    lora, hq = m["kv_lora_rank"], a.n_heads
    ks = jax.random.split(key, 6)
    cq = rmsnorm(qlinear(h, _linear_w(ks[0], a.d_model, m["q_lora_rank"], wq), prec), 1e-5)
    q = qlinear(cq, _linear_w(ks[1], m["q_lora_rank"], hq * (nope + rdim), wq), prec)
    q = q.reshape(n, t, hq, nope + rdim)
    q_rope = rope(q[..., nope:], pos, a.rope_theta)
    kv = qlinear(h, _linear_w(ks[2], a.d_model, lora + rdim, wq), prec)
    c_kv = rmsnorm(kv[..., :lora], 1e-5)
    k_rope = rope(kv[..., lora:][:, :, None, :], pos, a.rope_theta)[:, :, 0]
    lat = kv_roundtrip(jnp.concatenate([c_kv, k_rope], -1))  # the int8 cache
    # kv_up is served dense in bf16 (it never crosses the DBB wire); the
    # heads read the latent through it in MLA's absorbed form: queries
    # through its key half, the latent context through its value half
    w_up = _normal(ks[3], (lora, hq * (nope + dv)), 1.0 / math.sqrt(lora))
    w_up = w_up.reshape(lora, hq, nope + dv)
    q_abs = jnp.einsum("nthd,lhd->nthl", q[..., :nope], w_up[..., :nope],
                       preferred_element_type=F32).astype(BF16)
    qq = jnp.concatenate([q_abs, q_rope], -1)
    ctx = attend(qq, lat[:, :, None, :], lat[:, :, None, :lora],
                 1.0 / math.sqrt(nope + rdim))
    o = jnp.einsum("nthl,lhd->nthd", ctx, w_up[..., nope:],
                   preferred_element_type=F32).astype(BF16)
    return qlinear(o.reshape(n, t, hq * dv), _linear_w(ks[4], hq * dv, a.d_model, wq), prec)


def layer(a: Arch, key, x, pos, prec, wq):
    """One pre-norm decoder layer; ``key`` is the layer's own key and
    ``wq`` the weights' integer bound (``qmax_of(prec.w_bits)``, traced)."""
    k_attn, k_mlp, _ = jax.random.split(key, 3)
    h = rmsnorm(x, a.norm_eps)
    attn = mla_attention if a.mla is not None else gqa_attention
    x = x + attn(a, k_attn, h, pos, prec, wq)
    kg, ku, kd = jax.random.split(k_mlp, 3)
    h = rmsnorm(x, a.norm_eps)
    g = qlinear(h, _linear_w(kg, a.d_model, a.d_ff, wq), prec, act="silu")
    u = qlinear(h, _linear_w(ku, a.d_model, a.d_ff, wq), prec)
    return x + qlinear(g * u, _linear_w(kd, a.d_ff, a.d_model, wq), prec)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _layer_jit(a, key, x, pos, prec, wq):
    return layer(a, key, x, pos, prec, wq)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed_jit(a, key, tokens):
    table = _normal(key, (a.padded_vocab, a.d_model), 0.02)
    return table[tokens]


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head_jit(a, key, x, rows, prec, wq):
    h = rmsnorm(x.reshape(-1, a.d_model)[rows], a.norm_eps)
    w = _linear_w(key, a.d_model, a.padded_vocab, wq)
    return qlinear(h, w, prec, prune=False, out=F32)[:, : a.vocab]


def pad_sequences(seqs: Sequence[np.ndarray], length: int) -> np.ndarray:
    out = np.zeros((len(seqs), length), np.int32)
    for i, s in enumerate(seqs):
        out[i, : len(s)] = s
    return out


def forward_logits(
    a: Arch, seed: int, tokens: np.ndarray, rows: np.ndarray,
    prec: Precision = Precision(),
) -> jax.Array:
    """Float32 logits ``[len(rows), vocab]`` of ``tokens [n, T]`` (right-
    padded; padding never reaches a real row under the causal mask) at
    flat row indices ``rows`` into ``n * T``."""
    k_embed, k_layers, k_head = jax.random.split(model_key(seed), 3)
    layer_keys = jax.random.split(k_layers, a.n_layers)
    n, t = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (n, t))
    wq = jnp.float32(qmax_of(prec.w_bits))
    x = _embed_jit(a, k_embed, jnp.asarray(tokens))
    for i in range(a.n_layers):
        x = _layer_jit(a, layer_keys[i], x, pos, prec, wq)
    return _head_jit(a, k_head, x, jnp.asarray(rows), prec, wq)
