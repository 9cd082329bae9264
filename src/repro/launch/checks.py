"""Correctness checks for a launcher run (``chip_smoke.py`` drives them on
the chip; ``tests/test_launch.py`` at a small size on the CPU).

* :func:`batch_invariance` — every request of a :class:`~repro.launch.
  serve.ServeRun`, served again alone on the same engine, gives the same
  tokens byte for byte.  That is the engine's per-row contract on the
  int8 wire (per-token activation scales, integer accumulation), so it
  holds on any backend and costs no new compile.
* :func:`kernel_phase` — each Pallas kernel once at a model's decode (and
  prefill) shapes against its jnp oracle: the int8 DBB matmuls and DAP
  bit-exact, the float matmuls and fused paged attention within the
  bounds stated beside each comparison.

Each check yields ``(name, ok, detail)`` rows as it goes, so a caller can
report progress on a slow device.
"""

from __future__ import annotations

import math
from typing import Iterator, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dbb
from repro.kernels import dap_prune, dbb_matmul, ops, paged_attn, ref
from repro.launch import serve
from repro.models import attention
from repro.serve.scheduler import FINISH_NUMERICAL

Row = Tuple[str, bool, str]

# f32 unit roundoff: the accumulation error bound of the float matmuls
F32_U = 2.0 ** -24
# fused vs gather paged attention, bf16 K/V and queries: the gather
# path rounds the normalized softmax to bf16 before the PV product,
# the fused kernel carries exp(logit - running max) to ~2^-17 (two
# bf16 parts), and both round the output to bf16 — a few bf16 ulps
# (2^-8 relative) on outputs of unit scale.  The repo's bf16
# boundary test (tests/test_paged_attn.py) uses the same bound.
PAGED_ATTN_TOL = 3e-2


def batch_invariance(run) -> Iterator[Row]:
    """Serve each request of ``run`` alone and compare with its co-batched
    output; also check token ids are in the vocab and no request was
    quarantined."""
    vocab = run.cfg.vocab
    for i, (prompt, res) in enumerate(zip(run.prompts, run.results)):
        solo = np.asarray(run.engine.serve_requests([prompt], run.n_new)[0].tokens)
        out = np.asarray(res.tokens)
        same = np.array_equal(solo, out)
        differ = np.flatnonzero(solo[: out.size] != out[: solo.size])
        first = int(differ[0]) - prompt.shape[0] if differ.size else None
        in_vocab = bool(np.all((out >= 0) & (out < vocab)))
        finite = res.finish_reason != FINISH_NUMERICAL
        yield (
            f"request {i} (prompt {prompt.shape[0]}) solo == co-batched",
            same and in_vocab and finite and res.ok,
            f"finish={res.finish_reason} generated={res.n_generated} "
            f"in_vocab={in_vocab} identical={same} first_diff_token={first}",
        )


def _exact(name, got, want) -> Row:
    got, want = np.asarray(got), np.asarray(want)
    bad = int(np.sum(got != want))
    return (name, bad == 0 and got.shape == want.shape, f"{bad} of {want.size} differ")


def _bounded(name, got, want, bound, why) -> Row:
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    bound = np.asarray(bound, np.float32)
    worst = float(np.max(err / np.maximum(bound, 1e-30)))
    return (name, bool(np.all(err <= bound)), f"max err/bound {worst:.3g} ({why})")


def _matmul_rows(m, k, n, key, interpret) -> Iterator[Row]:
    """The four DBB matmuls at one ``[M, K] x [K, N]`` shape."""
    cfg = dbb.DBBConfig(4, 8)  # the serving wire's 4/8 bound
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (m, k), jnp.float32).astype(jnp.bfloat16)
    w = (jax.random.normal(kw, (k, n), jnp.float32) / math.sqrt(k)).astype(
        jnp.bfloat16
    )
    tag = f"M={m} K={k} N={n}"

    # int8 wire: integer accumulation is exact, so the kernels equal the
    # oracle bit for bit (no bias or activation: one f32 multiply by the
    # shared combined scale, identical on both sides)
    wv, wm, ws = ops.pack_weight_int8(w, cfg)
    xq, xs = ops.quantize_act(x, per_row=True)
    yield _exact(
        f"dbb_matmul_int8 {tag}",
        dbb_matmul.dbb_matmul_int8_pallas(
            xq, xs, wv, wm, ws, cfg=cfg, interpret=interpret
        ),
        ref.dbb_matmul_int8_ref(xq, xs, wv, wm, ws, cfg, out_dtype=jnp.float32),
    )
    av, am, as_ = ops.dap_pack_int8(x, cfg.nnz, cfg.bz, act_scale="per_row")
    yield _exact(
        f"dbb_matmul_aw_int8 {tag}",
        dbb_matmul.dbb_matmul_aw_int8_pallas(
            av, am, as_, wv, wm, ws, cfg_a=cfg, cfg_w=cfg, interpret=interpret
        ),
        ref.dbb_matmul_aw_int8_ref(
            av, am, as_, wv, wm, ws, cfg, cfg, out_dtype=jnp.float32
        ),
    )

    # bf16 wire: bf16 products are exact in f32, so kernel and oracle
    # differ only in the order of the f32 sums; any order is within
    # K * u * sum|x||w| of the exact sum (u = 2^-24), so twice that
    # bounds the difference
    wv, wm = ops.pack_weight(w, cfg)
    l1 = ref.dbb_matmul_ref(jnp.abs(x), jnp.abs(wv), wm, cfg, out_dtype=jnp.float32)
    why = "2 K u sum|x||w|: f32 sums of exact bf16 products"
    yield _bounded(
        f"dbb_matmul bf16 {tag}",
        dbb_matmul.dbb_matmul_pallas(
            x, wv, wm, cfg=cfg, out_dtype=jnp.float32, interpret=interpret
        ),
        ref.dbb_matmul_ref(x, wv, wm, cfg, out_dtype=jnp.float32),
        2 * k * F32_U * l1, why,
    )
    av, am = ops.dap_pack(x, cfg.nnz, cfg.bz)
    l1 = ref.dbb_matmul_aw_ref(
        jnp.abs(av), am, jnp.abs(wv), wm, cfg, cfg, out_dtype=jnp.float32
    )
    yield _bounded(
        f"dbb_matmul_aw bf16 {tag}",
        dbb_matmul.dbb_matmul_aw_pallas(
            av, am, wv, wm, cfg_a=cfg, cfg_w=cfg, out_dtype=jnp.float32,
            interpret=interpret,
        ),
        ref.dbb_matmul_aw_ref(av, am, wv, wm, cfg, cfg, out_dtype=jnp.float32),
        2 * k * F32_U * l1, why,
    )


def _paged_state(key, cfg, b, ps, int8):
    """A paged K/V pool at ``cfg``'s KV width with ``b`` requests of mixed
    lengths on shuffled pages, null-page padded, plus garbage in every
    slot no request owns (``pos = -1``)."""
    kvd = cfg.kv_dim()
    lens = [1 + (97 * (i + 1)) % 300 for i in range(b)]
    p_cnt = max(-(-n // ps) for n in lens) + 1
    n_pages = b * p_cnt + 1
    kk, kv, kp, kq = jax.random.split(key, 4)
    cache = {
        "k": jax.random.normal(kk, (n_pages, ps, kvd), jnp.float32),
        "v": jax.random.normal(kv, (n_pages, ps, kvd), jnp.float32),
    }
    if int8:
        qk, sk = attention.quantize_kv(cache["k"])
        qv, sv = attention.quantize_kv(cache["v"])
        cache = {"k": qk, "k_scale": sk, "v": qv, "v_scale": sv}
    else:
        cache = {n: a.astype(jnp.bfloat16) for n, a in cache.items()}
    order = np.asarray(jax.random.permutation(kp, np.arange(1, n_pages)))
    tables = np.zeros((b, p_cnt), np.int32)
    pos_tbl = np.full((n_pages, ps), -1, np.int32)
    nxt = 0
    for i, n in enumerate(lens):
        for j in range(-(-n // ps)):
            page = int(order[nxt])
            nxt += 1
            tables[i, j] = page
            slots = np.arange(j * ps, (j + 1) * ps)
            pos_tbl[page] = np.where(slots < n, slots, -1)
    q = jax.random.normal(
        kq, (b, 1, cfg.n_heads, cfg.head_dim()), jnp.float32
    ).astype(jnp.bfloat16)
    q_pos = jnp.asarray([[n - 1] for n in lens], jnp.int32)
    return q, cache, jnp.asarray(pos_tbl), jnp.asarray(tables), q_pos


def _paged_rows(cfg, key, b, ps, interpret) -> Iterator[Row]:
    kvh, dh = cfg.n_kv_heads, cfg.head_dim()
    for int8 in (False, True):
        q, cache, pos_tbl, tables, q_pos = _paged_state(key, cfg, b, ps, int8)
        got = paged_attn.paged_attn_cache_layer(
            q, cache, pos_tbl, tables, q_pos, kv_heads=kvh, interpret=interpret
        )
        k_win, v_win, pos_win = attention.paged_read(
            cache, pos_tbl, tables, dtype=jnp.bfloat16
        )
        t = k_win.shape[1]
        want = attention.mha(
            q, k_win.reshape(b, t, kvh, dh), v_win.reshape(b, t, kvh, dh),
            q_pos, pos_win,
        )
        yield _bounded(
            f"paged_attn_fused {'int8' if int8 else 'bf16'} K/V B={b} "
            f"H={cfg.n_heads} KV={kvh} D={dh} PS={ps} P={tables.shape[1]}",
            got, want, PAGED_ATTN_TOL * (1.0 + np.abs(np.asarray(want, np.float32))),
            "bf16 probabilities rounded at different points, bf16 output",
        )


def kernel_phase(cfg, *, interpret: bool = False) -> Iterator[Row]:
    """Every Pallas kernel of the repo once at ``cfg``'s widths against its
    jnp oracle: the DBB matmuls for the MLP up/down and the KV
    projections at a decode M (8) and the launcher's mixed-step M
    (4 x 64), DAP over both MLP widths, and fused paged attention
    (through the serving front end, ``paged_attn_cache_layer``) on bf16
    and int8 K/V pages of the launcher's page size."""
    m_decode, m_prefill = 8, serve.MAX_BATCH * serve.PREFILL_CHUNK
    key = jax.random.PRNGKey(0)
    d, f, kvd = cfg.d_model, cfg.d_ff, cfg.kv_dim()
    for i, (k, n) in enumerate(((d, f), (f, d), (d, kvd))):
        for j, m in enumerate((m_decode, m_prefill)):
            yield from _matmul_rows(
                m, k, n, jax.random.fold_in(key, 10 * i + j), interpret
            )
    for i, k in enumerate((d, f)):
        x = jax.random.normal(
            jax.random.fold_in(key, 100 + i), (m_decode, k), jnp.float32
        ).astype(jnp.bfloat16)
        got = dap_prune.dap_prune_pallas(x, nnz=4, interpret=interpret)
        want = ref.dap_prune_ref(x, 4)
        name = f"dap_prune M={m_decode} K={k}"
        yield _exact(name + " values", got[0], want[0])
        yield _exact(name + " mask", got[1], want[1])
    yield from _paged_rows(
        cfg, jax.random.fold_in(key, 200), serve.MAX_BATCH, serve.PAGE_SIZE, interpret
    )
