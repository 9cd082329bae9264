"""Kernel microbenchmarks on this host (CPU): wall time of the jnp DBB
ops (the dry-run path) and the packed-vs-dense byte ratio they realize.
Pallas kernels target TPU; interpret-mode timing is not meaningful, so we
time the jnp implementations that lower to the same HLO structure."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dbb
from repro.kernels import autotune, ops
from repro.kernels.dbb_matmul import dbb_matmul_int8_pallas, dbb_matmul_pallas


def maybe_autotune(x, wv, wm, cfg):
    """When REPRO_AUTOTUNE=1, sweep Pallas tile candidates for this shape
    and cache the winner (persisted via REPRO_AUTOTUNE_CACHE).  Meaningful
    on TPU; on hosts without a TPU every candidate fails to compile and
    the sweep falls back to the heuristic (still recorded)."""
    if not autotune.autotune_enabled():
        return None
    m, k = x.shape
    n = wv.shape[-1]

    def run(tiles):
        tm, tk, tn = tiles
        return lambda: dbb_matmul_pallas(
            x, wv, wm, cfg=cfg, tm=tm, tk=tk, tn=tn
        )

    return autotune.autotune(run, m, k, n, cfg.nnz, cfg.bz, kind="w")


def maybe_autotune_int8(x, wv8, wm8, ws8, cfg):
    """Companion sweep for the int8 kernel — populates the ``w_int8``
    cache kind (its wider-K candidates are a different optimum than the
    f32 kind's, so the keys never alias)."""
    if not autotune.autotune_enabled():
        return None
    xq, xs = ops.quantize_act(x)
    m, k = x.shape
    n = wv8.shape[-1]

    def run(tiles):
        tm, tk, tn = tiles
        return lambda: dbb_matmul_int8_pallas(
            xq, xs, wv8, wm8, ws8, cfg=cfg, tm=tm, tk=tk, tn=tn
        )

    return autotune.autotune(run, m, k, n, cfg.nnz, cfg.bz, kind="w_int8")


def _time(f, *args, n=5, passes=3):
    """Best-of-``passes`` mean wall time (µs) after one warmup call.

    Best-of suppresses background-load noise (this host is shared); the
    warmup is a single call (the seed version dispatched ``f`` twice)."""
    jax.block_until_ready(f(*args))  # warmup/compile — exactly one call
    best = float("inf")
    for _ in range(passes):
        t0 = time.perf_counter()
        for _ in range(n):
            jax.block_until_ready(f(*args))
        best = min(best, (time.perf_counter() - t0) / n * 1e6)
    return best


def bench_dbb_matmul(smoke: bool = False):
    cfg = dbb.DBBConfig(4, 8)
    # keep the acceptance-criterion shape even in smoke mode (timing is
    # cheap; only the rep count drops) so BENCH_kernels.json always tracks
    # the same operating point across PRs
    m, k, n = 256, 1024, 1024
    reps = 2 if smoke else 5
    x = jnp.asarray(np.random.default_rng(0).normal(size=(m, k)).astype(np.float32))
    w = jnp.asarray(np.random.default_rng(1).normal(size=(k, n)).astype(np.float32))
    b = jnp.asarray(np.random.default_rng(2).normal(size=(n,)).astype(np.float32))
    wv, wm = ops.pack_weight(w, cfg)
    f_dense = jax.jit(lambda a, c: a @ c)
    f_dbb = jax.jit(lambda a, v, mk: ops.dbb_matmul(a, v, mk, cfg, impl="jnp"))

    # seed-era decode (moveaxis + expand_bitmask round-trip) kept as the
    # in-run baseline for the decode-rewrite speedup (docs/perf.md)
    def _seed_decode_matmul(a, v, mk):
        w_dense = dbb.expand_bitmask(
            jnp.moveaxis(v, -1, 0), jnp.moveaxis(mk, -1, 0), cfg
        ).T
        return jnp.dot(
            a, w_dense.astype(a.dtype), preferred_element_type=jnp.float32
        ).astype(a.dtype)

    f_seed = jax.jit(_seed_decode_matmul)
    f_fused = jax.jit(
        lambda a, v, mk, bb: ops.dbb_matmul(
            a, v, mk, cfg, impl="jnp", bias=bb, act="silu"
        )
    )
    f_aw = jax.jit(
        lambda a, v, mk: ops.dbb_matmul_aw(
            *ops.dap_pack(a, 4, 8), v, mk, cfg, cfg, impl="jnp"
        )
    )
    # INT8 wire format (the paper's datapath): int8 values + bitmask +
    # scales, int32 accumulate, dequant fused in the epilogue
    wv8, wm8, ws8 = ops.pack_weight_int8(w, cfg)
    f_int8 = jax.jit(
        lambda a, v, mk, sc: ops.dbb_matmul_int8(a, v, mk, sc, cfg, impl="jnp")
    )
    f_int8_fused = jax.jit(
        lambda a, v, mk, sc, bb: ops.dbb_matmul_int8(
            a, v, mk, sc, cfg, impl="jnp", bias=bb, act="silu"
        )
    )
    tuned = maybe_autotune(x, wv, wm, cfg)
    tuned_i8 = maybe_autotune_int8(x, wv8, wm8, ws8, cfg)
    us_dense = _time(f_dense, x, w, n=reps)
    us_dbb = _time(f_dbb, x, wv, wm, n=reps)
    us_seed = _time(f_seed, x, wv, wm, n=reps)
    us_fused = _time(f_fused, x, wv, wm, b, n=reps)
    us_aw = _time(f_aw, x, wv, wm, n=reps)
    us_int8 = _time(f_int8, x, wv8, wm8, ws8, n=reps)
    us_int8_fused = _time(f_int8_fused, x, wv8, wm8, ws8, b, n=reps)
    dense_bytes = w.size * 4
    dense_bf16_bytes = w.size * 2
    packed_bytes = wv.size * 4 + wm.size
    int8_packed_bytes = wv8.size * 1 + wm8.size + ws8.size * 4
    rows = [
        {"impl": "dense", "us": round(us_dense, 1)},
        {"impl": "dbb_jnp", "us": round(us_dbb, 1)},
        {"impl": "dbb_jnp_seed_decode", "us": round(us_seed, 1)},
        {"impl": "dbb_jnp_fused_bias_silu", "us": round(us_fused, 1)},
        {"impl": "dbb_jnp_aw_packed_handoff", "us": round(us_aw, 1)},
        {"impl": "dbb_jnp_int8", "us": round(us_int8, 1)},
        {"impl": "dbb_int8_fused_epilogue", "us": round(us_int8_fused, 1)},
        {"decode_rewrite_speedup": round(us_seed / us_dbb, 2)},
        # bytes ratios vs the dense weights this bench actually allocates
        # (f32 on this host); int8_vs_bf16 is the serving-dtype view
        {"weight_bytes_ratio": round(dense_bytes / packed_bytes, 3)},
        {"int8_weight_bytes_ratio": round(dense_bytes / int8_packed_bytes, 3)},
        {
            "int8_vs_bf16_weight_bytes_ratio": round(
                dense_bf16_bytes / int8_packed_bytes, 3
            )
        },
        {"shape": [m, k, n], "cfg": str(cfg)},
    ]
    if tuned is not None:
        rows.append({"autotuned_tiles": list(tuned)})
    if tuned_i8 is not None:
        rows.append({"autotuned_tiles_int8": list(tuned_i8)})
    return rows, round(dense_bytes / int8_packed_bytes, 3)


def bench_kv_quant(smoke: bool = False):
    """The int8 KV cache's write/read helpers on a decode-shaped window:
    per-row quantize (write side) and dequantize (read side) of a
    [B*W, KVD] logical window — the per-step overhead the
    ``int8_kv_bytes_ratio`` buys (serve_bench has the end-to-end rows)."""
    from repro.core import quant

    rows_n = 4 * 64 if smoke else 16 * 512
    kvd = 1024
    reps = 2 if smoke else 5
    x = jnp.asarray(
        np.random.default_rng(3).normal(size=(rows_n, kvd)).astype(np.float32)
    )
    f_q = jax.jit(quant.quantize_rows)
    f_dq = jax.jit(lambda q, s: quant.dequantize_rows(q, s, dtype=jnp.float32))
    us_q = _time(f_q, x, n=reps)
    q, s = f_q(x)
    us_dq = _time(f_dq, q, s, n=reps)
    rows = [
        {"impl": "kv_quantize_rows", "us": round(us_q, 1)},
        {"impl": "kv_dequantize_rows", "us": round(us_dq, 1)},
        {"shape": [rows_n, kvd]},
    ]
    return rows, round(us_q + us_dq, 1)


def paged_attn_window_bytes(
    b: int, p: int, ps: int, dk: int, dv: int,
    wire_bytes: int, compute_bytes: int, n_scale_planes: int,
):
    """HBM bytes the attention *window* costs per paged step, both paths.

    Fused (in-kernel page-table walk): each request's pages stream from
    HBM exactly once, in wire format — values, per-token scale planes
    (int8 wire), and the slot-position words.

    Gather (``paged_read`` + ``mha``): the same page reads, PLUS the
    dense ``[B, P*ps, D]`` logical window materialized in compute dtype
    (one write) and read back by ``mha`` (one read) — the separate
    dequant pass under the int8 wire is part of that same window
    round-trip (dequantization happens into the materialized copy).

    Query/output tensors are identical on both paths and excluded.
    Returns ``(gather_bytes, fused_bytes)`` — an exact function of the
    layout, so the derived ratio is deterministic and gated
    (``benchmarks/compare.py`` TRACKED_RATIOS).
    """
    tokens = b * p * ps
    page_reads = tokens * (
        (dk + dv) * wire_bytes + n_scale_planes * 4 + 4  # values+scales+pos
    )
    window = tokens * ((dk + dv) * compute_bytes + 4)  # dense k/v + pos
    return page_reads + 2 * window, page_reads


def bench_paged_attn(smoke: bool = False):
    """Paged decode attention: gather (paged_read + mha) vs the fused
    page-table-walk formulation, plus the deterministic window-bytes
    ratios the fusion buys.

    µs rows time the **jnp forms** of both paths (the Pallas kernel
    targets TPU; ``ref.paged_attn_ref`` mirrors its online-softmax block
    tiling and is the timeable CPU proxy, exactly like the DBB rows).
    REPRO_AUTOTUNE=1 additionally times the two implementations against
    each other and caches the winner under the autotune ``paged_attn``
    kind (kernels/autotune.py).
    """
    from repro import configs
    from repro.kernels import ref as kref
    from repro.models import attention

    # timing shape: a small decode step (CPU-friendly)
    b, p_cnt, ps, kvh, dh = 4, 4, 16, 4, 64
    kvd = kvh * dh
    reps = 2 if smoke else 5
    rng = np.random.default_rng(5)
    cache = {
        "k": jnp.asarray(rng.normal(size=(b * p_cnt + 1, ps, kvd)).astype(np.float32)),
        "v": jnp.asarray(rng.normal(size=(b * p_cnt + 1, ps, kvd)).astype(np.float32)),
    }
    tables = jnp.asarray(
        np.arange(1, b * p_cnt + 1, dtype=np.int32).reshape(b, p_cnt)
    )
    pos = np.tile(np.arange(p_cnt * ps, dtype=np.int32), (b, 1))
    pos_tbl = attention.paged_update_pos(
        jnp.full((b * p_cnt + 1, ps), -1, jnp.int32), jnp.asarray(pos), tables
    )
    q = jnp.asarray(rng.normal(size=(b, 1, 2 * kvh, dh)).astype(np.float32))
    q_pos = jnp.full((b, 1), p_cnt * ps - 1, jnp.int32)

    def gather(k_pages, v_pages):
        c = {"k": k_pages, "v": v_pages}
        k_win, v_win, pos_win = attention.paged_read(
            c, pos_tbl, tables, dtype=jnp.float32
        )
        t = k_win.shape[1]
        return attention.mha(
            q, k_win.reshape(b, t, kvh, dh), v_win.reshape(b, t, kvh, dh),
            q_pos, pos_win, window=None, chunk=None,
        )

    def fused(k_pages, v_pages):
        return kref.paged_attn_ref(
            q, k_pages, v_pages, pos_tbl, tables, q_pos, kv_heads=kvh
        )

    f_gather = jax.jit(gather)
    f_fused = jax.jit(fused)
    us_gather = _time(f_gather, cache["k"], cache["v"], n=reps)
    us_fused = _time(f_fused, cache["k"], cache["v"], n=reps)

    if autotune.autotune_enabled():
        from repro.kernels.paged_attn import paged_attn_fused

        # jit BOTH candidates: an eager fused call would pay per-op
        # dispatch the jitted gather path doesn't, biasing the timing
        f_kernel = jax.jit(
            lambda k_pages, v_pages: paged_attn_fused(
                q, k_pages, v_pages, pos_tbl, tables, q_pos, kv_heads=kvh
            )
        )

        def run(impl):
            fn = f_gather if impl == "gather" else f_kernel
            return lambda: fn(cache["k"], cache["v"])

        sg = q.shape[1] * (q.shape[2] // kvh)  # query rows per kv head
        autotune.autotune_paged_attn(run, b, sg, ps, dh)

    # deterministic window-bytes ratios at the REAL model's kv_dim and a
    # serving-scale window (tiny timing rows would understate them)
    full = configs.get_config("granite_3_8b")
    kvd_full = full.kv_dim()
    shape = dict(b=8, p=32, ps=16, dk=kvd_full, dv=kvd_full)
    g_f32, f_f32 = paged_attn_window_bytes(
        **shape, wire_bytes=4, compute_bytes=4, n_scale_planes=0
    )
    g_i8, f_i8 = paged_attn_window_bytes(
        **shape, wire_bytes=1, compute_bytes=4, n_scale_planes=2
    )
    rows = [
        {"impl": "paged_attn_gather", "us": round(us_gather, 1)},
        {"impl": "paged_attn_fused", "us": round(us_fused, 1)},
        {"paged_attn_window_bytes_ratio": round(g_f32 / f_f32, 3)},
        {"paged_attn_window_bytes_ratio_int8": round(g_i8 / f_i8, 3)},
        {"shape": [b, p_cnt, ps, kvh, dh], "ratio_kv_dim": kvd_full},
    ]
    return rows, round(g_f32 / f_f32, 3)


def bench_dap_prune(smoke: bool = False):
    shape = (128, 1024) if smoke else (512, 4096)
    reps = 2 if smoke else 5
    x = jnp.asarray(
        np.random.default_rng(2).normal(size=shape).astype(np.float32)
    )
    f = jax.jit(lambda a: ops.dap_prune(a, 4, 8, impl="jnp"))
    us = _time(f, x, n=reps)
    f_pack = jax.jit(lambda a: ops.dap_pack(a, 4, 8))
    us_pack = _time(f_pack, x, n=reps)
    pruned, mask = f(x)
    density = float(jnp.mean((pruned != 0).astype(jnp.float32)))
    rows = [
        {"impl": "dap_prune", "us": round(us, 1), "post_density": round(density, 3)},
        {"impl": "dap_pack_fused", "us": round(us_pack, 1)},
    ]
    return rows, round(density, 3)
