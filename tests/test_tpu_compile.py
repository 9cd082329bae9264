"""Ahead-of-time compiles of the main-path Pallas kernels for a described
TPU v5e chip, at granite-3-8b's published widths — no chip needed.

Interpret-mode tests (test_kernels.py, test_paged_attn.py) never run
Mosaic, so they cannot see a block that breaks the (8, 128) tiling rule
or a primitive Mosaic does not lower; these compiles do, in about a
second each.  The kernel entry points are called with
``interpret=False`` (the CPU backend would otherwise pick the
interpreter).  The topology is described inside a module fixture — only
the worker that runs this file loads the TPU compiler — and the
persistent compilation cache is off around these compiles.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import dbb
from repro.kernels import autotune, dap_prune, dbb_matmul, paged_attn

# granite-3-8b (configs/granite_3_8b.py)
D_MODEL, D_FF, HEADS, KV_HEADS, HEAD_DIM = 4096, 12800, 32, 8, 128
KV_DIM = KV_HEADS * HEAD_DIM
PAGE = 16
MATMULS = [(D_MODEL, D_FF), (D_FF, D_MODEL), (D_MODEL, KV_DIM)]
M_DECODE, M_PREFILL = 8, 256
CFG = dbb.DBBConfig(4, 8)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text  # the Mosaic kernel, not a fallback


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("s", [1, 64], ids=["decode", "chunk64"])
def test_paged_attn_fused_compiles(one_chip, kv, s):
    b, p_cnt, n_pages = 8, 40, 321
    shapes = [
        ((b, s, HEADS, HEAD_DIM), jnp.bfloat16),
        ((n_pages, PAGE, KV_DIM), jnp.int8 if kv == "int8" else jnp.bfloat16),
        ((n_pages, PAGE, KV_DIM), jnp.int8 if kv == "int8" else jnp.bfloat16),
        ((n_pages, PAGE), jnp.int32),
        ((b, p_cnt), jnp.int32),
        ((b, s), jnp.int32),
    ]
    if kv == "int8":
        shapes += [((n_pages, PAGE), jnp.float32)] * 2

        def fn(q, k, v, pos, tbl, qp, ks, vs):
            return paged_attn.paged_attn_fused(
                q, k, v, pos, tbl, qp, kv_heads=KV_HEADS, k_scale=ks, v_scale=vs
            )
    else:

        def fn(q, k, v, pos, tbl, qp):
            return paged_attn.paged_attn_fused(
                q, k, v, pos, tbl, qp, kv_heads=KV_HEADS
            )

    _compile(one_chip, fn, *shapes)


@pytest.mark.parametrize("m", [M_DECODE, M_PREFILL], ids=["decode", "prefill"])
@pytest.mark.parametrize("k,n", MATMULS, ids=[f"{k}x{n}" for k, n in MATMULS])
def test_dbb_matmul_int8_compiles(one_chip, m, k, n):
    _compile(
        one_chip,
        lambda x, xs, wv, wm, ws: dbb_matmul.dbb_matmul_int8_pallas(
            x, xs, wv, wm, ws, cfg=CFG
        ),
        ((m, k), jnp.int8), ((m,), jnp.float32),
        ((k // 8, CFG.nnz, n), jnp.int8), ((k // 8, n), jnp.uint8),
        ((n,), jnp.float32),
    )


@pytest.mark.parametrize("m", [M_DECODE, M_PREFILL], ids=["decode", "prefill"])
@pytest.mark.parametrize("k,n", MATMULS, ids=[f"{k}x{n}" for k, n in MATMULS])
def test_dbb_matmul_aw_int8_compiles(one_chip, m, k, n):
    _compile(
        one_chip,
        lambda xv, xm, xs, wv, wm, ws: dbb_matmul.dbb_matmul_aw_int8_pallas(
            xv, xm, xs, wv, wm, ws, cfg_a=CFG, cfg_w=CFG
        ),
        ((m, k // 8, CFG.nnz), jnp.int8), ((m, k // 8), jnp.uint8),
        ((m,), jnp.float32),
        ((k // 8, CFG.nnz, n), jnp.int8), ((k // 8, n), jnp.uint8),
        ((n,), jnp.float32),
    )


@pytest.mark.parametrize("k,n", MATMULS, ids=[f"{k}x{n}" for k, n in MATMULS])
def test_dbb_matmul_bf16_compiles(one_chip, k, n):
    _compile(
        one_chip,
        lambda x, wv, wm: dbb_matmul.dbb_matmul_pallas(x, wv, wm, cfg=CFG),
        ((M_DECODE, k), jnp.bfloat16),
        ((k // 8, CFG.nnz, n), jnp.bfloat16), ((k // 8, n), jnp.uint8),
    )


@pytest.mark.parametrize("k,n", MATMULS, ids=[f"{k}x{n}" for k, n in MATMULS])
def test_dbb_matmul_aw_bf16_compiles(one_chip, k, n):
    _compile(
        one_chip,
        lambda xv, xm, wv, wm: dbb_matmul.dbb_matmul_aw_pallas(
            xv, xm, wv, wm, cfg_a=CFG, cfg_w=CFG
        ),
        ((M_DECODE, k // 8, CFG.nnz), jnp.bfloat16), ((M_DECODE, k // 8), jnp.uint8),
        ((k // 8, CFG.nnz, n), jnp.bfloat16), ((k // 8, n), jnp.uint8),
    )


@pytest.mark.parametrize("k", [D_MODEL, D_FF])
def test_dap_prune_compiles(one_chip, k):
    _compile(
        one_chip,
        lambda x: dap_prune.dap_prune_pallas(x, nnz=4),
        ((M_DECODE, k), jnp.bfloat16),
    )


@pytest.mark.parametrize("m", [M_DECODE, M_PREFILL, 200])
@pytest.mark.parametrize("k,n", MATMULS + [(64, 128), (40, 128)])
@pytest.mark.parametrize("int8", [False, True])
def test_heuristic_tiles_are_legal_blocks(m, k, n, int8):
    """Every heuristic tile divides its axis and is a multiple of 128 or
    the whole axis (lane dims: TK, TN, and TM for the transposed
    activation tile), with TK in whole 8-blocks."""
    tm, tk, tn = autotune.heuristic_tiles(m, k, n, 8, int8=int8)
    for t, dim in ((tm, m), (tk, k), (tn, n)):
        assert dim % t == 0 and (t % 128 == 0 or t == dim), (t, dim)
    assert tk % 8 == 0
