"""The plain reference against the program at smoke sizes, both archs.

The reference makes its weights from the seed itself; here the program
builds its own from the same seed and runs its decoder layers through
the paged cache (the fused paged-attention kernel, interpreted on the
CPU) and its head.  Each layer gets the program's input on both sides:
the reference's output lies within a tenth or two of the program's (its
float32 attention rounds otherwise, and DAP's top-4 choice turns a
rounding into a different pruning), the 4-bit control's several times
farther.  On the logits the program's tokens are the reference's
argmax at the median position; the control's are not.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import model as ref
from bench.tests.conftest import smoke_config

SEED = 2**31 + 99


@pytest.mark.parametrize("name", ["granite-3-8b", "minicpm3-4b"])
def test_reference_matches_program_layer_by_layer(name):
    from repro import configs
    from repro.models import attention, blocks, lm
    from repro.serve import engine, paged_cache

    conf = smoke_config(name)
    cfg = configs.get_config(conf["arch"], smoke=True)
    sp = dataclasses.replace(cfg.sparsity, act_scale="per_row", kv_dtype="int8",
                             paged_attn="fused")
    cfg = dataclasses.replace(cfg, sparsity=sp)  # as Engine serves on a TPU
    params = engine.init_serving_params(cfg, ref.model_key(SEED), "int8")
    arch = ref.Arch.from_json(conf["model"])

    t = 40
    tokens = np.random.default_rng(0).integers(0, cfg.vocab, (1, t)).astype(np.int32)
    pos = jnp.arange(t, dtype=jnp.int32)[None]
    cache = paged_cache.make_paged_cache(cfg, 8, 16)
    table = jnp.asarray([[1, 2, 3, 0]], jnp.int32)
    pos_tbl = attention.paged_update_pos(cache["pos"], pos, table)
    rope_cs = None if cfg.mla is not None else lm._rope_cs(cfg, pos)

    k_embed, k_layers, k_head = jax.random.split(ref.model_key(SEED), 3)
    layer_keys = jax.random.split(k_layers, arch.n_layers)
    wq = jnp.float32(ref.qmax_of(8))
    padded = np.zeros((1, ref.SEQ_MULTIPLE), np.int32)
    padded[:, :t] = tokens
    pos_r = jnp.arange(ref.SEQ_MULTIPLE, dtype=jnp.int32)[None]

    def rel(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return np.linalg.norm(a - b) / np.linalg.norm(b)

    x_p = jnp.take(params["embed"]["w"], jnp.asarray(tokens), axis=0)
    x_r = ref._embed_jit(arch, k_embed, jnp.asarray(padded))
    assert np.array_equal(np.asarray(x_p, np.float32), np.asarray(x_r[:, :t], np.float32))
    for i in range(arch.n_layers):
        lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
        kv = {k: v[i] for k, v in cache.items() if k != "pos"}
        x_in = jnp.pad(x_p, ((0, 0), (0, ref.SEQ_MULTIPLE - t), (0, 0)))
        x_r = ref.layer(arch, layer_keys[i], x_in, pos_r, ref.Precision(), wq)[:, :t]
        x_c = ref.layer(arch, layer_keys[i], x_in, pos_r, ref.Precision(w_bits=4),
                        jnp.float32(ref.qmax_of(4)))[:, :t]
        x_p, _, _ = blocks.decoder_block(
            lp, x_p, cfg, pos, cache_layer={**kv, "pos": pos_tbl},
            page_tables=table, rope_cs=rope_cs)
        assert rel(x_p, x_r) <= 0.2, f"layer {i}"
        assert rel(x_c, x_r) >= 2 * rel(x_p, x_r), f"layer {i}"
    logits_p = np.asarray(lm._head(params, x_p, cfg)[0, :, : cfg.vocab], np.float32)
    rows = np.arange(t)
    logits_r = np.asarray(ref.forward_logits(arch, SEED, padded, rows))
    logits_c = np.asarray(ref.forward_logits(arch, SEED, padded, rows, ref.Precision(w_bits=4)))

    def median_rank(logits):
        chosen = logits_r[rows, logits.argmax(-1)]
        return np.median(np.sum(logits_r > chosen[:, None], axis=-1))

    assert median_rank(logits_p) == 0
    assert median_rank(logits_c) >= 1
