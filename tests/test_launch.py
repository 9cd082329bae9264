"""The serving launcher and what it builds on, at a small size on the CPU.

* ``engine.init_serving_params`` — packed weights built layer by layer —
  equals packing the whole ``lm.init_lm`` tree, leaf for leaf;
* the engine takes weights packed for its wire and refuses any other
  packed tree, and never keeps a raw copy;
* ``launch.serve.serve`` at the smoke config: the run ``chip_smoke.py``
  makes on the chip, with both of its checks (batch invariance, every
  kernel against its oracle — here through the Pallas interpreter);
* ``runtime.compile_cache.enable`` follows ``JAX_COMPILATION_CACHE_DIR``
  or a fixed directory in the checkout (the tests never turn it on), and
  ``runtime.precision.exact_rounding`` appends to ``XLA_FLAGS``.
"""

import dataclasses
import os
import pathlib

import jax
import numpy as np
import pytest

from repro import configs
from repro.launch import checks, serve
from repro.models import lm
from repro.runtime import compile_cache, precision
from repro.serve.engine import (
    Engine,
    ServeConfig,
    SpecConfig,
    init_serving_params,
    pack_params_for_serving,
)

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("arch", ["granite_3_8b", "minicpm3_4b"])
@pytest.mark.parametrize("wire", ["native", "int8"])
def test_layerwise_serving_params_equal_packed_init(arch, wire):
    cfg = configs.get_config(arch, smoke=True)
    key = jax.random.PRNGKey(3)
    want = pack_params_for_serving(lm.init_lm(cfg, key)[0], cfg, wire)
    got = init_serving_params(cfg, key, wire)
    want_l, want_t = jax.tree_util.tree_flatten(want)
    got_l, got_t = jax.tree_util.tree_flatten(got)
    assert got_t == want_t
    for a, b in zip(got_l, want_l):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _small():
    cfg = configs.get_config("granite_3_8b", smoke=True)
    return dataclasses.replace(cfg, vocab=64, d_model=64, d_ff=128, dtype="float32")


def test_engine_serves_prepacked_weights_like_raw():
    cfg = _small()
    key = jax.random.PRNGKey(0)
    scfg = ServeConfig(
        prefill_mode="continuous", pack_weights=True, wire_dtype="int8",
        kv_dtype="int8", max_seq=32, page_size=8, max_batch=2, prefill_chunk=4,
    )
    prompts = serve.make_prompts(cfg.vocab, (9, 5), seed=1)
    raw = Engine(lm.init_lm(cfg, key)[0], cfg, scfg)
    packed = Engine(init_serving_params(cfg, key, "int8"), cfg, scfg)
    assert not hasattr(packed, "_raw_params")
    for a, b in zip(raw.generate_requests(prompts, 5), packed.generate_requests(prompts, 5)):
        np.testing.assert_array_equal(a, b)


def test_engine_refuses_packed_weights_for_another_wire():
    cfg = _small()
    native = init_serving_params(cfg, jax.random.PRNGKey(0), "native")
    with pytest.raises(ValueError, match="already packed"):
        Engine(native, cfg, ServeConfig(pack_weights=True, wire_dtype="int8"))
    with pytest.raises(ValueError, match="already packed"):
        Engine(native, cfg, ServeConfig())  # pack_weights=False
    # the int8_wire draft packs raw weights; it cannot take packed ones
    with pytest.raises(ValueError, match="int8_wire"):
        Engine(native, cfg, ServeConfig(
            prefill_mode="continuous", pack_weights=True,
            spec=SpecConfig(draft="int8_wire"),
        ))


@pytest.fixture(scope="module")
def smoke_run():
    return serve.serve(smoke=True, prompt_lens=(20, 70, 33), n_new=6)


def test_launcher_smoke_run(smoke_run):
    run = smoke_run
    assert [r.finish_reason for r in run.results] == ["length"] * 3
    assert run.tokens_served == 3 * 6
    assert run.engine.paged_compiles == 2
    assert run.engine.health()["fused_fallbacks"] == 0
    assert run.weight_bytes == sum(
        x.nbytes for x in jax.tree_util.tree_leaves(run.engine.params)
    )
    # the CPU resolves the gather path; a TPU resolves the fused kernel
    assert run.engine.paged_attn_impl() == (
        "fused" if jax.default_backend() == "tpu" else "gather"
    )


def test_launcher_batch_invariance_check(smoke_run):
    rows = list(checks.batch_invariance(smoke_run))
    assert len(rows) == 3
    assert all(ok for _, ok, _ in rows), rows


def test_kernel_phase_interpret():
    rows = list(checks.kernel_phase(
        configs.get_config("granite_3_8b", smoke=True), interpret=True
    ))
    assert len(rows) == 3 * 2 * 4 + 2 * 2 + 2
    assert all(ok for _, ok, _ in rows), [r for r in rows if not r[1]]


def test_launcher_cli_smoke(monkeypatch, capsys):
    monkeypatch.setattr(compile_cache, "enable", lambda: "off")  # tests never cache
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", ""))  # restored after
    serve.main(["--smoke", "--prompt-lens", "9,17", "--gen", "3"])
    out = capsys.readouterr().out
    assert "arch=granite-3-8b-smoke" in out
    assert "tokens_served=6 paged_compiles=2" in out


def test_compile_cache_follows_env(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/cache")
    assert compile_cache.enable() == "/somewhere/cache"
    assert calls == []  # JAX reads the variable itself
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.enable() == str(REPO / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", str(REPO / ".jax_cache"))]
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_exact_rounding_appends_to_xla_flags(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=/tmp/x")
    assert precision.exact_rounding() == f"--xla_dump_to=/tmp/x {precision.FLAG}"
    assert precision.exact_rounding() == os.environ["XLA_FLAGS"]  # idempotent
    monkeypatch.delenv("XLA_FLAGS")
    assert precision.exact_rounding() == precision.FLAG
