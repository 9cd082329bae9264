"""Compile a cell's programs for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python3 bench/aot_check.py <cell> [<cell> ...]

For each cell it builds the shapes the engine would hold (packed int8
DBB weights from ``jax.eval_shape`` of ``engine.init_serving_params``,
the paged cache at the cell's page pool) and compiles, for one chip of a
described ``v5e:2x2``, the two
programs of the continuous loop: the mixed step (``lm.paged_step`` at
``[max_batch, prefill_chunk]``) and the fused decode loop
(``lm.paged_decode_loop`` at ``[max_batch, 1]`` for ``decode_block``
steps).  It prints each program's compile seconds and
``memory_analysis()``.  Nothing runs, so it gives neither times nor
results; what it catches is a program the chip's compiler refuses or
that does not fit.  Not a test: run it by hand before a cell's first
chip run.
"""

from __future__ import annotations

import dataclasses
import os
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def gib(n) -> str:
    return f"{n / 2**30:.3f} GiB"


def report(name, compiled, seconds):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"  {name}: compiled in {seconds:.1f} s; arguments "
          f"{gib(m.argument_size_in_bytes)}, outputs {gib(m.output_size_in_bytes)}, "
          f"aliased {gib(m.alias_size_in_bytes)}, temporaries "
          f"{gib(m.temp_size_in_bytes)}; live at once <= {gib(total)}")
    return total


def check_cell(name: str, one) -> None:
    import jax
    import jax.numpy as jnp

    from bench import cells
    from bench.run import model_config
    from repro.models import lm
    from repro.serve import engine as eng_mod
    from repro.serve import paged_cache
    from repro.serve.scheduler import Scheduler

    cell = cells.resolve(name)
    cfg = model_config(cell)
    serve = {**cell.config["serve"], **cell.mix.get("serve", {})}
    scfg = eng_mod.ServeConfig(**serve)
    sp = dataclasses.replace(
        cfg.sparsity, act_scale="per_row", kv_dtype=scfg.kv_dtype,
        paged_attn="fused",
    )
    cfg = dataclasses.replace(cfg, sparsity=sp)  # the engine's effective cfg
    print(f"{name}: {cfg.name} max_batch={scfg.max_batch} "
          f"chunk={scfg.prefill_chunk} max_seq={scfg.max_seq} "
          f"pages={scfg.total_pages}x{scfg.page_size}")

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), tree
        )

    params = sds(jax.eval_shape(
        lambda k: eng_mod.init_serving_params(cfg, k, scfg.wire_dtype),
        jax.random.PRNGKey(0),
    ))
    cache = sds(jax.eval_shape(
        lambda: paged_cache.make_paged_cache(cfg, scfg.total_pages, scfg.page_size)
    ))
    sched = Scheduler(
        max_batch=scfg.max_batch, page_size=scfg.page_size,
        n_pages=scfg.total_pages, max_pages_per_req=scfg.pages_per_request,
        prefill_chunk=scfg.prefill_chunk, decode_block=scfg.decode_block,
    )
    i32, f32, u32 = jnp.int32, jnp.float32, jnp.uint32
    b, c, p = scfg.max_batch, scfg.prefill_chunk, scfg.pages_per_request

    def arr(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    t = time.perf_counter()
    mixed = jax.jit(
        lambda pr, ca, tk, pos, tbl, scrub, cow: lm.paged_step(
            pr, ca, tk, pos, tbl, cfg, scrub_pages=scrub, cow_pages=cow),
        donate_argnums=(),
    ).lower(params, cache, arr((b, c), i32), arr((b, c), i32), arr((b, p), i32),
            arr((sched.scrub_width,), i32), arr((sched.cow_width, 2), i32)).compile()
    report("mixed step", mixed, time.perf_counter() - t)
    assert "tpu_custom_call" in mixed.as_text(), "no Pallas kernel in the mixed step"
    t = time.perf_counter()
    run = jax.jit(
        lambda pr, ca, tk, pos, tbl, scrub, cow, st, sk, sp_, ss, n:
        lm.paged_decode_loop(
            pr, ca, tk, pos, tbl, n, cfg, max_steps=scfg.decode_block,
            scrub_pages=scrub, cow_pages=cow, sampling=(st, sk, sp_, ss)),
    ).lower(params, cache, arr((b, 1), i32), arr((b,), i32), arr((b, p), i32),
            arr((sched.run_scrub_width,), i32), arr((sched.cow_width, 2), i32),
            arr((b,), f32), arr((b,), i32), arr((b,), f32), arr((b,), u32),
            arr((), i32)).compile()
    report("decode loop", run, time.perf_counter() - t)
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params))
    kv = sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(cache))
    print(f"  packed weights {gib(weights)}, paged cache {gib(kv)}")


def main(argv=None) -> int:
    names = (argv if argv is not None else sys.argv[1:])
    if not names:
        print(__doc__)
        return 2
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.default_backend = lambda: "tpu"  # kernels take their compiled path
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    for name in names:
        check_cell(name, one)
    return 0


if __name__ == "__main__":
    sys.exit(main())
