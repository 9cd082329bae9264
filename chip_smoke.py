"""Chip smoke: serve granite-3-8b at its published widths on one TPU chip.

    python chip_smoke.py [--seed N]

Drives the serving launcher's own function (``repro.launch.serve.serve``):
random weights from ``--seed``, packed layer by layer onto the int8 4/8
DBB wire (40 layers, d_model 4096, d_ff 12800, int8 KV), then four
requests of mixed prompt lengths with staggered arrivals through
continuous batching.  Prints what ran and what it cost, then two checks,
each of which fails the run:

1. batch invariance — each request served alone on the same engine gives
   the same tokens as when it was co-batched; ids are in the vocab and
   no request was quarantined;
2. kernels — every Pallas kernel, compiled, at granite decode shapes
   against its jnp oracle (``repro.launch.checks``).

It also fails unless the serving loop compiled exactly two traces, never
fell back from the fused paged-attention kernel, and resolved that kernel
compiled.  One process; it starts none.  It appends
``--xla_allow_excess_precision=false`` to ``XLA_FLAGS``
(``repro.runtime.precision``: without it the TPU compiler rounds
differently in the two serving programs and batch invariance fails) and
leaves the machine's ``LIBTPU_INIT_ARGS`` as it is.  With no TPU it exits non-zero
before any work (it never falls back to the CPU).  The last line of
standard output is the JSON result, and only a run that passed prints it.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys


def main() -> int:
    ap = argparse.ArgumentParser(description="serve granite-3-8b on one TPU chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    sys.stdout.reconfigure(line_buffering=True)  # progress survives a kill
    logging.basicConfig(stream=sys.stdout, format="%(asctime)s %(message)s")
    logging.getLogger("repro.launch").setLevel(logging.INFO)
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
    from repro.runtime import precision

    xla_flags = precision.exact_rounding()  # before JAX creates a backend

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {dev.platform!r})", file=sys.stderr)
        return 1
    # tiles and kernel choices come from the in-repo heuristics, never
    # from an autotune file outside the tree
    os.environ.pop("REPRO_AUTOTUNE_CACHE", None)
    os.environ.pop("REPRO_AUTOTUNE", None)
    from repro.launch import checks, serve
    from repro.runtime import compile_cache

    ndev = len(jax.devices())
    print(f"device: kind={dev.device_kind} count={ndev}")
    print(f"XLA_FLAGS: {xla_flags}")
    print(f"compile cache: {compile_cache.enable()}")

    run = serve.serve("granite_3_8b", smoke=False, seed=args.seed)
    eng, cfg = run.engine, run.cfg
    stats = dev.memory_stats() or {}
    peak, limit = stats.get("peak_bytes_in_use"), stats.get("bytes_limit")
    impl = eng.paged_attn_impl()
    compiled = jax.default_backend() == "tpu"  # paged_attn_cache_layer's rule
    health = eng.health()
    print(f"model: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"d_ff={cfg.d_ff} heads={cfg.n_heads}/{cfg.n_kv_heads} vocab={cfg.vocab} "
          f"wire={eng.scfg.wire_dtype} kv={eng.scfg.kv_dtype}")
    print(f"weights: packed_bytes_on_device={run.weight_bytes} build_s={run.build_s:.3f}")
    print(f"hbm: peak_bytes_in_use={peak} bytes_limit={limit}")
    print(f"serve: compile_s={run.compile_s:.3f} wall_s={run.wall_s:.3f} "
          f"tokens_served={run.tokens_served} requests={len(run.results)} "
          f"prompt_lens={[int(p.shape[0]) for p in run.prompts]} "
          f"arrivals={run.arrivals}")
    print(f"serve: paged_compiles={eng.paged_compiles} "
          f"fused_fallbacks={health['fused_fallbacks']} "
          f"paged_attn={impl} compiled={compiled}")

    failures = []
    if eng.paged_compiles != 2:
        failures.append(f"paged_compiles={eng.paged_compiles}, expected 2")
    if health["fused_fallbacks"] != 0:
        failures.append(f"fused_fallbacks={health['fused_fallbacks']}, expected 0")
    if impl != "fused" or not compiled:
        failures.append(f"paged attention resolved to {impl} (compiled={compiled})")
    if peak is None or limit is None or peak > limit:
        failures.append(f"peak HBM {peak} not within bytes_limit {limit}")

    for phase, check in (
        ("batch invariance", lambda: checks.batch_invariance(run)),
        ("kernels", lambda: checks.kernel_phase(cfg, interpret=False)),
    ):
        for name, ok, detail in check():
            print(f"check {phase}: {'PASS' if ok else 'FAIL'} {name}: {detail}")
            if not ok:
                failures.append(f"{phase}: {name}")

    if failures:
        for f in failures:
            print(f"chip_smoke: FAILED {f}", file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": ndev},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
