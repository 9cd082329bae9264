"""Serving launcher: continuous int8-DBB serving of one architecture.

    PYTHONPATH=src python -m repro.launch.serve --arch granite_3_8b
    PYTHONPATH=src python -m repro.launch.serve --arch granite_3_8b --smoke

Without ``--smoke`` the model is the published configuration at full
width and depth (granite-3-8b: 40 layers, d_model 4096, d_ff 12800 —
one v5e chip holds it only on the int8 wire); ``--smoke`` takes the
reduced same-family config, which runs on a CPU.  Weights are random,
from ``--seed``, and are packed layer by layer
(``engine.init_serving_params``), so the raw weight stack never exists.

The run is the README's documented serving config (:data:`SERVE_CONFIG`:
continuous batching over the paged KV cache, int8 4/8 DBB weights and
activations, int8 KV, seeded sampling at temperature 0.7) driven through
``Engine.serve_requests``: a few requests of mixed prompt lengths with
staggered arrivals, so chunked prefill, mixed prefill+decode steps and
fused decode runs all execute.  ``chip_smoke.py`` calls :func:`serve`.
"""

from __future__ import annotations

import argparse
import dataclasses
import logging
import time
from typing import List, Sequence

import numpy as np

from repro import configs
from repro.serve.engine import Engine, RequestResult, ServeConfig, init_serving_params

logger = logging.getLogger(__name__)

# README.md's documented serving config ("Serving in three lines")
SERVE_CONFIG = dict(
    prefill_mode="continuous",
    pack_weights=True,
    wire_dtype="int8",
    kv_dtype="int8",
    temperature=0.7,
)
# pool and step shape: four concurrent requests, 64-token prefill chunks
# (a mixed step is [4, 64] tokens), 16-token KV pages
MAX_BATCH, PREFILL_CHUNK, PAGE_SIZE = 4, 64, 16
ARRIVAL_GAP = 3  # scheduler iterations between request arrivals


@dataclasses.dataclass
class ServeRun:
    """One launcher run: the engine, what it was asked, what it served."""

    cfg: object  # the model config (configs.base.ModelConfig)
    engine: Engine
    prompts: List[np.ndarray]
    arrivals: List[int]  # scheduler iterations
    n_new: int
    results: List[RequestResult]
    weight_bytes: int  # packed serving weights held by the engine
    build_s: float  # weight generation + packing
    compile_s: float  # warm-up request: compiles both serving traces
    wall_s: float  # the requests themselves

    @property
    def tokens_served(self) -> int:
        return sum(r.n_generated for r in self.results)


def make_prompts(vocab: int, lens: Sequence[int], seed: int) -> List[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).astype(np.int32) for n in lens]


def serve(
    arch: str = "granite_3_8b",
    *,
    smoke: bool = False,
    seed: int = 0,
    prompt_lens: Sequence[int] = (64, 512, 192, 320),
    n_new: int = 32,
) -> ServeRun:
    """Build the packed engine for ``arch`` and serve one set of requests.

    A warm-up request shorter than a page (so the prefix cache keeps
    nothing of it) runs first: one mixed step and one fused decode run,
    the two traces the continuous loop ever compiles.  Its time is
    reported as ``compile_s``, apart from the served requests' ``wall_s``.
    """
    import jax

    cfg = configs.get_config(arch, smoke=smoke)
    t0 = time.perf_counter()
    params = init_serving_params(
        cfg, jax.random.PRNGKey(seed), SERVE_CONFIG["wire_dtype"]
    )
    jax.block_until_ready(params)
    build_s = time.perf_counter() - t0
    weight_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    logger.info("%s: %d packed weight bytes built in %.1f s",
                cfg.name, weight_bytes, build_s)

    scfg = ServeConfig(
        **SERVE_CONFIG,
        seed=seed,
        max_seq=max(prompt_lens) + n_new,
        max_batch=MAX_BATCH,
        prefill_chunk=PREFILL_CHUNK,
        page_size=PAGE_SIZE,
    )
    engine = Engine(params, cfg, scfg)
    del params  # the engine holds the only reference

    warm = make_prompts(cfg.vocab, [PAGE_SIZE - 1], seed + 1)
    t0 = time.perf_counter()
    engine.serve_requests(warm, 2)
    compile_s = time.perf_counter() - t0
    logger.info("warm-up request (compiles) took %.1f s", compile_s)

    prompts = make_prompts(cfg.vocab, prompt_lens, seed)
    arrivals = [i * ARRIVAL_GAP for i in range(len(prompts))]
    t0 = time.perf_counter()
    results = engine.serve_requests(prompts, n_new, arrivals=arrivals)
    wall_s = time.perf_counter() - t0
    return ServeRun(
        cfg=cfg, engine=engine, prompts=prompts, arrivals=arrivals,
        n_new=n_new, results=results, weight_bytes=weight_bytes,
        build_s=build_s, compile_s=compile_s, wall_s=wall_s,
    )


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--arch", default="granite_3_8b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (default: published widths)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prompt-lens", default="64,512,192,320",
                    help="comma-separated prompt lengths, one request each")
    ap.add_argument("--gen", type=int, default=32, help="new tokens per request")
    args = ap.parse_args(argv)

    from repro.runtime import compile_cache, precision

    precision.exact_rounding()  # batch invariance on a TPU
    compile_cache.enable()
    run = serve(
        args.arch, smoke=args.smoke, seed=args.seed, n_new=args.gen,
        prompt_lens=[int(n) for n in args.prompt_lens.split(",")],
    )
    print(f"arch={run.cfg.name} layers={run.cfg.n_layers} "
          f"d_model={run.cfg.d_model} d_ff={run.cfg.d_ff}")
    print(f"weight_bytes={run.weight_bytes} build_s={run.build_s:.3f} "
          f"compile_s={run.compile_s:.3f} wall_s={run.wall_s:.3f}")
    print(f"tokens_served={run.tokens_served} "
          f"paged_compiles={run.engine.paged_compiles} "
          f"finish={[r.finish_reason for r in run.results]}")


if __name__ == "__main__":
    main()
