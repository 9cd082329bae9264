"""Serving-throughput microbenchmark: continuous batching (paged KV,
chunked-prefill interleaving, fused decode runs) vs the one-shot
batched-prefill engine on identical request sets, plus the int8 KV cache
and shared-prefix page-caching rows.

Times whole ``generate`` calls (host scheduling + jitted steps) on a tiny
CPU config after a warmup pass per engine, and reports tokens/s plus the
continuous-vs-oneshot ratio.  The continuous row also splits COLD wall
time (first call: jit tracing + compiles) from the warm timed number and
records ``paged_compiles`` — the bucketed plan shapes keep the whole
continuous loop at exactly two compiled traces (one mixed step + one
fused decode loop), which is what the warm timings rely on.
``continuous_vs_oneshot_throughput`` is timing-derived but gated with a
loose tolerance in benchmarks/compare.py (TRACKED_RATIOS): the fused
decode loop is the difference between ~0.4 and ~1.0 on this workload,
and a silent fallback to per-token dispatch must fail CI.

Prefix-caching rows: a shared-system-prompt workload (identical 32-token
prefix, distinct tails) runs twice through one engine; ``prefix_hit_rate``
and ``prefill_tokens_saved_ratio`` report the page-granularity hit rate
and the fraction of prompt tokens whose prefill FLOPs were skipped
(docs/serving.md).  ``python -m benchmarks.serve_bench --check-prefix``
re-reads BENCH_kernels.json and fails if the rows are missing or zero —
the CI smoke gate for the prefix cache.

INT8 KV rows: ``int8_kv_bytes_ratio`` is the deterministic paged-cache
byte shrink vs f32 KV storage (~4x; int8 values + one f32 scale per
token row — gated like the other wire-format ratios), and the
``serve_decode_step_{f32,int8}_kv`` µs rows time one warm jitted decode
step under each KV wire (the dequant-at-read overhead the ratio buys).

Sampled-decode row: ``serve_continuous_sampled`` runs the same workload
with ``temperature=0.7`` — every in-loop sample routes through the
seeded categorical sampler (core/sampling.py) instead of the argmax
fast path — and ``sampled_vs_greedy_throughput`` (timing-derived, loose
tolerance in compare.py) tracks its cost.  ``python -m
benchmarks.serve_bench --check-sampling`` is the live CI smoke: fused
sampled bytes == stepped sampled bytes, sampled output actually
diverges from greedy, greedy bytes unchanged by the sampler, stop
tokens fire, and the 2-trace compile budget holds with sampling fused
in-loop.

Speculative-decode row: ``serve_spec_decode`` serves the greedy workload
through a spec-enabled engine (2/8-tightened draft proposing inside each
fused window, one multi-token verify — docs/serving.md "Speculative
decoding") and reports ``acceptance_rate`` plus
``spec_vs_plain_throughput`` (both tracked in compare.py).  ``python -m
benchmarks.serve_bench --check-spec`` is the live CI smoke for the
byte-exactness contract.

Durability rows (docs/serving.md "Durability"): ``serve_snapshot_save``
/ ``serve_snapshot_load`` time one crash-consistent engine snapshot
publish and one warm in-place reload (µs rows; the one-off cold
``Engine.restore`` wall — re-jit + re-pack — rides along ungated), and
``snapshot_bytes_ratio`` is the deterministic on-disk shrink an int8 KV
cache buys the snapshot itself (tracked tight in compare.py).  The
``serve_latency`` row reports queueing/TTFT percentiles from the
:class:`RequestResult` latency fields.  ``python -m
benchmarks.serve_bench --check-restore`` is the live CI smoke:
SIGKILL-simulated crashes at an iteration boundary and mid-save must
restore from the last published snapshot and finish byte-identical,
with no-dup/no-gap streaming and zero leaked pages.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np


def _time_once(fn, passes=3):
    """Best-of-``passes`` wall seconds (engines are warm: jit cached)."""
    best = float("inf")
    for _ in range(passes):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _time_step(fn, passes=3, n=5):
    """Best-of-``passes`` mean wall µs of a jitted step (warm)."""
    best = float("inf")
    for _ in range(passes):
        t0 = time.perf_counter()
        for _ in range(n):
            jax.block_until_ready(fn())
        best = min(best, (time.perf_counter() - t0) / n * 1e6)
    return best


def bench_kv_cache(cfg, params, passes):
    """INT8 KV cache rows: deterministic bytes ratio + decode-step µs."""
    import dataclasses as dc

    import jax.numpy as jnp
    import numpy as np

    from repro.models import lm
    from repro.serve import paged_cache

    def kv_bytes(c):
        # pos is identical bookkeeping under both wires: exclude it so
        # the ratio reflects the K/V payload the wire actually changes
        return paged_cache.cache_nbytes({n: c[n] for n in c if n != "pos"})

    # bytes ratio at the REAL model's kv_dim (eval_shape: no allocation)
    # — the tiny timing config's 64-wide rows would understate the
    # asymptotic 4D/(D+4) shrink the wire delivers at serving width
    from repro import configs

    full = dc.replace(configs.get_config("granite_3_8b"), dtype="float32")
    full8 = dc.replace(
        full, sparsity=dc.replace(full.sparsity, kv_dtype="int8")
    )
    cache_f = jax.eval_shape(lambda: paged_cache.make_paged_cache(full, 17, 16))
    cache_8 = jax.eval_shape(lambda: paged_cache.make_paged_cache(full8, 17, 16))
    ratio = kv_bytes(cache_f) / kv_bytes(cache_8)

    cfg8 = dc.replace(
        cfg, sparsity=dc.replace(cfg.sparsity, kv_dtype="int8")
    )

    rows = [{"int8_kv_bytes_ratio": round(ratio, 3)}]
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab, (4, 1)).astype(np.int32)
    )
    for label, c in (("f32", cfg), ("int8", cfg8)):
        step = jax.jit(lambda p, ca, t, pos, _c=c: lm.decode_step(p, ca, t, pos, _c))
        cache = lm.make_cache(c, 4, 64)
        jax.block_until_ready(step(params, cache, toks, jnp.int32(8)))  # warm
        us = _time_step(
            lambda: step(params, cache, toks, jnp.int32(8))[0], passes
        )
        rows.append(
            {"impl": f"serve_decode_step_{label}_kv", "us": round(us, 1)}
        )
    return rows, round(ratio, 3)


def bench_prefix_cache(params, cfg, b):
    """Shared-system-prompt workload through a persistent prefix cache.

    Four requests share an identical 32-token prefix (two full 16-token
    pages) with distinct 8-token tails; the same engine serves two such
    calls, so the second call's prompts hit the pages the first call
    registered.  Returns the ``prefix_hit_rate`` /
    ``prefill_tokens_saved_ratio`` rows (page-granularity stats counted
    at admission — serve/paged_cache.PrefixCache)."""
    from repro.serve.engine import Engine, ServeConfig

    rng = np.random.default_rng(7)
    system = rng.integers(0, cfg.vocab, (32,)).astype(np.int32)
    eng = Engine(params, cfg, ServeConfig(
        prefill_mode="continuous", max_seq=64, page_size=16,
        max_batch=b, prefill_chunk=8, prefix_cache=True,
    ))
    for _ in range(2):
        tails = rng.integers(0, cfg.vocab, (b, 8)).astype(np.int32)
        prompts = [np.concatenate([system, tails[i]]) for i in range(b)]
        eng.generate_requests(prompts, 8)
    stats = eng.prefix_stats()
    return [
        {"prefix_hit_rate": round(stats["hit_rate"], 3),
         "prefill_tokens_saved_ratio": round(stats["tokens_saved_ratio"], 3),
         "prefix_pages_hit": stats["page_hits"],
         "prefill_tokens_saved": stats["prefill_tokens_saved"]},
    ]


def bench_overload(params, cfg, passes):
    """2x pool-oversubscribed workload through the robust serving API.

    Six requests whose lifetime page needs are twice the pool's capacity
    arrive at once: the scheduler must queue, age, preempt-and-recompute
    — and still complete every request (typed outcomes, no exceptions).
    ``overload_completion_ratio`` = completed / submitted is an exact
    property of the robustness machinery (gated at 1.0 in
    benchmarks/compare.py TRACKED_RATIOS); preemption and queue counters
    ride along for the trajectory."""
    from repro.serve.engine import Engine, ServeConfig

    rng = np.random.default_rng(5)
    prompts = [
        rng.integers(0, cfg.vocab, (s,)).astype(np.int32)
        for s in (16, 12, 18, 14, 16, 13)
    ]
    n_new = 16
    # each request needs 2-3 pages of 16 for prompt+16 new tokens
    # (~13 data pages total); 7 pages incl. the null page is ~2x
    # oversubscribed, so at most two requests ever coexist
    eng = Engine(params, cfg, ServeConfig(
        prefill_mode="continuous", max_seq=64, page_size=16,
        max_batch=4, max_pages=7, prefill_chunk=8,
        prefix_cache=False, preempt_after=2,
    ))
    results = eng.serve_requests(prompts, n_new)  # warmup/compile
    s = _time_once(lambda: eng.serve_requests(prompts, n_new), passes)
    results = eng.serve_requests(prompts, n_new)
    completed = sum(r.ok for r in results)
    health = eng.health()
    tok = sum(r.n_generated for r in results)
    return [
        {"impl": "serve_overload_2x", "us": round(s * 1e6, 1),
         "tokens_per_s": round(tok / s, 1),
         "preemptions": health["preemptions"],
         "queue_high_water": health["queue_high_water"]},
        {"overload_completion_ratio": round(completed / len(results), 3)},
    ]


def bench_spec(params, cfg, ckw, prompts, n_new, passes, tps_plain):
    """Self-speculative decoding on the DBB density ladder vs the plain
    continuous engine, same greedy workload (docs/serving.md
    "Speculative decoding").

    The draft here is the DEGENERATE rung — ``draft_nnz`` equal to the
    target's own bound, so draft == target and every proposal must
    verify.  On this random-weight smoke model a genuinely tighter rung
    proposes at chance level (~1/vocab acceptance, a coin flip across
    BLAS builds), which would make the gates noise; the degenerate rung
    instead makes both tracked keys exact: ``acceptance_rate`` must be
    1.0 (any acceptance-indexing or draft/verify-divergence bug drops
    it — gated tight in benchmarks/compare.py) and
    ``spec_vs_plain_throughput`` isolates the draft+verify plumbing
    overhead at full acceptance (timing-derived, loose tolerance).  The
    accuracy-driven acceptance of real lower rungs needs trained
    weights; only the exactness contract is measurable here."""
    from repro.serve.engine import Engine, ServeConfig, SpecConfig

    eng = Engine(params, cfg, ServeConfig(
        spec=SpecConfig(draft="nnz", draft_nnz=cfg.sparsity.a_nnz), **ckw
    ))
    eng.generate(prompts, n_new)  # warmup/compile
    s = _time_once(lambda: eng.generate(prompts, n_new), passes)
    tok = prompts.shape[0] * n_new
    tps = tok / s
    stats = eng.spec_stats()
    return [
        {"impl": "serve_spec_decode", "us": round(s * 1e6, 1),
         "tokens_per_s": round(tps, 1),
         "acceptance_rate": round(stats["acceptance_rate"], 3),
         "spec_runs": stats["spec_runs"],
         "paged_compiles": eng.paged_compiles},
        {"spec_vs_plain_throughput": round(tps / tps_plain, 3)},
    ]


def bench_snapshot(params, cfg, passes):
    """Durability rows: snapshot publish/reload µs + the deterministic
    on-disk byte ratio between f32-KV and int8-KV engine snapshots of
    the same serving state (int8 KV snapshots at wire size — the pool's
    int8 planes + per-token scales are written as stored, never
    rehydrated to f32)."""
    import os
    import tempfile

    from repro.serve.engine import Engine, ServeConfig

    rng = np.random.default_rng(9)
    prompts = [
        rng.integers(0, cfg.vocab, (s,)).astype(np.int32)
        for s in (12, 9, 14, 7)
    ]

    def dir_bytes(path):
        return sum(
            os.path.getsize(os.path.join(root, name))
            for root, _, files in os.walk(path)
            for name in files
        )

    rows, sizes = [], {}
    for label, kv in (("f32", "native"), ("int8", "int8")):
        with tempfile.TemporaryDirectory() as d:
            eng = Engine(params, cfg, ServeConfig(
                prefill_mode="continuous", max_seq=64, page_size=16,
                max_batch=4, prefill_chunk=8, kv_dtype=kv,
                snapshot_dir=d, snapshot_keep=1,
            ))
            eng.generate_requests(prompts, 8)  # warm pool, pages, jits
            sizes[label] = dir_bytes(eng.snapshot())
            if label == "f32":
                save_us = _time_once(lambda: eng.snapshot(), passes) * 1e6
                load_us = _time_once(
                    lambda: eng.load_snapshot(), passes
                ) * 1e6
                t0 = time.perf_counter()
                Engine.restore(d, params, cfg)
                cold_us = (time.perf_counter() - t0) * 1e6
                rows += [
                    {"impl": "serve_snapshot_save", "us": round(save_us, 1),
                     "snapshot_kb": round(sizes[label] / 1024, 1)},
                    # warm reload (compiled traces kept); the cold
                    # Engine.restore wall is compile-dominated and
                    # one-off, so recorded but not a gated µs row
                    {"impl": "serve_snapshot_load", "us": round(load_us, 1),
                     "cold_restore_wall_us": round(cold_us, 1)},
                ]
    rows.append(
        {"snapshot_bytes_ratio": round(sizes["f32"] / sizes["int8"], 3)}
    )
    return rows


def bench_serve(smoke: bool = False):
    from repro import configs
    from repro.models import lm
    from repro.serve.engine import Engine, ServeConfig

    cfg = dataclasses.replace(
        configs.get_config("granite_3_8b", smoke=True),
        vocab=64, d_model=64, d_ff=128, n_layers=2, dtype="float32",
    )
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    b, s0, n_new = 4, 16, 16
    passes = 2 if smoke else 4
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab, (b, s0)
    ).astype(np.int32)

    oneshot = Engine(params, cfg, ServeConfig(max_seq=64, prefill_mode="batched"))
    # headline continuous row runs with the prefix cache off: identical
    # prompts every pass would otherwise skip prefill after the first,
    # and the row must time the full prefill+decode work the one-shot
    # engine does (the prefix win has its own rows below)
    ckw = dict(
        prefill_mode="continuous", max_seq=64,
        page_size=16, max_batch=b, prefill_chunk=8, prefix_cache=False,
    )
    cont = Engine(params, cfg, ServeConfig(**ckw))
    # the fused page-table-walk engine: on this CPU host the kernel runs
    # through the Pallas interpreter, so this row tracks the *wiring*
    # cost of the fused path, not TPU performance (the deterministic
    # paged_attn_window_bytes_ratio rows in kernel_paged_attn carry the
    # HBM-traffic claim; docs/perf.md)
    cont_fused = Engine(params, cfg, ServeConfig(paged_attn="fused", **ckw))
    # seeded sampled decode through the same fused loop (temperature>0
    # routes every in-loop sample through the categorical sampler);
    # sampled_vs_greedy_throughput tracks what sampling costs the loop
    cont_sampled = Engine(params, cfg, ServeConfig(
        temperature=0.7, seed=11, **ckw
    ))
    oneshot.generate(prompts, n_new)  # warmup/compile
    # cold wall: first continuous call pays jit tracing + both compiles
    # (mixed step + fused decode loop); warm passes time the steady state
    s_cold = _time_once(lambda: cont.generate(prompts, n_new), passes=1)
    cont_fused.generate(prompts, n_new)
    cont_sampled.generate(prompts, n_new)
    s_one = _time_once(lambda: oneshot.generate(prompts, n_new), passes)
    s_cont = _time_once(lambda: cont.generate(prompts, n_new), passes)
    s_fused = _time_once(lambda: cont_fused.generate(prompts, n_new), passes)
    s_samp = _time_once(lambda: cont_sampled.generate(prompts, n_new), passes)
    tok = b * n_new
    tps_one, tps_cont = tok / s_one, tok / s_cont
    tps_samp = tok / s_samp
    kv_rows, _ = bench_kv_cache(cfg, params, passes)
    # per-request latency percentiles from the RequestResult timing
    # fields (staggered arrivals; each counts from its own); reported
    # for the trajectory, not gated — the µs rows guard these paths
    from repro.runtime import monitor

    lat = cont.serve_requests(
        list(prompts), n_new, arrivals=list(range(b))
    )
    ttft = [r.time_to_first_token * 1e6 for r in lat]
    queue = [r.queue_time * 1e6 for r in lat]
    lat_row = {
        "impl": "serve_latency",
        "ttft_p50_us": round(monitor.percentile(ttft, 50), 1),
        "ttft_p99_us": round(monitor.percentile(ttft, 99), 1),
        "queue_time_p50_us": round(monitor.percentile(queue, 50), 1),
        "tokens_per_s_p50": round(monitor.percentile(
            [r.tokens_per_second for r in lat], 50
        ), 1),
    }
    rows = [
        {"impl": "serve_oneshot_batched", "us": round(s_one * 1e6, 1),
         "tokens_per_s": round(tps_one, 1)},
        # cold_wall_us is one-off compile-dominated wall time: recorded
        # for the trajectory, deliberately NOT a gated ``us`` row.
        # paged_compiles counts the loop's compiled traces — the shape
        # bucketing keeps it at exactly 2 across the whole workload.
        {"impl": "serve_continuous", "us": round(s_cont * 1e6, 1),
         "tokens_per_s": round(tps_cont, 1),
         "cold_wall_us": round(s_cold * 1e6, 1),
         "paged_compiles": cont.paged_compiles,
         "decode_run_calls": cont.decode_run_calls,
         "fused_tokens": cont.fused_tokens},
        {"impl": "serve_continuous_paged_attn_fused",
         "us": round(s_fused * 1e6, 1),
         "tokens_per_s": round(tok / s_fused, 1)},
        {"impl": "serve_continuous_sampled",
         "us": round(s_samp * 1e6, 1),
         "tokens_per_s": round(tps_samp, 1),
         "paged_compiles": cont_sampled.paged_compiles},
        # timing-derived; gated with a loose per-key tolerance in
        # benchmarks/compare.py (see module docstring)
        lat_row,
        {"continuous_vs_oneshot_throughput": round(tps_cont / tps_one, 3)},
        {"sampled_vs_greedy_throughput": round(tps_samp / tps_cont, 3)},
        *bench_spec(params, cfg, ckw, prompts, n_new, passes, tps_cont),
        *bench_prefix_cache(params, cfg, b),
        *bench_overload(params, cfg, passes),
        *bench_snapshot(params, cfg, passes),
        *kv_rows,
        {"shape": [b, s0, n_new], "prefill_chunk": 8, "page_size": 16},
    ]
    return rows, round(tps_cont / tps_one, 3)


def check_prefix(path: str = "BENCH_kernels.json") -> int:
    """CI smoke gate: the recorded serve_bench rows must show a live
    prefix cache (hit rate and saved-token ratio > 0) and the two-trace
    compile budget.  Returns a process exit code."""
    import json

    with open(path) as f:
        record = json.load(f)
    rows = record["benchmarks"]["serve_bench"]["rows"]
    flat = {}
    for r in rows:
        if isinstance(r, dict):
            if r.get("impl") == "serve_continuous":
                flat["paged_compiles"] = r.get("paged_compiles")
            flat.update({
                k: r[k] for k in (
                    "prefix_hit_rate", "prefill_tokens_saved_ratio",
                    "continuous_vs_oneshot_throughput",
                ) if k in r
            })
    failures = []
    if not flat.get("prefix_hit_rate", 0) > 0:
        failures.append(f"prefix_hit_rate not > 0: {flat.get('prefix_hit_rate')}")
    if not flat.get("prefill_tokens_saved_ratio", 0) > 0:
        failures.append(
            "prefill_tokens_saved_ratio not > 0: "
            f"{flat.get('prefill_tokens_saved_ratio')}"
        )
    if flat.get("paged_compiles") != 2:
        failures.append(f"paged_compiles != 2: {flat.get('paged_compiles')}")
    if "continuous_vs_oneshot_throughput" not in flat:
        failures.append("continuous_vs_oneshot_throughput row missing")
    for line in failures:
        print(f"check-prefix FAIL: {line}")
    if not failures:
        print(
            "check-prefix ok: "
            f"hit_rate={flat['prefix_hit_rate']} "
            f"tokens_saved_ratio={flat['prefill_tokens_saved_ratio']} "
            f"paged_compiles={flat['paged_compiles']}"
        )
    return 1 if failures else 0


def check_chaos(n_seeds: int = 12) -> int:
    """CI smoke gate for fault isolation: seeded chaos over a 2x
    oversubscribed pool — injected allocator failures, one forced
    fused-kernel failure, one NaN-poisoned request per seed, free-page
    scribbles.  Fails if any engine exception escapes, any request comes
    back without a typed outcome, or any *healthy* request's tokens
    differ from the fault-free reference run (tests/test_faults.py runs
    the same fuzz at 200 seeds under ``-m chaos``).  Returns a process
    exit code."""
    from repro import configs
    from repro.models import lm
    from repro.serve import faults
    from repro.serve.engine import Engine, ServeConfig

    cfg = dataclasses.replace(
        configs.get_config("granite_3_8b", smoke=True),
        vocab=64, d_model=64, d_ff=128, n_layers=2, dtype="float32",
    )
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(13)
    prompts = [
        rng.integers(0, cfg.vocab, (s,)).astype(np.int32)
        for s in (9, 5, 12, 7, 10, 6)
    ]
    n_tok = 8
    skw = dict(
        prefill_mode="continuous", max_seq=48, page_size=4,
        max_batch=3, max_pages=13, prefill_chunk=4, preempt_after=3,
    )
    ref_eng = Engine(params, cfg, ServeConfig(
        max_seq=48, prefill_mode="stepped"
    ))
    ref = [ref_eng.generate(p[None], n_tok)[0] for p in prompts]
    failures = []
    eng = Engine(params, cfg, ServeConfig(**skw))
    for seed in range(n_seeds):
        victim = eng._rid + 1 + (seed % len(prompts))
        eng.set_faults(faults.FaultConfig(
            seed=seed, alloc_fail_p=0.05, nan_rids=(victim,),
            scrub_corrupt_p=0.1,
        ))
        try:
            res = eng.serve_requests(prompts, n_tok)
        except Exception as exc:  # the one thing that must never happen
            failures.append(f"seed {seed}: engine raised {exc!r}")
            break
        for i, r in enumerate(res):
            if r.finish_reason == "length":
                if not np.array_equal(r.tokens, ref[i]):
                    failures.append(
                        f"seed {seed}: healthy request {i} corrupted"
                    )
            elif r.finish_reason != "numerical_error":
                failures.append(
                    f"seed {seed}: request {i} untyped/unexpected "
                    f"outcome {r.finish_reason!r}"
                )
    # forced fused-kernel failure -> one-way gather fallback, byte-exact
    fcfg = dataclasses.replace(
        cfg, sparsity=dataclasses.replace(cfg.sparsity, paged_attn="fused")
    )
    feng = Engine(params, fcfg, ServeConfig(**skw))
    feng.set_faults(faults.FaultConfig(seed=0, fail_fused=True))
    try:
        fres = feng.serve_requests(prompts, n_tok)
        if feng.fallbacks != 1:
            failures.append(f"fused fallback count {feng.fallbacks} != 1")
        for i, r in enumerate(fres):
            if not (r.ok and np.array_equal(r.tokens, ref[i])):
                failures.append(
                    f"fused-fallback request {i} not byte-exact "
                    f"({r.finish_reason})"
                )
    except Exception as exc:
        failures.append(f"fused fault: engine raised {exc!r}")
    for line in failures:
        print(f"check-chaos FAIL: {line}")
    if not failures:
        h = eng.health()
        print(
            f"check-chaos ok: {n_seeds} seeds, "
            f"alloc_faults={h.get('injected_alloc_faults', 0)} "
            f"nan_poisons={h.get('injected_nan_poisons', 0)} "
            f"scribbles={h.get('injected_scribbles', 0)} "
            f"preemptions={h.get('preemptions', 0)} "
            f"fused_fallbacks={feng.fallbacks}"
        )
    return 1 if failures else 0


def check_sampling() -> int:
    """CI smoke gate for seeded sampling: one live mini-workload asserts
    the reproducibility contract end to end (docs/serving.md "Sampling")
    — fused-loop sampled tokens byte-identical to the stepped sampler
    under the same seed, sampled output diverging from greedy, greedy
    output identical with and without the sampler in the loop, stop
    tokens finishing as ``"stop"``, and ``paged_compiles == 2`` with
    sampling fused in-loop.  Returns a process exit code."""
    from repro import configs
    from repro.models import lm
    from repro.serve.engine import Engine, ServeConfig

    cfg = dataclasses.replace(
        configs.get_config("granite_3_8b", smoke=True),
        vocab=64, d_model=64, d_ff=128, n_layers=2, dtype="float32",
    )
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompts = [
        rng.integers(0, cfg.vocab, (s,)).astype(np.int32)
        for s in (9, 5, 12)
    ]
    n_tok = 8
    ckw = dict(
        prefill_mode="continuous", max_seq=32, page_size=8,
        max_batch=2, prefill_chunk=4, prefix_cache=False,
    )
    skw = dict(temperature=0.7, seed=11)
    failures = []
    sampled_eng = Engine(params, cfg, ServeConfig(**ckw, **skw))
    sampled = sampled_eng.generate_requests(prompts, n_tok)
    stepped_eng = Engine(params, cfg, ServeConfig(
        max_seq=32, prefill_mode="stepped", **skw
    ))
    stepped = [stepped_eng.generate(p[None], n_tok)[0] for p in prompts]
    for i, (a, b_) in enumerate(zip(sampled, stepped)):
        if not np.array_equal(a, b_):
            failures.append(f"request {i}: fused sampled != stepped sampled")
    if sampled_eng.decode_run_calls == 0:
        failures.append("sampled workload never used the fused decode loop")
    if sampled_eng.paged_compiles != 2:
        failures.append(
            f"paged_compiles != 2 with sampling: {sampled_eng.paged_compiles}"
        )
    greedy_eng = Engine(params, cfg, ServeConfig(**ckw))
    greedy = greedy_eng.generate_requests(prompts, n_tok)
    greedy_stepped = Engine(params, cfg, ServeConfig(
        max_seq=32, prefill_mode="stepped"
    ))
    for i, (g, p) in enumerate(zip(greedy, prompts)):
        if not np.array_equal(g, greedy_stepped.generate(p[None], n_tok)[0]):
            failures.append(f"request {i}: greedy bytes changed")
    if all(np.array_equal(a, g) for a, g in zip(sampled, greedy)):
        failures.append("temperature=0.7 never diverged from greedy")
    # stop tokens: stop on the 3rd greedy continuation token
    stop = int(greedy[0][len(prompts[0]) + 2])
    res = greedy_eng.serve_requests(prompts[:1], n_tok, stop_tokens=[stop])
    if res[0].finish_reason != "stop":
        failures.append(
            f"stop token did not fire: {res[0].finish_reason!r}"
        )
    elif int(res[0].tokens[-1]) != stop:
        failures.append("stop token not recorded as the final output token")
    for line in failures:
        print(f"check-sampling FAIL: {line}")
    if not failures:
        print(
            "check-sampling ok: fused==stepped over "
            f"{len(prompts)} sampled requests, "
            f"paged_compiles={sampled_eng.paged_compiles}, "
            f"stop fired at {res[0].n_generated} tokens"
        )
    return 1 if failures else 0


def check_spec() -> int:
    """CI smoke gate for self-speculative decoding: one live
    mini-workload asserts the exactness contract end to end
    (docs/serving.md "Speculative decoding") — spec output byte-
    identical to the plain continuous engine for both draft kinds,
    nonzero proposals, acceptance_rate == 1.0 when the draft IS the
    target (int8 wire on both sides — pins acceptance indexing), a stop
    token inside a draft window truncating exactly, and the 3-trace
    compile budget.  Returns a process exit code."""
    from repro import configs
    from repro.models import lm
    from repro.serve.engine import Engine, ServeConfig, SpecConfig

    cfg = dataclasses.replace(
        configs.get_config("granite_3_8b", smoke=True),
        vocab=64, d_model=64, d_ff=128, n_layers=2, dtype="float32",
    )
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompts = [
        rng.integers(0, cfg.vocab, (s,)).astype(np.int32)
        for s in (9, 5, 12)
    ]
    n_tok = 10
    ckw = dict(
        prefill_mode="continuous", max_seq=32, page_size=8,
        max_batch=2, prefill_chunk=4, prefix_cache=False,
    )
    failures = []
    plain = Engine(params, cfg, ServeConfig(**ckw)).generate_requests(
        prompts, n_tok
    )
    spec_eng = None
    for draft in ("nnz", "int8_wire"):
        spec_eng = Engine(params, cfg, ServeConfig(
            spec=SpecConfig(draft=draft, draft_nnz=2), **ckw
        ))
        out = spec_eng.generate_requests(prompts, n_tok)
        for i, (a, b_) in enumerate(zip(out, plain)):
            if not np.array_equal(a, b_):
                failures.append(f"draft={draft} request {i}: bytes diverged")
        if spec_eng.spec_stats()["proposed"] == 0:
            failures.append(f"draft={draft}: no proposals made")
    if spec_eng.paged_compiles != 3:
        failures.append(
            f"paged_compiles != 3 with spec: {spec_eng.paged_compiles}"
        )
    # draft == target (int8 wire both sides): every proposal must verify
    ident = Engine(params, cfg, ServeConfig(
        spec=SpecConfig(draft="int8_wire"),
        pack_weights=True, wire_dtype="int8", **ckw
    ))
    ident.generate_requests(prompts, n_tok)
    rate = ident.spec_stats()["acceptance_rate"]
    if rate != 1.0:
        failures.append(f"identical-draft acceptance_rate != 1.0: {rate}")
    # stop token sampled inside a draft window truncates exactly
    stop = int(plain[0][len(prompts[0]) + 2])
    seng = Engine(params, cfg, ServeConfig(spec=SpecConfig(), **ckw))
    res = seng.serve_requests(prompts[:1], n_tok, stop_tokens=[stop])
    if res[0].finish_reason != "stop":
        failures.append(f"stop inside window did not fire: {res[0].finish_reason!r}")
    elif int(res[0].tokens[-1]) != stop:
        failures.append("stop token not the final output token")
    elif not np.array_equal(
        res[0].tokens, plain[0][: len(prompts[0]) + 3]
    ):
        failures.append("stop-truncated output != plain prefix")
    for line in failures:
        print(f"check-spec FAIL: {line}")
    if not failures:
        print(
            "check-spec ok: both draft kinds byte-identical over "
            f"{len(prompts)} requests, identical-draft acceptance=1.0, "
            f"paged_compiles={spec_eng.paged_compiles}, "
            f"stop fired at {res[0].n_generated} tokens"
        )
    return 1 if failures else 0


def check_restore() -> int:
    """CI smoke gate for durable serving (docs/serving.md "Durability"):
    kill a sampled continuous workload at an iteration boundary,
    cold-restore a fresh engine from the last published snapshot, resume,
    and require byte-identical output, no-dup/no-gap streaming across
    the crash, and zero leaked pages; then kill a second run mid-save
    and require the orphaned ``.tmp`` to be ignored by restore.  Returns
    a process exit code."""
    import os
    import tempfile

    from repro import configs
    from repro.models import lm
    from repro.serve import faults
    from repro.serve.engine import Engine, ServeConfig

    cfg = dataclasses.replace(
        configs.get_config("granite_3_8b", smoke=True),
        vocab=64, d_model=64, d_ff=128, n_layers=2, dtype="float32",
    )
    params, _ = lm.init_lm(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompts = [
        rng.integers(0, cfg.vocab, (s,)).astype(np.int32)
        for s in (9, 5, 12, 7)
    ]
    n_tok = 8
    skw = dict(
        prefill_mode="continuous", max_seq=48, page_size=4,
        max_batch=3, max_pages=13, prefill_chunk=4,
        temperature=0.7, seed=11,
    )
    failures = []
    ref = Engine(params, cfg, ServeConfig(**skw)).generate_requests(
        prompts, n_tok
    )

    # --- kill at an iteration boundary, stream across the crash
    streamed = {}
    with tempfile.TemporaryDirectory() as d:
        victim = Engine(params, cfg, ServeConfig(
            snapshot_dir=d, snapshot_every=2, snapshot_keep=4, **skw
        ))
        victim.set_faults(
            faults.FaultConfig(kill_at=5, kill_point="iteration")
        )
        try:
            victim.serve_requests(
                prompts, n_tok,
                on_token=lambda rid, toks, start:
                    streamed.setdefault(rid, []).extend(toks),
            )
            failures.append("victim engine survived its kill point")
        except faults.SimulatedCrash:
            pass
        collected = {}

        def resume_cb(rid, toks, start):
            s0, buf = collected.setdefault(rid, (start, []))
            if start != s0 + len(buf):
                failures.append(f"request {rid}: stream gap/duplicate")
            buf.extend(toks)

        try:
            eng = Engine.restore(d, params, cfg)
            results = eng.resume(
                on_token=resume_cb,
                delivered={r: len(t) for r, t in streamed.items()},
            )
        except Exception as exc:
            failures.append(f"restore/resume raised {exc!r}")
            results, eng = [], None
        if not results:
            failures.append("no in-flight requests survived the snapshot")
        for r in results:
            if not np.array_equal(r.tokens, ref[r.rid - 1]):
                failures.append(
                    f"request {r.rid}: bytes diverged after restore"
                )
            s0, buf = collected.get(r.rid, (0, []))
            full = list(r.tokens[len(r.tokens) - r.n_generated:])
            if streamed.get(r.rid, []) + buf != full:
                failures.append(
                    f"request {r.rid}: crash-spanning stream != output"
                )
        if eng is not None:
            state = eng._cont["allocator"].export_state()
            if state["tables"]:
                failures.append(f"leaked page tables: {state['tables']}")
            n_data = state["n_pages"] - 1
            if len(state["free"]) + len(state["refs"]) != n_data:
                failures.append(
                    f"page accounting broken: {len(state['free'])} free + "
                    f"{len(state['refs'])} prefix-held != {n_data}"
                )

    # --- kill mid-save: the orphaned .tmp must not confuse restore
    with tempfile.TemporaryDirectory() as d:
        victim = Engine(params, cfg, ServeConfig(
            snapshot_dir=d, snapshot_every=2, snapshot_keep=4, **skw
        ))
        victim.set_faults(
            faults.FaultConfig(kill_at=2, kill_point="mid_save")
        )
        try:
            victim.generate_requests(prompts, n_tok)
            failures.append("mid-save victim survived its kill point")
        except faults.SimulatedCrash:
            pass
        if not any(n.endswith(".tmp") for n in os.listdir(d)):
            failures.append("mid-save crash left no .tmp dir behind")
        try:
            res = Engine.restore(d, params, cfg).resume()
            for r in res:
                if not np.array_equal(r.tokens, ref[r.rid - 1]):
                    failures.append(
                        f"mid-save: request {r.rid} diverged after restore"
                    )
        except Exception as exc:
            failures.append(f"mid-save restore raised {exc!r}")

    for line in failures:
        print(f"check-restore FAIL: {line}")
    if not failures:
        print(
            f"check-restore ok: {len(results)} in-flight requests "
            "byte-identical after iteration-kill restore "
            f"({sum(len(t) for t in streamed.values())} tokens streamed "
            "pre-crash, no dups/gaps), mid-save .tmp ignored"
        )
    return 1 if failures else 0


if __name__ == "__main__":
    import sys

    if "--check-prefix" in sys.argv:
        args = [a for a in sys.argv[1:] if a != "--check-prefix"]
        sys.exit(check_prefix(*args[:1]))
    if "--check-chaos" in sys.argv:
        sys.exit(check_chaos())
    if "--check-sampling" in sys.argv:
        sys.exit(check_sampling())
    if "--check-spec" in sys.argv:
        sys.exit(check_spec())
    if "--check-restore" in sys.argv:
        sys.exit(check_restore())
    for row in bench_serve(smoke="--smoke" in sys.argv)[0]:
        print(row)
