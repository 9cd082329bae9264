"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``bench/cells.py``) is made of files alone: ``configs/<config>.json``,
``traffic/<traffic>.json``, its correctness limits ``checks/<cell>.json``
and its entry in ``BENCHMARK.json``; a new one needs new files and no edit.

One process.  It builds the configuration's packed int8 DBB weights on
the device from ``--seed`` (``engine.init_serving_params``), the
``Engine`` with the configuration's serving fields, warms up the two
programs of the continuous loop with one request, serves the mix's
shared document once where it has one (the prefix cache then holds it),
and then serves bursts (``bench/traffic.py``) back to back through
``Engine.serve_requests`` until ``--seconds`` have passed; the burst in
flight then finishes, and the window ends with it.  After the window it
reads the device's peak memory, frees the engine, and checks the greedy
requests the window finished against the plain reference
(``bench/reference``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, last, ``check``
(each compared number beside its limit, which the last lines of
standard error repeat; the limits are the cell's ``checks/<cell>.json``,
``bench/check.py``).  ``--trace 0`` reports the end-to-end metrics;
``--trace 1`` traces the first burst of the window instead, stops after
it, and reports the per-layer metrics (``bench/metrics/<name>.py``),
the device's busy and traced seconds and a ``breakdown`` of device
operations and idle gaps.  ``--control w4`` (not used by the benchmark's
own runs) judges the control instead: the reference with 4-bit weights
in the program's place, under the same limit; its verdict is the line's
``correct``, and the program's readings are printed beside it.

It appends ``--xla_allow_excess_precision=false`` to ``XLA_FLAGS``
(``repro.runtime.precision``), leaves ``LIBTPU_INIT_ARGS`` as it is,
keeps JAX's compilation cache where ``repro.runtime.compile_cache`` puts
it, and exits non-zero with no result when JAX finds no TPU, fewer chips
than the cell asks for, or a device kind missing from ``peaks.json``.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
REPO = BENCH.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench import cells, check, stats, traffic  # noqa: E402

TRACE_DIR = BENCH / "out" / "trace"  # gitignored; replaced by every traced run


def parse_args(argv):
    ap = argparse.ArgumentParser(description="run one benchmark cell once")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("w4",), default=None,
                    help="judge the lower-precision control in the program's place")
    return ap.parse_args(argv)


def fail(msg: str, code: int = 2) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr)
    return code


def device_check(cell, require_tpu):
    """``(device, peaks)`` or an error message."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        return None, f"no TPU: JAX sees {dev.platform!r} ({dev.device_kind})"
    if len(devices) < cell.chips:
        return None, (f"cell {cell.name} needs {cell.chips} chips, JAX sees "
                      f"{len(devices)} {dev.device_kind}")
    table = json.loads((BENCH / "peaks.json").read_text())["kinds"]
    peaks = table.get(dev.device_kind)
    if require_tpu and peaks is None:
        return None, f"device kind {dev.device_kind!r} is not in peaks.json"
    return (dev, len(devices), peaks), None


def model_config(cell):
    """The program's model configuration, checked against the file."""
    from repro import configs

    conf = cell.config
    cfg = configs.get_config(conf["arch"], smoke=conf.get("smoke", False))
    m = conf["model"]
    have = {
        "n_layers": cfg.n_layers, "d_model": cfg.d_model,
        "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim(), "d_ff": cfg.d_ff, "vocab": cfg.vocab,
        "padded_vocab": cfg.padded_vocab, "rope_theta": cfg.rope_theta,
        "norm_eps": cfg.norm_eps, "dtype": cfg.dtype,
    }
    if cfg.mla is not None:
        import dataclasses

        have["mla"] = dataclasses.asdict(cfg.mla)
    diff = {k: (m.get(k), v) for k, v in have.items() if m.get(k) != v}
    if diff:
        raise ValueError(f"{conf['name']}: file and program differ: {diff}")
    return cfg


def build_engine(cell, cfg, seed):
    import jax

    from repro.serve import engine as eng_mod

    from bench.reference.model import model_key

    serve = {**cell.config["serve"], **cell.mix.get("serve", {})}
    # the program's own layer-by-layer build; one jitted call over the
    # whole stack took over ten minutes to compile for the chip
    params = eng_mod.init_serving_params(cfg, model_key(seed), serve["wire_dtype"])
    jax.block_until_ready(params)
    scfg = eng_mod.ServeConfig(**serve)
    return eng_mod.Engine(params, cfg, scfg)


def serve_burst(engine, reqs, records, trace_spans):
    """One ``serve_requests`` call; appends a ``stats.Record`` per request
    and returns ``(start, end, results)``."""
    from repro.core.sampling import SamplingParams

    first = [None] * len(reqs)
    last = [None] * len(reqs)
    count = [0] * len(reqs)

    def callback(i):
        def on_token(rid, tokens, start):
            now = time.perf_counter()
            if first[i] is None:
                first[i] = now
            last[i] = now
            count[i] += len(tokens)
            if trace_spans:
                import jax

                with jax.profiler.TraceAnnotation("on_token"):
                    pass
        return on_token

    sampling = [SamplingParams(temperature=r.temperature, seed=r.sample_seed)
                for r in reqs]
    t0 = time.perf_counter()
    results = engine.serve_requests(
        [r.prompt for r in reqs], [r.n_out for r in reqs],
        sampling=sampling, on_token=[callback(i) for i in range(len(reqs))],
    )
    t1 = time.perf_counter()
    for i, res in enumerate(results):
        records.append(stats.Record(
            due=t0, first=first[i], last=last[i], n_out=count[i], ok=res.ok,
        ))
    return t0, t1, results


def counters(engine):
    return {
        "step_calls": engine.step_calls,
        "decode_run_calls": engine.decode_run_calls,
        "fused_tokens": engine.fused_tokens,
        "prefix_tokens_saved": engine.prefix_stats().get("tokens_saved", 0),
    }


def main(argv=None, *, require_tpu=True, config_dir=cells.CONFIG_DIR,
         traffic_dir=cells.TRAFFIC_DIR, check_dir=cells.CHECK_DIR) -> int:
    args = parse_args(argv)
    try:
        cell = cells.resolve(args.workload, config_dir, traffic_dir,
                             check_dir=check_dir)
    except KeyError as err:
        return fail(str(err))
    sys.path.insert(0, str(REPO / "src"))
    try:
        from repro.runtime import compile_cache, precision
    except ImportError as err:
        return fail(f"the program is not here ({err})")
    if require_tpu:  # before JAX creates a backend; the CPU needs none
        precision.exact_rounding()

    found, err = device_check(cell, require_tpu)
    if err:
        return fail(err)
    dev, n_dev, peaks = found
    if require_tpu:
        compile_cache.enable()
    import jax
    import numpy as np

    cfg = model_config(cell)
    engine = build_engine(cell, cfg, args.seed)
    gen = traffic.Generator(cell.mix, cfg.vocab, args.seed)
    warm = np.arange(1, engine.scfg.page_size, dtype=np.int32)  # < one page
    engine.serve_requests([warm], 2)
    if gen.document is not None:  # the prefix cache holds it from here
        engine.serve_requests([gen.document], 1)
    compiles = engine.paged_compiles

    records, finished = [], []
    c0 = counters(engine)
    window_start = time.perf_counter()
    setup_s = window_start - T_PROCESS
    if args.trace:
        import shutil

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # no per-call Python events: the host
        # loop would slow down and the trace grow by millions of events
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    while True:
        reqs = gen.burst()
        with jax.profiler.TraceAnnotation("burst"):
            t0, t1, results = serve_burst(engine, reqs, records, args.trace)
        finished.extend(zip(reqs, results))
        if args.trace:
            jax.profiler.stop_trace()
            break
        if t1 - window_start >= args.seconds:
            break
    window_end = t1
    c1 = counters(engine)
    mem = dev.memory_stats() or {}
    peak = mem.get("peak_bytes_in_use")
    if engine.paged_compiles != compiles or compiles != 2:
        return fail(f"the serving loop compiled {engine.paged_compiles} "
                    f"programs ({compiles} before the window); expected 2")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_dev, "memory_peak_bytes": peak}
    attempted = len(records)
    n_failed = sum(not r.ok for r in records)
    serve_fields = {**cell.config["serve"], **cell.mix.get("serve", {})}
    del engine, results
    gc.collect()

    try:
        verdict = check.run(cell, args.seed, finished, args.control)
    except Exception:  # a check that cannot finish is a run that is not correct
        traceback.print_exc()
        verdict = check.failed(cell)
    if args.trace:
        from bench import readers

        metrics, breakdown, busy_s, traced_s = readers.per_layer(
            cell, TRACE_DIR, finished, c0, c1, serve_fields, peaks)
        device["busy_s"] = busy_s
        device["window_s"] = traced_s
    else:
        e2e = stats.end_to_end(records, window_start, window_end)
        measured = {
            "tokens_per_s": {"value": e2e["tokens_per_s"], "unit": "tokens/s"},
            "ttft_p90_s": {"value": e2e["ttft_p90_s"], "unit": "s"},
            "tpot_p90_ms": {"value": e2e["tpot_p90_ms"], "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        metrics = {name: measured[name] for name in cell.end_to_end}
        print(f"window: {e2e['window_s']:.3f} s, {e2e['n_requests']} requests, "
              f"tpot_p90_ms {e2e['tpot_p90_ms']!r} "
              f"({e2e['n_tpot']} with a token gap), counters "
              f"{ {k: c1[k] - c0[k] for k in c0} }", file=sys.stderr)
    out = {
        "correct": verdict["correct"],
        "attempted": attempted,
        "failed": n_failed,
        "metrics": metrics,
        "device": device,
    }
    if args.trace:
        out["breakdown"] = breakdown
    out["readings"] = verdict["readings"]
    if args.control:
        out["control"] = verdict["control"]
    out["check"] = verdict["check"]
    if args.control:
        print(f"program: {verdict['readings']}", file=sys.stderr)
    for name, item in verdict["check"].items():
        print(f"check {name}: {item['value']!r} limit {item['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.stdout.reconfigure(line_buffering=True)
    sys.exit(main())
