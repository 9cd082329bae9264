"""Per-layer metrics of a traced burst: builds what the readers in
``metrics/`` read, and runs each one the cell reports.

A reader is ``metrics/<name>.py`` with ``UNIT`` and ``read(ctx)``; it
returns a number, or ``None`` when the trace holds nothing for it, and
the harness then leaves the metric out of the line.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import sys
from typing import Optional

from bench import cells, counts, tracing


@dataclasses.dataclass
class Context:
    model: dict  # the configuration's ``model`` block
    peaks: dict  # peaks.json row of this device kind
    trace: tracing.Trace
    window: tuple  # (t0, t1): the traced burst, trace clock
    counters: dict  # engine counter deltas over the traced burst
    work: dict  # counts.request_work summed over the burst's requests
    programs: Optional[tuple]  # (mixed, decode) executions, or None
    serve: dict  # the cell's ServeConfig fields

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def load_reader(name: str, metric_dir=cells.METRIC_DIR):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", metric_dir / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def burst_work(model: dict, finished, serve: dict, doc_tokens: int) -> dict:
    """Work of every request of the traced burst (``counts.request_work``)."""
    ps, chunk = serve["page_size"], serve["prefill_chunk"]
    total = {"ops": 0, "kv_bytes": 0, "qo_bytes": 0, "tokens": 0}
    for req, res in finished:
        p = len(req.prompt)
        # adopted from the prefix cache: the document's whole pages, up to
        # the last prompt token (whose logits are always computed)
        cached = min((doc_tokens // ps) * ps, p - 1)
        w = counts.request_work(model, p, res.n_generated, cached, chunk)
        for k in total:
            total[k] += w[k]
    return total


def build_context(cell, trace_dir, finished, c0, c1, serve, peaks) -> Context:
    trace = tracing.load(trace_dir)
    window = trace.window()
    deltas = {k: c1[k] - c0[k] for k in c0}
    doc = cell.mix.get("document")
    work = burst_work(cell.config["model"], finished, serve,
                      doc["tokens"] if doc else 0)
    progs = tracing.split_programs(trace.modules, *window)
    n_mixed = deltas["step_calls"] - deltas["decode_run_calls"]
    if (len(progs[0]), len(progs[1])) != (n_mixed, deltas["decode_run_calls"]):
        print(f"trace: {len(progs[0])} mixed and {len(progs[1])} decode "
              f"programs, counters say {n_mixed} and "
              f"{deltas['decode_run_calls']}: program times left out",
              file=sys.stderr)
        progs = None
    return Context(cell.config["model"], peaks, trace, window, deltas, work,
                   progs, serve)


def per_layer(cell, trace_dir, finished, c0, c1, serve, peaks):
    """``(metrics, breakdown, busy_s, window_s)`` of the traced burst."""
    ctx = build_context(cell, trace_dir, finished, c0, c1, serve, peaks)
    metrics = {}
    for name in cell.per_layer:
        reader = load_reader(name)
        value = reader.read(ctx)
        if value is not None:
            metrics[name] = {"value": value, "unit": reader.UNIT}
    t0, t1 = ctx.window
    busy = sum(tracing.busy(ops, t0, t1) for ops in ctx.trace.device_ops.values())
    busy /= max(1, ctx.trace.n_devices)
    breakdown = {
        "device_ops": tracing.top_ops(ctx.trace.ops, t0, t1),
        "idle_gaps": tracing.label_gaps(ctx.trace, t0, t1),
    }
    return metrics, breakdown, busy, ctx.window_s
