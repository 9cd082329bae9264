"""The program's own spans in a profiler trace, and what they say about
the device's idle time.

The serving engine marks its loop with host spans (docs/serving.md
"Tracing"): ``serve.requests`` around each ``serve_requests`` call,
``serve.iteration`` around each scheduler iteration (arguments ``it``,
``kind``, ``rows``, ``fed``) with the children ``serve.snapshot``,
``serve.plan``, ``serve.dispatch``, ``serve.sample``, ``serve.wait`` and
``serve.commit``; ``serve.admit`` at each first admission (``rid``,
``queue_s``: the request's ``RequestResult.queue_time``); and
``python.gc`` around each garbage collection (``generation``).  They
sit on the host plane of the trace that holds the device's programs
and operations, on the same clock.

``load`` keeps them as :class:`Span` tuples in seconds, with device
0's operations as :class:`Op` tuples that carry the named scope
(``dbb_linear``, ``dap_pack``, ``paged_attn``) each belongs to, read
from the optimized HLO the profiler keeps in the trace
(:func:`hlo_scopes`).  ``bench/tracing.py`` keeps only the harness's own
spans and bare op names, so the readers that need the program's take
them from here (:func:`of`).  A trace of a program without spans or
scopes yields none, and those readers then read nothing.

Run as a script on a trace directory, it prints what the harness's
breakdown does not: the device-idle time put down to the deepest span
that covers it, the longest gaps led by that span, the device time of
the ops each named scope (``dbb_linear``, ``dap_pack``, ``paged_attn``)
holds, and the iterations by kind.

    python3 bench/spans.py [bench/out/trace]
"""

from __future__ import annotations

import bisect
import collections
import json
import pathlib
import sys
from typing import Dict, List, NamedTuple, Optional, Sequence

if __package__ in (None, ""):  # run as a script
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import cells, tracing  # noqa: E402

TRACE_DIR = cells.BENCH / "out" / "trace"  # where bench/run.py traces
SCOPES = ("dbb_linear", "dap_pack", "paged_attn")
NO_SPAN = "no span"


class Span(NamedTuple):
    name: str
    start: float  # s, trace clock
    end: float
    args: dict

    @property
    def seconds(self) -> float:
        return self.end - self.start


def is_program_span(name: str) -> bool:
    return name.startswith("serve.") or name == "python.gc"


def trace_file(trace_dir) -> pathlib.Path:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


class Op(NamedTuple):
    name: str  # the XLA op event's name: its HLO instruction
    start: float  # s, trace clock
    end: float
    scope: Optional[str]  # one of SCOPES, or None


class Program(NamedTuple):
    spans: List[Span]  # the program's host spans, sorted by start
    ops: List[Op]  # device 0's operations


def scope_of(texts: Sequence[str]) -> Optional[str]:
    """The named scope (``SCOPES``) an op path (``OpMetadata.op_name``,
    ``jit(paged_step)/.../dbb_linear/dot_general``) holds, or that an
    instruction's name gives (``%paged_attn.3 = ...``)."""
    for scope in SCOPES:
        for t in texts:
            if f"/{scope}/" in t or t.startswith((f"%{scope}.", f"{scope}.")):
                return scope
    return None


def _fields(buf):
    """``(field number, value)`` of a protobuf message: varints as ints,
    length-delimited values as memoryviews; fixed-width ones skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        else:  # 64- or 32-bit
            i += 8 if kind == 1 else 4
            continue
        yield key >> 3, val


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _varints(packed) -> List[int]:
    out, i = [], 0
    while i < len(packed):
        v, i = _varint(packed, i)
        out.append(v)
    return out


def hlo_scopes(data: bytes) -> Dict[str, Dict[str, str]]:
    """``{program: {instruction: scope}}`` from the optimized HLO that
    the profiler keeps beside the device trace (the ``/host:metadata``
    plane, one ``Hlo Proto`` per program, named like the program's
    ``XLA Modules`` events).  An instruction takes the scope of its op
    path; a fusion whose own path names none takes the scope most of its
    fused instructions carry.  The XLA op events carry neither path nor
    scope, only the instruction's text.

    Protobuf fields read: ``XSpace.planes`` 1; ``XPlane.name`` 2 and
    ``event_metadata`` 4 (map: value 2); ``XEventMetadata.name`` 2 and
    ``stats`` 5; ``XStat.bytes_value`` 6; ``HloProto.hlo_module`` 1;
    ``HloModuleProto.computations`` 3; ``HloComputationProto``
    ``instructions`` 2, ``id`` 5; ``HloInstructionProto`` ``name`` 1,
    ``opcode`` 2, ``metadata`` 7, ``called_computation_ids`` 38;
    ``OpMetadata.op_name`` 2."""
    out = {}
    for num, plane in _fields(memoryview(data)):
        if num != 1:
            continue
        parts = list(_fields(plane))
        if not any(f == 2 and bytes(v) == b"/host:metadata" for f, v in parts):
            continue
        for f, entry in parts:
            meta = [v for k, v in _fields(entry) if k == 2] if f == 4 else []
            for m in meta:
                fm = list(_fields(m))
                name = next((bytes(v).decode() for k, v in fm if k == 2), "")
                for k, stat in fm:
                    if k != 5:
                        continue
                    for sk, proto in _fields(stat):
                        if sk != 6:
                            continue
                        try:
                            out[name] = _module_scopes(proto)
                        except (IndexError, StopIteration, UnicodeDecodeError):
                            pass  # not an HLO module: its ops keep no scope
    return out


def _module_scopes(proto) -> Dict[str, str]:
    module = next(v for k, v in _fields(proto) if k == 1)
    comps = {}  # computation id -> [(name, opcode, scope, called ids)]
    for k, comp in _fields(module):
        if k != 3:
            continue
        cid, insts = None, []
        for ck, cv in _fields(comp):
            if ck == 5:
                cid = cv
            elif ck == 2:
                name = opcode = path = ""
                called = []
                for ik, iv in _fields(cv):
                    if ik == 1:
                        name = bytes(iv).decode()
                    elif ik == 2:
                        opcode = bytes(iv).decode()
                    elif ik == 7:
                        path = next((bytes(v).decode() for mk, v in _fields(iv)
                                     if mk == 2), "")
                    elif ik == 38:
                        called += (_varints(iv) if isinstance(iv, memoryview)
                                   else [iv])
                insts.append((name, opcode, scope_of([path]), called))
        comps[cid] = insts
    scopes = {}
    for insts in comps.values():
        for name, opcode, scope, called in insts:
            if scope is None and opcode == "fusion":
                inner = collections.Counter(
                    sc for c in called for _, _, sc, _ in comps.get(c, ())
                    if sc is not None)
                scope = inner.most_common(1)[0][0] if inner else None
            if scope is not None:
                scopes[name] = scope
    return scopes


def load(trace_dir) -> Program:
    """The program's spans and device 0's operations, each operation
    with its scope (:func:`hlo_scopes` of the program that ran it, or
    the scope its name gives), from the newest trace under
    ``trace_dir``."""
    from jax.profiler import ProfileData

    path = trace_file(trace_dir)
    pd = ProfileData.from_file(str(path))
    spans, devices = [], {}
    for plane in pd.planes:
        name = plane.name
        if name.startswith("/host:"):
            spans += [Span(e.name, e.start_ns * 1e-9,
                           (e.start_ns + e.duration_ns) * 1e-9, dict(e.stats))
                      for line in plane.lines for e in line.events
                      if is_program_span(e.name)]
        elif name.startswith("/device:TPU:") and "Core" not in name:
            try:
                devices[int(name.rsplit(":", 1)[1])] = plane
            except ValueError:
                continue
    spans.sort(key=lambda s: (s.start, -s.end))
    if not devices:
        return Program(spans, [])
    lines = {line.name: line for line in devices[min(devices)].lines}
    if "XLA Ops" not in lines:
        return Program(spans, [])
    by_program = hlo_scopes(path.read_bytes())
    bases = collections.Counter(n.split("(")[0] for n in by_program)
    by_base = {n.split("(")[0]: m for n, m in by_program.items()
               if bases[n.split("(")[0]] == 1}
    runs = sorted(((e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for e in lines["XLA Modules"].events)
                  if "XLA Modules" in lines else [])
    starts = [r[0] for r in runs]
    ops = []
    for e in lines["XLA Ops"].events:
        k = bisect.bisect_right(starts, e.start_ns) - 1
        program = runs[k][2] if k >= 0 and e.start_ns < runs[k][1] else None
        inst = e.name.split(" = ", 1)[0].lstrip("%")
        known = by_program.get(program) or by_base.get(
            (program or "").split("(")[0], {})
        scope = known.get(inst) or scope_of([e.name])
        ops.append(Op(e.name, e.start_ns * 1e-9,
                      (e.start_ns + e.duration_ns) * 1e-9, scope))
    return Program(spans, ops)


def of(ctx) -> Program:
    """:func:`load` of the trace ``bench/run.py`` wrote, once, kept on
    the readers' context (``bench/readers.Context`` predates it).  No
    trace there: nothing."""
    prog = getattr(ctx, "program", None)
    if prog is None:
        try:
            prog = load(TRACE_DIR)
        except FileNotFoundError:
            prog = Program([], [])
        ctx.program = prog
    return prog


def scope_seconds(ops, scope: str, t0: float, t1: float) -> float:
    """Device seconds of ``[t0, t1]`` in which an operation of ``scope``
    runs (the union, so nested events count once)."""
    return tracing.busy([(n, s, e) for n, s, e, sc in ops if sc == scope],
                        t0, t1)


def top_ops_by_scope(ops, t0: float, t1: float, n: int = 10):
    """``bench/tracing.top_ops`` with each operation's scope after its
    short name: ``%paged_attn.3 custom-call tpu_custom_call [paged_attn]``."""
    agg: Dict[str, float] = collections.defaultdict(float)
    named = [(f"{tracing.short_name(nm)} [{sc}]" if sc else tracing.short_name(nm),
              s, e) for nm, s, e, sc in ops]
    for name, secs in tracing.self_times(named, t0, t1):
        agg[name] += secs
    return [[k, v] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])[:n]]


def within(spans: Sequence[Span], t0: float, t1: float, name: str):
    """Spans called ``name`` that start inside ``[t0, t1]``."""
    return [s for s in spans if s.name == name and t0 <= s.start <= t1]


def dispatches(spans: Sequence[Span], t0: float, t1: float) -> List[Span]:
    """``serve.iteration`` spans of the window that ran a dispatch (an
    idle iteration, which only advances the clock, is ``kind`` tick)."""
    return [s for s in within(spans, t0, t1, "serve.iteration")
            if s.args.get("kind") not in (None, "tick")]


def children(spans: Sequence[Span], parent: Span, name: str) -> List[Span]:
    return [s for s in spans if s.name == name
            and parent.start <= s.start and s.end <= parent.end]


def host_seconds(spans: Sequence[Span], it: Span) -> float:
    """An iteration's time outside its blocking reads (``serve.wait``):
    what the host spent planning, dispatching, sampling and committing."""
    return it.seconds - sum(w.seconds for w in children(spans, it, "serve.wait"))


def deepest(spans: Sequence[Span], s: float, e: float) -> Optional[Span]:
    """The shortest span that covers more than half of ``[s, e]``; the
    spans nest, so that is the deepest one."""
    best = None
    for sp in spans:
        if sp.start >= e:
            break
        cover = min(sp.end, e) - max(sp.start, s)
        if cover * 2 > e - s and (best is None or sp.seconds < best.seconds):
            best = sp
    return best


def gap_labels(trace: tracing.Trace, spans: Sequence[Span], t0: float,
               t1: float, n: int = 10):
    """``bench/tracing.label_gaps`` with each label led by the deepest
    program span over the gap (``no span`` where none covers most of
    it): ``serve.commit | jit_paged_step -> jit_sample_at, 0 commits``."""
    gaps = sorted(tracing.idle_gaps(trace.ops, t0, t1),
                  key=lambda g: g[0] - g[1])[:n]
    out = []
    for (s, e), (label, secs) in zip(gaps, tracing.label_gaps(trace, t0, t1, n)):
        sp = deepest(spans, s, e)
        out.append([f"{sp.name if sp else NO_SPAN} | {label}", secs])
    return out


def idle_by_span(trace: tracing.Trace, spans: Sequence[Span], t0: float,
                 t1: float):
    """``[[span name, seconds]]``: every device-idle second of the window
    put down to the deepest program span open at that time (``no span``
    where none is), largest first."""
    cuts = sorted({t0, t1} | {t for sp in spans for t in (sp.start, sp.end)
                              if t0 < t < t1})
    names = []  # the deepest open span of each [cuts[i], cuts[i + 1]]
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        open_ = [sp for sp in spans if sp.start <= mid < sp.end]
        names.append(min(open_, key=lambda sp: sp.seconds).name
                     if open_ else NO_SPAN)
    agg: Dict[str, float] = collections.defaultdict(float)
    for s, e in tracing.idle_gaps(trace.ops, t0, t1):
        i = max(0, bisect.bisect_right(cuts, s) - 1)
        while i < len(names) and cuts[i] < e:
            agg[names[i]] += min(e, cuts[i + 1]) - max(s, cuts[i])
            i += 1
    return [[k, v] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])]


def main(argv=None) -> int:
    trace_dir = pathlib.Path((argv or sys.argv[1:] or [TRACE_DIR])[0])
    trace = tracing.load(trace_dir)
    spans, ops = load(trace_dir)
    try:
        t0, t1 = trace.window()  # the harness's burst
    except ValueError:  # a trace taken around serve_requests calls
        calls = [s for s in spans if s.name == "serve.requests"]
        if not calls:
            raise
        t0, t1 = min(s.start for s in calls), max(s.end for s in calls)
    its = dispatches(spans, t0, t1)
    kinds = collections.Counter(s.args.get("kind") for s in its)
    host = [host_seconds(spans, it) for it in its]
    out = {
        "window_s": t1 - t0,
        "busy_s": tracing.busy(trace.ops, t0, t1),
        "iterations": dict(kinds),
        "host_step_ms": 1e3 * sum(host) / len(host) if host else None,
        "host_step_ms_max": 1e3 * max(host) if host else None,
        "idle_by_span": idle_by_span(trace, spans, t0, t1),
        "idle_gaps": gap_labels(trace, spans, t0, t1),
        "device_ops": top_ops_by_scope(ops, t0, t1),
        "scope_s": {sc: scope_seconds(ops, sc, t0, t1) for sc in SCOPES},
        "gc": [[s.args.get("generation"), s.seconds]
               for s in within(spans, t0, t1, "python.gc")
               if s.seconds > 1e-3],
    }
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
