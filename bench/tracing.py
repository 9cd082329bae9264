"""Reduction of a profiler trace to what the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps,
in seconds on the trace's own clock, three kinds of interval:

* device operations (the ``XLA Ops`` line of each TPU device plane),
* device programs (its ``XLA Modules`` line: one event per execution of
  a jitted program),
* host spans the harness annotated (``burst`` around each
  ``serve_requests`` call, ``on_token`` at each commit callback).

Everything else works on plain ``(name, start, end)`` tuples, so it is
tested on small synthetic traces.  Interval arithmetic clips to the
traced window: the ``burst`` span.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import pathlib
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[str, float, float]  # (name, start s, end s)

HOST_SPANS = ("burst", "on_token")


@dataclasses.dataclass
class Trace:
    ops: List[Interval]  # device 0's operations
    modules: List[Interval]  # device 0's program executions
    host: List[Interval]  # harness spans
    n_devices: int
    device_ops: Dict[int, List[Interval]]  # every device's operations

    def window(self) -> Tuple[float, float]:
        bursts = [h for h in self.host if h[0] == "burst"]
        if not bursts:
            raise ValueError("trace has no 'burst' span")
        return min(b[1] for b in bursts), max(b[2] for b in bursts)


def _events(line) -> List[Interval]:
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def load(trace_dir: pathlib.Path) -> Trace:
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    device_ops: Dict[int, List[Interval]] = {}
    modules: Dict[int, List[Interval]] = {}
    host: List[Interval] = []
    for plane in pd.planes:
        name = plane.name
        if name.startswith("/device:TPU:") and "Core" not in name:
            try:
                idx = int(name.rsplit(":", 1)[1])
            except ValueError:
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device_ops[idx] = _events(line)
                elif line.name == "XLA Modules":
                    modules[idx] = _events(line)
        elif name.startswith("/host:"):
            for line in plane.lines:
                host.extend(ev for ev in _events(line) if ev[0] in HOST_SPANS)
    first = min(device_ops) if device_ops else None
    return Trace(
        ops=device_ops.get(first, []),
        modules=modules.get(first, []),
        host=host,
        n_devices=len(device_ops),
        device_ops=device_ops,
    )


def clip(intervals: Sequence[Interval], t0: float, t1: float) -> List[Interval]:
    out = []
    for name, s, e in intervals:
        s, e = max(s, t0), min(e, t1)
        if e > s:
            out.append((name, s, e))
    return out


def merged(intervals: Sequence[Interval]) -> List[Tuple[float, float]]:
    """The union of the intervals as disjoint sorted spans."""
    spans: List[List[float]] = []
    for _, s, e in sorted(intervals, key=lambda iv: iv[1]):
        if spans and s <= spans[-1][1]:
            spans[-1][1] = max(spans[-1][1], e)
        else:
            spans.append([s, e])
    return [(s, e) for s, e in spans]


def busy(intervals: Sequence[Interval], t0: float, t1: float) -> float:
    """Seconds of ``[t0, t1]`` in which at least one interval runs."""
    return sum(e - s for s, e in merged(clip(intervals, t0, t1)))


def idle_gaps(intervals: Sequence[Interval], t0: float, t1: float):
    """``[(start, end)]`` of ``[t0, t1]`` covered by no interval."""
    gaps, cur = [], t0
    for s, e in merged(clip(intervals, t0, t1)):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        gaps.append((cur, t1))
    return gaps


def short_name(name: str) -> str:
    """An XLA op event's name is its whole HLO instruction; keep the
    instruction's name and its opcode (and a custom call's target)."""
    lhs, _, rhs = name.partition(" = ")
    if not rhs:
        return name
    m = re.search(r"\s([a-z][\w\-.]*)\(", rhs)
    out = f"{lhs} {m.group(1)}" if m else lhs
    t = re.search(r'custom_call_target="([^"]+)"', rhs)
    return f"{out} {t.group(1)}" if t else out


def self_times(intervals: Sequence[Interval], t0: float, t1: float):
    """``[(name, seconds)]``: each operation's time in the window less the
    time of the operations nested inside it (a loop's body ops nest in
    the loop's own event)."""
    evs = sorted(clip(intervals, t0, t1), key=lambda iv: (iv[1], -iv[2]))
    out: List[list] = []
    stack: List[int] = []  # indices into out of the open enclosing events
    for name, s, e in evs:
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            out[stack[-1]][3] -= e - s
        out.append([name, s, e, e - s])
        stack.append(len(out) - 1)
    return [(name, self_s) for name, _, _, self_s in out]


def top_ops(intervals: Sequence[Interval], t0: float, t1: float, n: int = 10):
    """``[[name, seconds]]`` of the ``n`` operations (by short name) that
    took most device self time in the window."""
    agg: Dict[str, float] = collections.defaultdict(float)
    for name, secs in self_times(intervals, t0, t1):
        agg[short_name(name)] += secs
    return [[k, v] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])[:n]]


def _before(intervals: Sequence[Interval], t: float) -> Optional[str]:
    best = None
    for name, s, e in intervals:
        if e <= t and (best is None or e > best[1]):
            best = (name, e)
    return best[0] if best else None


def _after(intervals: Sequence[Interval], t: float) -> Optional[str]:
    best = None
    for name, s, e in intervals:
        if s >= t and (best is None or s < best[1]):
            best = (name, s)
    return best[0] if best else None


def label_gaps(trace: Trace, t0: float, t1: float, n: int = 10):
    """``[[label, seconds]]`` of the ``n`` longest device-idle gaps.  A
    label names the programs the gap lies between and how many token
    commits (``on_token`` spans) the host made in it."""
    gaps = sorted(idle_gaps(trace.ops, t0, t1), key=lambda g: g[0] - g[1])[:n]
    commits = sorted(h[1] for h in trace.host if h[0] == "on_token")
    out = []
    for s, e in gaps:
        prev = _before(trace.modules, s) or "window start"
        nxt = _after(trace.modules, e) or "window end"
        k = bisect.bisect_left(commits, e) - bisect.bisect_left(commits, s)
        out.append([f"{prev} -> {nxt}, {k} commits", e - s])
    return out


def split_programs(modules: Sequence[Interval], t0: float, t1: float):
    """Executions of the continuous loop's two programs in the window:
    ``(mixed, decode)``.  The mixed step is the program that the sampling
    program (``sample_at``) follows; the fused decode loop samples inside
    and is, of the other programs, the one that takes most device time
    (small helpers, such as a scalar's conversion, run beside it).  The
    caller checks the counts against the engine's counters."""
    evs = sorted(clip(modules, t0, t1), key=lambda iv: iv[1])
    followed = collections.Counter(
        ev[0] for ev, nxt in zip(evs, evs[1:])
        if "sample_at" in nxt[0] and "sample_at" not in ev[0])
    mixed_name = followed.most_common(1)[0][0] if followed else None
    total: Dict[str, float] = collections.defaultdict(float)
    for name, s, e in evs:
        if name != mixed_name and "sample_at" not in name:
            total[name] += e - s
    decode_name = max(total, key=total.get) if total else None
    return ([ev for ev in evs if ev[0] == mixed_name],
            [ev for ev in evs if ev[0] == decode_name])
