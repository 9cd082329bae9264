"""Runtime health: step-time tracking, straggler detection, preemption
hooks, and the host spans of the profiler trace.  On a real multi-host
deployment each host reports its step wall time; hosts whose rolling
time exceeds the fleet median by ``threshold``× are flagged (and, with
an orchestrator, drained/replaced).  Here the same logic runs over
per-step samples so it is fully unit-tested.

Spans (:class:`Span`, :func:`install_gc_spans`) are
``jax.profiler.TraceAnnotation`` events: while a profiler trace records,
they land on the host plane of the same trace as the device's programs
and operations, on its clock; otherwise each costs one check of whether
a trace is active.
"""

from __future__ import annotations

import collections
import gc
import statistics
import time

from jax.profiler import TraceAnnotation


class StepTimer:
    def __init__(self, window: int = 20):
        self.times = collections.deque(maxlen=window)
        self._t0 = None

    def start(self):
        self._t0 = time.perf_counter()

    def stop(self) -> float:
        dt = time.perf_counter() - self._t0
        self.times.append(dt)
        return dt

    @property
    def median(self) -> float:
        return statistics.median(self.times) if self.times else 0.0


def percentile(samples, q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]) — 0.0 on empty input.

    Deliberately dependency-free and deterministic so ``Engine.health()``
    can surface step-time p50/p99 without numpy on the host path.
    """
    if not samples:
        return 0.0
    xs = sorted(samples)
    idx = min(len(xs) - 1, max(0, int(round(q / 100.0 * (len(xs) - 1)))))
    return xs[idx]


class HangWatchdog:
    """Flags individual steps whose wall time exceeds ``threshold``× the
    rolling median — the single-host analogue of :class:`StragglerDetector`
    (there the unit is a host, here it is an iteration).  ``note(dt)``
    returns True when ``dt`` is a straggler step; the caller decides what
    to do (the serving engine bumps a ``health()`` counter and logs once).

    Straggler samples still enter the window — a *persistently* slow phase
    (e.g. a recompile storm) raises the median and stops re-flagging, so
    the watchdog detects discontinuities, not steady load.
    """

    def __init__(self, threshold: float = 10.0, window: int = 20, min_samples: int = 5):
        if threshold <= 1.0:
            raise ValueError(f"threshold must be > 1, got {threshold}")
        self.threshold = threshold
        self.min_samples = min_samples
        self.times = collections.deque(maxlen=window)
        self.trips = 0

    def note(self, dt: float) -> bool:
        slow = False
        if len(self.times) >= self.min_samples:
            med = statistics.median(self.times)
            if med > 0 and dt > self.threshold * med:
                slow = True
                self.trips += 1
        self.times.append(dt)
        return slow


class StragglerDetector:
    """Flags hosts whose rolling median step time exceeds the fleet median
    by ``threshold``x (default 1.5x, typical production setting)."""

    def __init__(self, n_hosts: int, window: int = 20, threshold: float = 1.5):
        self.threshold = threshold
        self.hosts = [collections.deque(maxlen=window) for _ in range(n_hosts)]

    def report(self, host_id: int, step_time: float):
        self.hosts[host_id].append(step_time)

    def stragglers(self):
        meds = [
            statistics.median(h) if h else None for h in self.hosts
        ]
        known = [m for m in meds if m is not None]
        if not known:
            return []
        fleet = statistics.median(known)
        return [
            i
            for i, m in enumerate(meds)
            if m is not None and fleet > 0 and m > self.threshold * fleet
        ]


class PreemptionGuard:
    """Cooperative preemption: orchestrators signal shutdown; the training
    loop checks ``should_stop`` each step and checkpoints before exit."""

    def __init__(self):
        self._stop = False

    def signal(self):
        self._stop = True

    @property
    def should_stop(self) -> bool:
        return self._stop


def tracing() -> bool:
    """True while a profiler trace records.  Guard span arguments that
    cost work to build with it."""
    return TraceAnnotation.is_enabled()


class Span:
    """A named host span: a profiler ``TraceAnnotation`` that also keeps
    its own wall time (``seconds``, ``time.perf_counter``), so a caller
    times a region and traces it with one pair of stamps.

    ``args`` become the span's arguments in the trace; :meth:`annotate`
    adds more once they are known (a no-op while no trace records)."""

    __slots__ = ("_ann", "t0", "seconds")

    def __init__(self, name: str, **args):
        self._ann = TraceAnnotation(name, **args)
        self.t0 = 0.0
        self.seconds = 0.0

    def __enter__(self) -> "Span":
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.seconds = time.perf_counter() - self.t0
        self._ann.__exit__(*exc)
        return False

    def annotate(self, **args) -> None:
        self._ann.set_metadata(**args)


class _GcSpans:
    """``gc.callbacks`` hook: one ``python.gc`` span per collection,
    argument ``generation``, recorded only while a trace records."""

    def __init__(self):
        self._open = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            if TraceAnnotation.is_enabled():
                self._open = TraceAnnotation(
                    "python.gc", generation=info["generation"]
                )
                self._open.__enter__()
        elif self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None


def install_gc_spans() -> None:
    """Trace every garbage collection of this process as a ``python.gc``
    span (idempotent)."""
    if not any(isinstance(cb, _GcSpans) for cb in gc.callbacks):
        gc.callbacks.append(_GcSpans())
