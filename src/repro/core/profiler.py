"""Profiling — ASA Algorithm 1 lines 6-7 and the re-profile trigger (21-23),
and the host spans of the trainer and the serving engine.

  * ComponentProfiler — measures wall-time of jitted per-component apply fns
    (initial profiling phase).  On CPU this measures the smoke-scale configs;
    on TPU the same harness times the real blocks.  Measurements are turned
    into *calibration factors* (measured / predicted) for the cost model.
  * StepMonitor — EMA of live step times; signals drift (paper: "if
    communication patterns changed significantly -> re-profile").
  * Span — one host phase as a ``jax.profiler.TraceAnnotation``: on the
    profiler's clock, beside the device's operations, whenever a profiler
    trace runs; optionally also timed by a caller's clock and handed to a
    sink (the engine's phase histograms and Chrome tracer).
  * compile_count — traces and compiles made in this process so far, so a
    caller can say which step compiled.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Optional

import jax


@dataclasses.dataclass
class ProfileResult:
    name: str
    mean_s: float
    n: int


def time_fn(fn: Callable, *args, iters: int = 5, warmup: int = 2) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters


class ComponentProfiler:
    """Times per-component jitted fns and derives calibration factors."""

    def __init__(self):
        self.measurements: dict[str, ProfileResult] = {}

    def profile(self, name: str, fn: Callable, *args,
                iters: int = 5) -> ProfileResult:
        mean = time_fn(jax.jit(fn), *args, iters=iters)
        res = ProfileResult(name, mean, iters)
        self.measurements[name] = res
        return res

    def calibration(self, predicted: dict[str, float]) -> dict[str, float]:
        """measured/predicted per component (1.0 when unmeasured)."""
        out = {}
        for name, pred in predicted.items():
            m = self.measurements.get(name)
            if m is not None and pred > 0:
                out[name] = max(m.mean_s / pred, 1e-3)
        return out


class StepMonitor:
    """EMA step-time drift detector -> re-profile trigger: the trainer
    (through ``AdaptiveScheduler.record_step``) feeds step wall times and
    re-plans when ``update`` returns True."""

    def __init__(self, alpha: float = 0.1, drift_threshold: float = 0.25,
                 min_steps: int = 20):
        self.alpha = alpha
        self.threshold = drift_threshold
        self.min_steps = min_steps
        self.ema: Optional[float] = None
        self.baseline: Optional[float] = None
        self.steps = 0

    def update(self, step_time_s: float) -> bool:
        """Record one step; returns True when drift warrants re-planning."""
        self.steps += 1
        self.ema = (step_time_s if self.ema is None
                    else (1 - self.alpha) * self.ema + self.alpha * step_time_s)
        if self.baseline is None and self.steps >= self.min_steps:
            self.baseline = self.ema
        if self.baseline is None or self.steps < self.min_steps:
            return False
        drift = abs(self.ema - self.baseline) / self.baseline
        if drift > self.threshold:
            self.baseline = self.ema      # re-arm after trigger
            return True
        return False


# ---------------------------------------------------------------------------
# host spans on the profiler's clock
# ---------------------------------------------------------------------------

class Span:
    """One host phase: ``with Span(name, **args) as sp: ...``.

    It opens ``jax.profiler.TraceAnnotation(name, **args)``, which costs
    a microsecond or two when no profiler runs and otherwise puts the span,
    with ``args`` as its stats, on the host plane of the profiler's trace,
    on the device trace's clock.  ``sp.set(**args)`` adds arguments known
    only once the work is done.  Given a ``clock``, the span also stamps
    ``t0``/``t1`` from it and, still inside the annotation, hands itself to
    ``sink(span)``.  Arguments are built by the caller from values at hand:
    a span does no device read of its own.
    """

    __slots__ = ("name", "args", "clock", "sink", "t0", "t1", "_ann")

    def __init__(self, name: str, clock: Optional[Callable[[], float]] = None,
                 sink: Optional[Callable[["Span"], None]] = None, **args):
        self.name, self.args, self.clock, self.sink = name, args, clock, sink
        self.t0 = self.t1 = None

    def __enter__(self) -> "Span":
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.args)
        self._ann.__enter__()
        if self.clock is not None:
            self.t0 = self.clock()
        return self

    def set(self, **args) -> None:
        self.args.update(args)
        self._ann.set_metadata(**args)

    def __exit__(self, *exc) -> None:
        try:
            if self.clock is not None:
                self.t1 = self.clock()
                if self.sink is not None:
                    self.sink(self)
        finally:
            self._ann.__exit__(*exc)


# jax.monitoring's duration events that mean the host traced or compiled.
# The backend event wraps ``compile_or_get_cached``, so an executable loaded
# from the persistent compilation cache counts there too (jax 0.9).
COMPILE_EVENTS = frozenset({
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/backend_compile_duration",
})
_compiles = 0
_listening = False
_lock = threading.Lock()


def _on_duration(event: str, duration_s: float, **kwargs) -> None:
    global _compiles
    if event in COMPILE_EVENTS:
        with _lock:
            _compiles += 1


def compile_count() -> int:
    """Traces plus compiles (or persistent-cache loads) this process has
    made since the first call; the difference across a dispatch says
    whether that dispatch compiled.  A first call of a jitted function
    adds at least two (its trace and its compile)."""
    global _listening
    with _lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _listening = True
        return _compiles
