"""Process-wide counter registry: compile/dispatch accounting, always on.

A :class:`CounterRegistry` is a thread-safe map of monotonically-increasing
integer counters. The module-level singleton :data:`counters` is the one the
framework feeds:

- ``compiles`` — every XLA trace+compile in the process, counted by the
  session-wide promotion of the retrace sentinel's compile counting
  (:func:`ensure_compile_counter`; see
  :mod:`evotorch_tpu.analysis.retrace_sentinel`). A warmed-up run
  incrementing this counter IS a steady-state retrace — the runtime form
  of graftlint's ``retrace`` checker.
- ``trace_spans`` — spans recorded by the host tracer
  (:mod:`~evotorch_tpu.observability.tracer`); 0 while tracing is off.
- ``telemetry_fetches`` — device->host decodes of the packed eval-telemetry
  vector (:meth:`~evotorch_tpu.observability.devicemetrics.EvalTelemetry.from_array`).
  Each fetch is one ~24-byte transfer of an already-materialized program
  output; this counter exists so "zero extra transfers" is auditable.

Beyond the integer counters, the registry carries two program-ledger
companions (PR 9, :mod:`~evotorch_tpu.observability.programs`):

- ``compile_seconds`` — a FLOAT accumulator of compile-pipeline wall time
  (trace + MLIR lowering + backend compile), fed by jax's monitoring
  duration events via :func:`ensure_compile_timer` — the wall-clock twin
  of the ``compiles`` count.
- ``peak_hbm_bytes`` — a max-gauge over every ledger-captured program's
  analyzed peak footprint (:meth:`CounterRegistry.observe_max`).

``SearchAlgorithm.step`` snapshots the registry around each generation and
publishes the per-step deltas as status keys (``compiles``, ``trace_spans``,
``telemetry_fetches``, ``compile_seconds``) plus the absolute
``peak_hbm_bytes`` gauge, so every logger sees them for free.
"""

from __future__ import annotations

import threading
from typing import Dict, Iterable, Optional

__all__ = [
    "CounterRegistry",
    "counters",
    "ensure_compile_counter",
    "ensure_compile_timer",
]


class CounterRegistry:
    """Thread-safe named meters: monotonically-increasing counters
    (:meth:`increment` int, :meth:`accumulate` float) and high-water-mark
    gauges (:meth:`observe_max`)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counts: Dict[str, float] = {}

    def increment(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + int(n)

    def accumulate(self, name: str, value: float) -> None:
        """Float-valued increment (e.g. seconds); keeps the same snapshot /
        delta discipline as the integer counters."""
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + float(value)

    def observe_max(self, name: str, value: float) -> None:
        """High-water-mark gauge: the stored value only ever rises."""
        with self._lock:
            if value > self._counts.get(name, 0):
                self._counts[name] = value

    def get(self, name: str):
        with self._lock:
            return self._counts.get(name, 0)

    def snapshot(self, names: Optional[Iterable[str]] = None) -> Dict[str, int]:
        """A point-in-time copy — pair two snapshots with :meth:`delta` to
        meter a code region."""
        with self._lock:
            if names is None:
                return dict(self._counts)
            return {n: self._counts.get(n, 0) for n in names}

    def delta(self, since: Dict[str, int]) -> Dict[str, int]:
        """Counter increases since a prior :meth:`snapshot` (only the keys of
        ``since`` are reported, so a snapshot doubles as a key filter)."""
        with self._lock:
            return {n: self._counts.get(n, 0) - v for n, v in since.items()}


#: the process-wide registry every subsystem feeds
counters = CounterRegistry()


_compile_sink = None
_compile_lock = threading.Lock()


class _CompileCounterSink:
    """A permanent retrace-sentinel sink feeding ``counters['compiles']``."""

    def record(self, name: str) -> None:
        counters.increment("compiles")


def ensure_compile_counter() -> None:
    """Promote the retrace sentinel's compile counting to session scope:
    every XLA compile from now on increments ``counters['compiles']``.

    Idempotent and cheap to call anywhere a hot loop starts (searchers call
    it on construction). Composes with test-scoped
    :func:`~evotorch_tpu.analysis.retrace_sentinel.track_compiles` blocks —
    the sentinel's sink list is shared and nestable."""
    global _compile_sink
    with _compile_lock:
        if _compile_sink is not None:
            return
        from ..analysis import retrace_sentinel

        _compile_sink = _CompileCounterSink()
        retrace_sentinel.register_sink(_compile_sink)


_timer_installed = False


def _on_duration_event(event: str, duration: float, **_kwargs) -> None:
    """jax.monitoring duration listener: accumulate the compile pipeline's
    wall time (trace + jaxpr->MLIR + backend compile all emit under the
    ``/jax/core/compile/`` prefix) into ``counters['compile_seconds']``."""
    if event.startswith("/jax/core/compile/"):
        counters.accumulate("compile_seconds", duration)


def ensure_compile_timer() -> None:
    """Session-scope compile WALL-TIME accounting — the duration twin of
    :func:`ensure_compile_counter`: from the first call on, every compile's
    trace/lower/backend-compile durations accumulate into
    ``counters['compile_seconds']`` via jax's monitoring events.

    Idempotent."""
    global _timer_installed
    with _compile_lock:
        if _timer_installed:
            return
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_duration_event)
        _timer_installed = True
