"""Runtime retrace sentinel: count XLA compilations over a code region.

The static side (:mod:`evotorch_tpu.analysis.checkers`) catches retrace
*hazards*; this is the runtime ground truth. jax's pxla emits exactly one
``"Compiling <name> with global shapes ..."`` log record per actual
trace+compile (executable-cache misses; persistent-compilation-cache hits
still log, which is correct — a dispatch-cache miss IS a retrace, the
persistent cache only makes it cheaper). The record is logged at DEBUG
level unconditionally (``jax.log_compiles`` merely promotes it to
WARNING), so the sentinel needs no jax config at all: one counting handler
on the emitting logger, with the logger level pinned to DEBUG. A canary
test (``tests/test_retrace_sentinel.py``) guards against the log format
drifting out from under us on a jax upgrade.

The handler is installed ONCE per process and fans records out to a
registry of active sinks, which makes compile counting **nestable and
thread-safe**: overlapping :func:`track_compiles` blocks each see every
compile (sink scope is the whole process — XLA compiles on whichever
thread dispatches first, so per-thread scoping would undercount), and a
permanent sink can promote the counting to session scope — that is how the
always-on observability registry's ``compiles`` counter works
(:func:`evotorch_tpu.observability.registry.ensure_compile_counter`).

Usage::

    with track_compiles() as log:
        step(state, key)
    assert log.count == 0            # steady state: nothing recompiled

    with assert_compiles(0):         # raises RetraceError otherwise
        for _ in range(3):
            state, scores = step(state, key)

Tests wrap the four eval contracts (budget / episodes / episodes_compact /
episodes_refill) and the jitted PGPE/SNES ask-tell steps with this, so any
change that starts retracing in steady state fails the fast tier.
"""

from __future__ import annotations

import contextlib
import logging
import re
import threading
from dataclasses import dataclass, field
from typing import List, Optional

__all__ = [
    "CompileLog",
    "RetraceError",
    "track_compiles",
    "assert_compiles",
    "register_sink",
    "unregister_sink",
]

# the logger that emits exactly one "Compiling <name> with global shapes"
# record per trace+lower (jax/_src/interpreters/pxla.py; tests/
# test_retrace_sentinel.py and chip_smoke.py each first show the sentinel
# counting a compile they know happened, in case the line ever moves)
_PXLA_LOGGER = "jax._src.interpreters.pxla"
_COMPILE_RE = re.compile(r"^Compiling (\S+) with global shapes")
# siblings jax.log_compiles turns chatty when a CALLER enabled it; quiet=True
# keeps them off the console while a tracking block is active
_NOISY_LOGGERS = ("jax._src.dispatch", "jax._src.compiler")


class RetraceError(AssertionError):
    """Raised by :func:`assert_compiles` when a region compiled more than its
    budget — a steady-state retrace."""


@dataclass(eq=False)
class CompileLog:
    """Names of the programs compiled while tracking was active.

    ``eq=False``: logs are registry entries, and registry membership is by
    IDENTITY — value equality (two logs that happened to observe the same
    records) once made ``unregister_sink`` remove the wrong sink (see its
    docstring)."""

    names: List[str] = field(default_factory=list)

    def record(self, name: str) -> None:
        """Sink protocol: called once per observed compile (any thread;
        ``list.append`` is atomic under the GIL)."""
        self.names.append(name)

    @property
    def count(self) -> int:
        return len(self.names)

    def count_matching(self, substring: str) -> int:
        return sum(1 for n in self.names if substring in n)


# ---------------------------------------------------------------------------
# the shared dispatch handler + sink registry
# ---------------------------------------------------------------------------

_LOCK = threading.RLock()
_SINKS: List = []  # objects with .record(name); mutated under _LOCK
_INSTALLED = False
_QUIET_DEPTH = 0
_QUIET_SAVED: Optional[list] = None
_QUIET_NULL = logging.NullHandler()


class _DispatchHandler(logging.Handler):
    """The one handler on the pxla logger: matches compile records and fans
    them out to every registered sink."""

    def emit(self, record: logging.LogRecord) -> None:
        m = _COMPILE_RE.match(record.getMessage())
        if m is None:
            return
        name = m.group(1)
        with _LOCK:
            sinks = list(_SINKS)
        for sink in sinks:
            sink.record(name)


def _ensure_installed() -> None:
    """Install the dispatch handler once: the pxla logger is pinned to DEBUG
    so the per-compile record (DEBUG-level without ``jax.log_compiles``)
    always reaches the handler, and propagation is turned off so the
    records feed the counter instead of the console — once the sentinel is
    in use, the sentinel owns this logger (``jax.log_compiles`` console
    chatter from it is intentionally absorbed; the counting is the
    observable)."""
    global _INSTALLED
    with _LOCK:
        if _INSTALLED:
            return
        logger = logging.getLogger(_PXLA_LOGGER)
        logger.addHandler(_DispatchHandler())
        if logger.level == logging.NOTSET or logger.level > logging.DEBUG:
            logger.setLevel(logging.DEBUG)
        logger.propagate = False
        _INSTALLED = True


def register_sink(sink) -> None:
    """Add a permanent sink (an object with ``record(name: str)``) that sees
    every subsequent compile — the session-wide promotion of
    :class:`CompileLog`. Thread-safe; compose freely with
    :func:`track_compiles` blocks."""
    _ensure_installed()
    with _LOCK:
        _SINKS.append(sink)


def unregister_sink(sink) -> None:
    """Remove a sink by IDENTITY, never equality: ``list.remove`` removes
    the first ``==`` element, and two value-equal sinks (e.g. nested
    ``CompileLog``s that observed the same records — the common case for
    overlapping blocks) would make one block's exit silently unregister
    the OTHER block's sink, which then misses every later compile."""
    with _LOCK:
        for i, registered in enumerate(_SINKS):
            if registered is sink:
                del _SINKS[i]
                return


def _push_quiet() -> None:
    """Refcounted console silencing of the SIBLING loggers (the pxla logger
    itself is owned outright by the handler install): while any quiet
    tracking block is active, a caller-enabled ``jax.log_compiles`` cannot
    spray dispatch/compiler chatter. A NullHandler keeps the handler-less
    siblings off ``logging.lastResort``."""
    global _QUIET_DEPTH, _QUIET_SAVED
    with _LOCK:
        if _QUIET_DEPTH == 0:
            saved = []
            for name in _NOISY_LOGGERS:
                lg = logging.getLogger(name)
                saved.append((lg, lg.propagate))
                lg.propagate = False
                lg.addHandler(_QUIET_NULL)
            _QUIET_SAVED = saved
        _QUIET_DEPTH += 1


def _pop_quiet() -> None:
    global _QUIET_DEPTH, _QUIET_SAVED
    with _LOCK:
        _QUIET_DEPTH -= 1
        if _QUIET_DEPTH == 0 and _QUIET_SAVED is not None:
            for lg, propagate in _QUIET_SAVED:
                lg.propagate = propagate
                lg.removeHandler(_QUIET_NULL)
            _QUIET_SAVED = None


@contextlib.contextmanager
def track_compiles(*, quiet: bool = True):
    """Context manager yielding a :class:`CompileLog` that records every XLA
    compilation inside the block. Nestable (every active block sees every
    compile) and thread-safe (the sink registry is shared and locked; sink
    scope is the process, not the thread). ``quiet=True`` (default) keeps
    any caller-enabled log_compiles chatter off the console while
    tracking."""
    log = CompileLog()
    register_sink(log)
    if quiet:
        _push_quiet()
    try:
        yield log
    finally:
        if quiet:
            _pop_quiet()
        unregister_sink(log)


@contextlib.contextmanager
def assert_compiles(
    at_most: int = 0, *, match: Optional[str] = None, quiet: bool = True
):
    """Assert the block compiles at most ``at_most`` programs (optionally
    only counting program names containing ``match``); raises
    :class:`RetraceError` listing the offending programs otherwise.

    ``assert_compiles(0)`` around a warmed-up hot loop is the steady-state
    contract: the executables are cached, nothing re-traces."""
    with track_compiles(quiet=quiet) as log:
        yield log
    names = log.names if match is None else [n for n in log.names if match in n]
    if len(names) > at_most:
        raise RetraceError(
            f"expected at most {at_most} compilation(s)"
            + (f" matching {match!r}" if match else "")
            + f", observed {len(names)}: {names}"
        )
