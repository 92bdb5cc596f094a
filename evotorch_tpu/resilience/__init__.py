"""Fault tolerance for long runs (ISSUE 17; docs/resilience.md).

Three legs, spanning the host runtime, the compiled eval programs, and
the ops tooling:

- :mod:`~evotorch_tpu.resilience.runstate` — durable, self-verifying run
  checkpoint bundles with atomic writes, keep-last-K retention and
  corrupt-bundle fallback; resume is bit-identical because the search
  state is a pure pytree.
- non-finite **score quarantine** lives inside the eval engines
  (``net/vecrl.py:_quarantine_nonfinite``; ``VecNE(nonfinite_quarantine=
  True)`` is the default) — it is listed here because this package's docs
  and tests own its contract: one diverged rollout must not NaN-poison
  ranking, and quarantined counts surface per group in the telemetry
  matrix plus the ``max_nonfinite_share`` SLO rule.
- :mod:`~evotorch_tpu.resilience.retry` /
  :mod:`~evotorch_tpu.resilience.faults` — bounded-backoff retries around
  the fragile host edges, and the deterministic ``EVOTORCH_FAULTS``
  injection harness that keeps every recovery path exercised by tests.
- :mod:`~evotorch_tpu.resilience.devices` — the device edge: an explicit
  request gets the CPU, anything else requires an accelerator and fails
  without one (no fallback), and every printed ``backend`` field comes
  from ``jax.devices()[0]``.
"""

from .devices import device_record, require_devices, setup_backend
from .faults import FaultRule, InjectedFault, configure, fault_point, parse_spec
from .retry import retry_call, retryable
from .runstate import BUNDLE_SCHEMA_VERSION, CorruptBundleError, RunCheckpointer

__all__ = [
    "FaultRule",
    "InjectedFault",
    "configure",
    "fault_point",
    "parse_spec",
    "retry_call",
    "retryable",
    "BUNDLE_SCHEMA_VERSION",
    "CorruptBundleError",
    "RunCheckpointer",
    "device_record",
    "require_devices",
    "setup_backend",
]
