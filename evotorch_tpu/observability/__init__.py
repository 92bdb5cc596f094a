"""Zero-sync telemetry for the eval stack.

Three cooperating pieces (docs/observability.md has the full catalog):

- :mod:`~evotorch_tpu.observability.devicemetrics` — ON-DEVICE metric
  accumulators: env-steps, episodes, lane capacity (occupancy), refill
  events and queue-wait lane-steps, accumulated inside the existing
  rollout ``lax.while_loop`` carries and returned as ONE packed int32
  array in the same device->host transfer as the scores. Zero extra
  dispatches, zero retraces (sentinel-asserted in the fast tier). The v4
  wire is a PER-GROUP ``(G, 20)`` matrix (segment-summed counters +
  bucketed queue-wait histograms + the float32 search-health block of
  score statistics, bit-cast into the int32 rows; ``GroupTelemetry``
  decodes it, and the v1 ``(6,)`` / v2 ``(G, 14)`` / v3 ``(G, 15)``
  wires still decode everywhere).
- :mod:`~evotorch_tpu.observability.health` — windowed, variance-aware
  trend detection over the health plane (``EWMATrend`` /
  ``HealthMonitor``), feeding the ``plateau`` / ``stdev_collapse`` /
  ``score_snr_floor`` SLO rule kinds.
- :mod:`~evotorch_tpu.observability.tracer` — a host-side span tracer
  emitting Chrome trace-event JSON loadable in Perfetto (ring-buffered;
  a no-op singleton when disabled). Spans cover ask/eval/tell in the
  search loop, the host pipeline's S1/S2/S3 stages + the physics worker
  thread (overlap is visible as parallel tracks), and hostpool syncs.
  Enable with ``EVOTORCH_TRACE=/path/to/trace.json`` or
  :func:`~evotorch_tpu.observability.tracer.start_tracing`.
- :mod:`~evotorch_tpu.observability.registry` — a process-wide counter
  registry (``compiles`` via the session-wide promotion of
  ``retrace_sentinel``'s compile counting, ``trace_spans``,
  ``telemetry_fetches``, ``compile_seconds`` wall time,
  ``peak_hbm_bytes`` gauge) surfaced through searcher ``status`` dicts, so
  ``StdOutLogger``/``PandasLogger`` pick everything up for free.
- :mod:`~evotorch_tpu.observability.programs` — the PROGRAM ledger
  (compile-time sibling of the runtime telemetry above): per
  (program, shape) XLA cost/memory accounting, runtime-verified
  ``donate_argnums`` aliasing, and the checked-in perf-regression
  baseline (``ledger_baseline.json``, gated in the fast tier). Report
  CLI: ``python -m evotorch_tpu.observability.report``.
- :mod:`~evotorch_tpu.observability.timings` — the MEASURED-timing
  ledger (runtime sibling of the program ledger: median steps/s,
  occupancy, compile seconds per (program, shape, machine) key) and the
  persisted tuned-config cache (``tuned_configs.json``) the eval stack
  consults at setup time — explicit knobs always override; every
  consumer reports ``tuned_config_source`` provenance. Filled by the
  autotuner: ``python -m evotorch_tpu.observability.autotune``
  (:mod:`~evotorch_tpu.observability.autotune`).
- :mod:`~evotorch_tpu.observability.metricshub` — streaming export of the
  decoded telemetry + counter registry as schema-versioned JSONL (manifest
  first line) or Prometheus text (``.prom`` suffix); wired to
  ``EVOTORCH_METRICS=path`` in the curve runner.
- :mod:`~evotorch_tpu.observability.slo` — declarative SLO watchdog
  (per-group occupancy floor, starvation ceiling off the top queue-wait
  bucket, steady_compiles == 0, min progress) surfaced as searcher status
  keys (``VecNEProblem(slo=...)``).
"""

from .compilecache import (  # noqa: F401
    cache_stats,
    enable_persistent_cache,
)
from .devicemetrics import (  # noqa: F401
    EvalTelemetry,
    GROUP_TELEMETRY_WIDTH,
    GroupTelemetry,
    HEALTH_TELEMETRY_WIDTH,
    HEALTH_WIDTH,
    QUEUE_WAIT_BUCKET_EDGES,
    QUEUE_WAIT_BUCKETS,
    TELEMETRY_SCHEMA_VERSION,
    TELEMETRY_WIDTH,
    append_health_block,
    compute_health_block,
    pack_eval_telemetry,
    pack_group_telemetry,
    queue_wait_bucket_index,
)
from .health import EWMATrend, HealthMonitor  # noqa: F401
from .metricshub import MetricsHub  # noqa: F401
from .programs import (  # noqa: F401
    DonationReport,
    ProgramLedger,
    ProgramRecord,
    compare_to_baseline,
    default_ledger_baseline_path,
    cost_analysis,
    memory_analysis,
    ledger,
    load_ledger_baseline,
    save_ledger_baseline,
    verify_runtime_donation,
)
from .registry import (  # noqa: F401
    CounterRegistry,
    counters,
    ensure_compile_counter,
    ensure_compile_timer,
)
from .slo import Rule, SLOReport, SLOWatchdog  # noqa: F401
from .timings import (  # noqa: F401
    SOURCE_CACHE,
    SOURCE_FALLBACK,
    SOURCE_OVERRIDE,
    TimingLedger,
    TimingRecord,
    TunedEntry,
    canonical_env_label,
    default_tuned_cache_path,
    load_tuned_cache,
    lookup_tuned,
    machine_fingerprint,
    resolve_knobs,
    save_tuned_entry,
    timing_key,
    timings,
)
from .tracer import (  # noqa: F401
    SpanTracer,
    get_tracer,
    instant,
    span,
    start_tracing,
    stop_tracing,
    tracing_enabled,
)

__all__ = [
    "cache_stats",
    "enable_persistent_cache",
    "EvalTelemetry",
    "GroupTelemetry",
    "GROUP_TELEMETRY_WIDTH",
    "HEALTH_TELEMETRY_WIDTH",
    "HEALTH_WIDTH",
    "QUEUE_WAIT_BUCKETS",
    "QUEUE_WAIT_BUCKET_EDGES",
    "TELEMETRY_SCHEMA_VERSION",
    "TELEMETRY_WIDTH",
    "append_health_block",
    "compute_health_block",
    "pack_eval_telemetry",
    "pack_group_telemetry",
    "queue_wait_bucket_index",
    "MetricsHub",
    "Rule",
    "SLOReport",
    "SLOWatchdog",
    "EWMATrend",
    "HealthMonitor",
    "CounterRegistry",
    "counters",
    "ensure_compile_counter",
    "ensure_compile_timer",
    "DonationReport",
    "ProgramLedger",
    "ProgramRecord",
    "compare_to_baseline",
    "default_ledger_baseline_path",
    "cost_analysis",
    "memory_analysis",
    "ledger",
    "load_ledger_baseline",
    "save_ledger_baseline",
    "verify_runtime_donation",
    "SpanTracer",
    "get_tracer",
    "instant",
    "span",
    "start_tracing",
    "stop_tracing",
    "tracing_enabled",
    "SOURCE_CACHE",
    "SOURCE_FALLBACK",
    "SOURCE_OVERRIDE",
    "TimingLedger",
    "TimingRecord",
    "TunedEntry",
    "canonical_env_label",
    "default_tuned_cache_path",
    "load_tuned_cache",
    "lookup_tuned",
    "machine_fingerprint",
    "resolve_knobs",
    "save_tuned_entry",
    "timing_key",
    "timings",
]
