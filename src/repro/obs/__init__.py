"""repro.obs — unified telemetry: spans, Perfetto traces, metrics.

Zero-dependency (stdlib + numpy only; never imports jax) so any module
in the stack can instrument itself without import-order concerns.

Three layers:

* :mod:`repro.obs.spans` — in-process span/instant/counter API, one
  process-global tracer, off by default (``REPRO_TRACE`` env /
  :func:`enable`); spans also go to the JAX profiler, as
  ``repro/<name>`` annotations, while a profiler session is active; the
  disabled path allocates nothing.
* :mod:`repro.obs.trace` — Chrome trace-event JSON export: live spans,
  plus merged measured/predicted device lanes for plan executions
  (``plan.execute(trace="out.json")``) — open in ui.perfetto.dev.
* :mod:`repro.obs.metrics` — the versioned metrics envelope every
  ``BENCH_*.json`` / ``--metrics`` artifact emits through, with the
  CI shape validator (``python -m repro.obs.metrics FILE...``).

Shared dispersion math (percentiles, median/MAD) lives in
:mod:`repro.obs.stats` — the single copy the serving stats, the load
generator, and the profiling estimator all use.
"""
from . import stats
from .metrics import (METRICS_FORMAT, METRICS_SCHEMA_VERSION,
                      MetricsRegistry, MetricsValidationError,
                      read_metrics, validate_doc, wrap_metrics)
from .spans import (Tracer, active, counter, enable, enabled, get_tracer,
                    instant, span, traced)
from .trace import (TraceBuilder, build_plan_trace, export_spans,
                    load_trace, predicted_vs_measured, validate_trace)

__all__ = [
    "stats", "span", "instant", "counter", "enabled", "active", "enable",
    "get_tracer", "Tracer", "traced",
    "TraceBuilder", "export_spans", "build_plan_trace", "load_trace",
    "validate_trace", "predicted_vs_measured",
    "MetricsRegistry", "MetricsValidationError", "wrap_metrics",
    "read_metrics", "validate_doc",
    "METRICS_FORMAT", "METRICS_SCHEMA_VERSION",
]
