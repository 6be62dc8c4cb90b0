"""Unified observability layer: metrics registry, tracing, exporters.

The serving stack publishes its exact analytic ledgers (stage bytes,
cache hits, µJ/query) and request lifecycles here. Host-side only —
never inside jitted code. `NULL_REGISTRY` makes metrics free when
disabled; `NULL_TRACER` records nothing in memory but still marks each
span on the JAX profiler's host plane (a TraceMe check when no profiler
records). See repro.obs.metrics / .tracing / .export for the pieces,
and the README's "Observability" section for the architecture and
overhead contract.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram, LabeledRegistry,
                               MetricsRegistry, NullRegistry, NULL_REGISTRY)
from repro.obs.tracing import (NullTracer, NULL_TRACER, TraceEvent, Tracer)
from repro.obs.export import (chrome_trace, parse_prometheus,
                              prometheus_text, write_chrome_trace)

__all__ = [
    "Counter", "Gauge", "Histogram", "LabeledRegistry", "MetricsRegistry",
    "NullRegistry",
    "NULL_REGISTRY", "NullTracer", "NULL_TRACER", "TraceEvent", "Tracer",
    "chrome_trace", "parse_prometheus", "prometheus_text",
    "write_chrome_trace",
]
