"""Observability: flight-recorder tracing, a unified metrics registry,
and TTFT critical-path attribution.

  * ``repro.obs.tracer``      — causal spans on the sim clock, with a
    null-tracer fast path (``install``/``current_tracer``);
  * ``repro.obs.metrics``     — counters/gauges/log-histograms/binned
    timelines under one naming scheme (``MetricsRegistry``);
  * ``repro.obs.export``      — Chrome-trace/Perfetto JSON export +
    schema validation (``python -m repro.obs.export``);
  * ``repro.obs.attribution`` — per-request TTFT decomposition that
    provably sums to measured TTFT, from the span trees;
  * ``repro.obs.spans``       — ``span(name)``: host spans of the
    transfer engine and the data plane on the JAX profiler's clock,
    counted in ``SPAN_METRICS`` only while a profiler session runs, and
    ``count(name)``, a count under the same switch.

This package imports nothing from ``repro.core`` (the core imports
*us*), so instrumentation can thread through every layer without
cycles.
"""
from .attribution import (
    PHASES,
    aggregate_attribution,
    request_trees,
    ttft_attribution,
    validate_span_tree,
)
from .export import to_chrome, validate_chrome_trace, write_chrome_trace
from .metrics import (
    BinnedTimeline,
    Counter,
    Gauge,
    LogHistogram,
    MetricsRegistry,
)
from .spans import SPAN_METRICS, count, span
from .tracer import (
    NULL_TRACER,
    NullTracer,
    Span,
    Tracer,
    current_tracer,
    install,
    spans_from_dicts,
    uninstall,
)

__all__ = [
    "PHASES", "aggregate_attribution", "request_trees",
    "ttft_attribution", "validate_span_tree",
    "to_chrome", "validate_chrome_trace", "write_chrome_trace",
    "BinnedTimeline", "Counter", "Gauge", "LogHistogram",
    "MetricsRegistry",
    "SPAN_METRICS", "count", "span",
    "NULL_TRACER", "NullTracer", "Span", "Tracer", "current_tracer",
    "install", "spans_from_dicts", "uninstall",
]
