"""Observability: the fifth registry concept (pluggable trace/metric sinks).

Public surface re-exported here; see :mod:`repro.obs.trace` for the core
semantics (zero-overhead-when-disabled spans, counter/gauge registry),
:mod:`repro.obs.export` for Chrome-trace output, :mod:`repro.obs.metrics`
for derived stats and :mod:`repro.obs.validate` for the trace-event
schema check used by ``make trace-smoke``.
"""

from repro.obs.export import ChromeTraceSink, chrome_trace, write_chrome_trace
from repro.obs.metrics import (
    StatsLineSink,
    counter_total,
    percentile,
    request_stats_from_events,
    summarize_spans,
    traced_kernels,
)
from repro.obs.trace import (
    NULL_SPAN,
    NullSink,
    PointRecord,
    RingSink,
    Sink,
    SpanRecord,
    active,
    clear_sinks,
    counter,
    counter_value,
    counters_snapshot,
    current_depth,
    disabled,
    event,
    gauge,
    gauge_value,
    gauges_snapshot,
    register_sink,
    reset_metrics,
    sinks,
    span,
    unregister_sink,
)
from repro.obs.validate import TraceFormatError, validate_chrome

__all__ = [
    "ChromeTraceSink",
    "NULL_SPAN",
    "NullSink",
    "PointRecord",
    "RingSink",
    "Sink",
    "SpanRecord",
    "StatsLineSink",
    "TraceFormatError",
    "active",
    "chrome_trace",
    "clear_sinks",
    "counter",
    "counter_total",
    "counter_value",
    "counters_snapshot",
    "current_depth",
    "disabled",
    "event",
    "gauge",
    "gauge_value",
    "gauges_snapshot",
    "percentile",
    "register_sink",
    "request_stats_from_events",
    "reset_metrics",
    "sinks",
    "span",
    "summarize_spans",
    "traced_kernels",
    "unregister_sink",
    "validate_chrome",
    "write_chrome_trace",
]
