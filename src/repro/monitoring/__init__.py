from repro.monitoring.metrics import (
    DRIVER_METRICS,
    METRIC_NAMES,
    REGISTRY,
    WORKER_METRICS,
    ChaosCounters,
    MetricDef,
    ServeCounters,
    TimeSeriesStore,
    build_registry,
    flush_guard,
    retrace_counts,
)
from repro.monitoring.spans import SpanTotals, count, span, step

__all__ = [
    "DRIVER_METRICS",
    "METRIC_NAMES",
    "REGISTRY",
    "WORKER_METRICS",
    "ChaosCounters",
    "MetricDef",
    "ServeCounters",
    "SpanTotals",
    "TimeSeriesStore",
    "build_registry",
    "flush_guard",
    "count",
    "retrace_counts",
    "span",
    "step",
]
