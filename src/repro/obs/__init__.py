"""Unified telemetry plane (DESIGN.md §17): typed step records, a
span-based flight recorder on one clock, Chrome-trace/Perfetto export,
and a stdlib metrics registry with Prometheus text exposition —
cross-cutting over both engines, the host sampler pool, the adaptive
controller, and the gateway."""
from repro.obs.export import (chrome_trace, chrome_trace_events,
                              write_chrome_trace)
from repro.obs.metrics import (DEFAULT_MS_BUCKETS, Counter, Gauge,
                               Histogram, MetricsRegistry,
                               render_registries)
from repro.obs.records import CycleRecord, RecordMapping, StepRecord
from repro.obs.telemetry import EngineMetrics, Telemetry
from repro.obs.tracer import (NULL_SPAN, NULL_TRACER, SPAN_KINDS,
                              SpanEvent, StepTracer)

__all__ = [
    "StepRecord", "CycleRecord", "RecordMapping",
    "StepTracer", "SpanEvent", "SPAN_KINDS", "NULL_TRACER", "NULL_SPAN",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "render_registries", "DEFAULT_MS_BUCKETS",
    "chrome_trace", "chrome_trace_events", "write_chrome_trace",
    "Telemetry", "EngineMetrics",
]
