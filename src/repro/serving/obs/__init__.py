"""Unified observability subsystem shared by both serving runtimes.

Everything here observes the simulation without perturbing it: spans are
stamped on the *simulated* clock (``repro.serving.obs.tracer``), streaming
stats are bounded-memory (``obs.stats``), the event-loop profiler measures
wall time only (``obs.profiler``), and scheduler introspection is a pure
read of policy state plus completed records (``obs.sched``).  Exporters
(``obs.export``) turn a finished tracer into Chrome trace-event JSON
(loads in Perfetto: pools as tracks, requests as flows) or JSONL.

The one exception is ``obs.spans``: host spans of the real device path,
written into the JAX profiler's trace on its clock.
"""
from repro.serving.obs.export import (export_runtime_telemetry,
                                      to_chrome_trace, validate_chrome_trace,
                                      write_chrome_trace, write_spans_jsonl)
from repro.serving.obs.profiler import EventLoopProfiler
from repro.serving.obs.sched import (SchedulerIntrospection, linucb_snapshot,
                                     scheduler_report)
from repro.serving.obs.spans import span
from repro.serving.obs.stats import (DepthSeries, ReservoirSample,
                                     StreamingQuantiles, latency_attribution,
                                     attribution_residual)
from repro.serving.obs.tracer import (HOP, QUEUE, REISSUE, SEGMENT,
                                      RequestTrace, Span, SpanTracer,
                                      span_structure)

__all__ = [
    "Span", "SpanTracer", "RequestTrace", "span_structure",
    "SEGMENT", "HOP", "QUEUE", "REISSUE",
    "to_chrome_trace", "write_chrome_trace", "write_spans_jsonl",
    "validate_chrome_trace", "export_runtime_telemetry",
    "StreamingQuantiles", "ReservoirSample", "DepthSeries",
    "latency_attribution", "attribution_residual",
    "SchedulerIntrospection", "linucb_snapshot", "scheduler_report",
    "EventLoopProfiler", "span",
]
