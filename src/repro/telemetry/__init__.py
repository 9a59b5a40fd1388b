"""Telemetry & adaptive control plane for the design service.

Three layers, each usable alone:

  * tracing (`repro.telemetry.spans`) — `trace_span` opens every span
    as a `design.<cat>.<name>` profiler annotation and, when a
    `SpanRecorder` is attached, records it there on the monotonic
    clock; `TraceExport` serializes recorded spans as a schema-stamped,
    Chrome-trace-compatible event list and a per-batch stage Gantt;
  * metrics (`repro.telemetry.metrics` + `repro.telemetry.export`) —
    a typed `Counter`/`Gauge`/`Histogram` registry snapshotable as
    versioned JSON or prometheus text;
  * control (`repro.telemetry.control`) — `FeedbackController` turns
    windowed metrics (arrival-rate EMA, queue depth, pool occupancy)
    into adaptive-coalescing and pool-autoscaling decisions, each
    recorded as a span.

`Telemetry` is the bundle `repro.serve.design_service.DesignService`
accepts (`telemetry=Telemetry()` or `telemetry=True`): one recorder +
one registry wired through the admission pump, all four stage workers,
the layout pool, and the retry/shed/preemption paths.
"""
from repro.telemetry.control import (ControlDecision, ControllerConfig,
                                     FeedbackController)
from repro.telemetry.export import (atomic_write_json, load_snapshot,
                                    render_prometheus, write_metrics_json)
from repro.telemetry.metrics import (DEFAULT_LATENCY_BUCKETS,
                                     HISTOGRAM_SAMPLE_CAP, METRICS_SCHEMA,
                                     Counter, Gauge, Histogram,
                                     MetricsRegistry, percentile)
from repro.telemetry.spans import (TRACE_SCHEMA, Span, SpanRecorder,
                                   TraceExport, trace_span)


class Telemetry:
    """One recorder + one registry: what the service threads through its
    pump, stages, pool, and fault paths.  Pass your own pieces to share
    a recorder between a session and several services, or rely on the
    defaults."""

    def __init__(self, *, recorder: SpanRecorder | None = None,
                 metrics: MetricsRegistry | None = None):
        self.recorder = recorder if recorder is not None else SpanRecorder()
        self.metrics = metrics if metrics is not None else MetricsRegistry()

    def export(self) -> TraceExport:
        return self.recorder.export()


__all__ = [
    "ControlDecision", "ControllerConfig", "Counter",
    "DEFAULT_LATENCY_BUCKETS", "FeedbackController", "Gauge", "Histogram",
    "HISTOGRAM_SAMPLE_CAP", "METRICS_SCHEMA", "MetricsRegistry", "Span",
    "SpanRecorder", "TRACE_SCHEMA", "Telemetry", "TraceExport",
    "atomic_write_json", "load_snapshot", "percentile", "render_prometheus",
    "trace_span", "write_metrics_json",
]
