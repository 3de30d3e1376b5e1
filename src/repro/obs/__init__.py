"""Fleet telemetry: tracing, metrics, and hardware-health timelines.

``repro.obs`` is the observability layer for the serving stack:

* :mod:`repro.obs.trace` — process-local ring-buffered event bus, and
  host spans that also land in a running ``jax.profiler`` trace.
* :mod:`repro.obs.metrics` — typed registry (counters / gauges /
  fixed-bucket histograms) backing the serve and traffic reports.
* :mod:`repro.obs.export` — Prometheus text exposition + JSONL traces,
  both round-trippable.
* :mod:`repro.obs.health` — per-slot hardware-health timelines and the
  fleet heatmap reconstructed from a trace.
"""
# repro-lint: module=observability

from repro.obs.export import (
    parse_prometheus,
    read_trace_jsonl,
    to_prometheus,
    write_trace_jsonl,
)
from repro.obs.health import (
    DriftStory,
    FleetHealthTimeline,
    drift_story,
    fleet_heatmap,
    from_events,
    rel_l2_to_sqnr_db,
    slot_timelines,
)
from repro.obs.metrics import (
    LATENCY_EDGES_S,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from repro.obs.trace import (
    DETAIL_KINDS,
    TraceBuffer,
    TraceEvent,
    bus,
    detail_enabled,
    emit,
    enabled,
    install,
    span,
    tracing,
    uninstall,
)

__all__ = [
    "DETAIL_KINDS",
    "Counter",
    "DriftStory",
    "FleetHealthTimeline",
    "Gauge",
    "Histogram",
    "LATENCY_EDGES_S",
    "MetricsRegistry",
    "TraceBuffer",
    "TraceEvent",
    "bus",
    "detail_enabled",
    "drift_story",
    "emit",
    "enabled",
    "fleet_heatmap",
    "from_events",
    "install",
    "parse_prometheus",
    "read_trace_jsonl",
    "rel_l2_to_sqnr_db",
    "slot_timelines",
    "span",
    "to_prometheus",
    "tracing",
    "uninstall",
    "write_trace_jsonl",
]
