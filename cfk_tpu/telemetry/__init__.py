"""Unified telemetry: span tracing, fault flight recorder, metrics export.

Three instruments, one package (ISSUE 14):

- ``trace`` — hierarchical, thread-aware host span tracing exported as
  Chrome-trace/Perfetto JSON (``--trace-dir``; colocate with the
  ``maybe_profile`` jax-profiler trace so host and device timelines line
  up).  Off by default and near-free when off.
- ``recorder`` — the fault flight recorder: a bounded ring of recent
  events dumped atomically on any trip/escalation/eviction/crash, so
  every chaos scenario (and real incident) leaves a forensic timeline.
- ``metrics`` / ``export`` — the thread-safe typed registry (counters,
  gauges, phases, bounded-reservoir histograms; the reworked
  ``utils.metrics.Metrics``), its periodic JSONL emitter, and the
  Prometheus-text ``/metrics`` endpoint the request server and
  ``cfk_tpu stream`` serve.

Telemetry-off is bit-identical by the sentinel discipline: nothing here
ever touches device values, and span/record calls are no-ops (one global
read) when nothing is configured (``tests/test_telemetry.py``; tracer on
against off on the chip: PERF.md section 6, PR 24).
"""

from cfk_tpu.telemetry.export import (
    MetricsHTTPServer,
    prometheus_text,
    sanitize_metric_name,
)
from cfk_tpu.telemetry.metrics import (
    Histogram,
    Metrics,
    MetricsEmitter,
    MetricsRegistry,
)
from cfk_tpu.telemetry.recorder import (
    FlightRecorder,
    dump_flight,
    get_recorder,
    install_crash_hooks,
    record_event,
)
from cfk_tpu.telemetry.trace import (
    GC_SPAN,
    Tracer,
    configure,
    get_tracer,
    shutdown,
    span,
    stage_overlap_from_events,
    validate_span_tree,
)

__all__ = [
    "FlightRecorder",
    "GC_SPAN",
    "Histogram",
    "Metrics",
    "MetricsEmitter",
    "MetricsHTTPServer",
    "MetricsRegistry",
    "Tracer",
    "configure",
    "dump_flight",
    "get_recorder",
    "get_tracer",
    "install_crash_hooks",
    "prometheus_text",
    "record_event",
    "sanitize_metric_name",
    "shutdown",
    "span",
    "stage_overlap_from_events",
    "validate_span_tree",
]
