"""The observability plane: a unified view over the telemetry plane.

Four coordinated pieces:

- :class:`TraceRecorder` — a structured span/instant/counter event stream
  tapped from the ``TelemetryHub`` / ``MemoryEngine`` / ``DmaChannel`` /
  simulator / executor / serving / daemon hooks, exported as Chrome Trace
  Event Format JSON (loadable in ``chrome://tracing`` or Perfetto).
- :class:`MetricsRegistry` — counters / gauges / histograms exposed by the
  scheduler daemon as a Prometheus text-format file refreshed with the
  heartbeat.
- :class:`DriftMonitor` — the sim-vs-measured accuracy watchdog: compares
  predicted peak/EOR/safe-point placement against measured values per
  fingerprint, emits drift gauges + WARN events past a threshold, and
  persists per-fingerprint drift history into the ``ExperienceStore``.
- :func:`span` — the program's own spans (``tensile.*``, listed in
  ``SPANS``) as profiler annotations, on the device trace's clock.

Every producer-side hook is ZERO-overhead when no recorder is attached:
one ``is not None`` check on an attribute that defaults to ``None`` —
the same discipline as the DMA channel's ``coalesce=False`` default.
"""
from .events import Event, EventLog
from .drift import DriftMonitor, DriftSample
from .metrics import MetricsRegistry, parse_metrics_text
from .spans import SPANS, gc_spans, span
from .trace import (TRACE_SCHEMA_VERSION, TraceRecorder, format_summary,
                    load_trace, summarize_trace, validate_chrome_trace)

__all__ = [
    "Event", "EventLog",
    "DriftMonitor", "DriftSample",
    "MetricsRegistry", "parse_metrics_text",
    "SPANS", "gc_spans", "span",
    "TRACE_SCHEMA_VERSION", "TraceRecorder", "format_summary", "load_trace",
    "summarize_trace", "validate_chrome_trace",
]
