"""Unified observability spine (PR 10) + tail-latency diagnostics (PR 14).

- :mod:`.trace` — request-/step-scoped hierarchical span tracer; Chrome-trace
  (Perfetto) + JSONL export; cross-process trace-id join over the subprocess
  serving pipe; ``scope(name)``, the declared name of a region inside a
  compiled program (op metadata, no switch);
- :mod:`.metrics` — bounded process-wide registry (counters / gauges /
  fixed-log-bucket histograms) with ONE declared tag schema, MonitorMaster as
  an export backend and Prometheus text exposition plus the HTTP status plane
  (``/metrics`` / ``/statusz`` / ``/healthz``);
- :mod:`.schema` — the declared tag table + the emission-site lint;
- :mod:`.profiler` — on-demand ``jax.profiler`` capture of N steps/chunks,
  armed by config or ``SIGUSR2``;
- :mod:`.attribution` — per-request latency decomposition (span tree → named
  phases summing to e2e) and the p50-vs-p99 phase-share breakdown;
- :mod:`.flight` — bounded tail-sampling flight recorder (full span trees for
  slow/failed/retried/shed/deadline-missed requests + a 1-in-N sample),
  control-plane decision journal, Perfetto-loadable dump bundles (on demand,
  ``SIGUSR1``, router drain, anomaly trips);
- :mod:`.anomaly` — EWMA+MAD scoring over registry streams; a trip dumps the
  flight bundle and arms the XLA profiler for the next K ticks.
"""

from . import schema
from .anomaly import AnomalyConfig, AnomalyDetector, get_detector
from .attribution import attribute, phase_breakdown
from .flight import (FlightConfig, FlightRecorder, get_recorder,
                     install_recorder)
from .flight import journal as flight_journal
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      get_registry, record_events, start_metrics_server)
from .profiler import ProfilerCapture, configure_capture, get_capture
from .profiler import tick as profiler_tick
from .trace import (CAT_ROUTER, CAT_SERVING, CAT_TRAIN, OpenSpan, SpanContext,
                    Tracer, chrome_events_from, get_tracer, scope)

__all__ = [
    "schema", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "get_registry", "record_events", "start_metrics_server",
    "ProfilerCapture", "configure_capture", "get_capture", "profiler_tick",
    "CAT_ROUTER", "CAT_SERVING", "CAT_TRAIN", "OpenSpan", "SpanContext",
    "Tracer", "get_tracer", "chrome_events_from", "scope",
    "attribute", "phase_breakdown",
    "FlightConfig", "FlightRecorder", "get_recorder", "install_recorder",
    "flight_journal",
    "AnomalyConfig", "AnomalyDetector", "get_detector",
]
