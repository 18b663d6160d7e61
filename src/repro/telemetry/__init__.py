"""repro.telemetry — lightweight, dependency-free metrics + tracing.

* :mod:`repro.telemetry.metrics` — counters / gauges / bucketed histograms
  behind a process-local :class:`Registry` with ``snapshot()``, Prometheus
  text ``exposition()``, and a JSONL event sink; near-zero cost when
  disabled (the disabled registry hands out a no-op singleton).
* :mod:`repro.telemetry.trace` — context-manager :func:`span`\\ s with
  wall-clock + optional device-sync timing, emitting to the registries and
  to ``jax.profiler`` (with the span's ``args`` as the event's stats) so
  engine tick phases show up labeled in profiles; :func:`named_scope`
  labels the layers inside jitted programs with the names in ``SCOPES``.
* :mod:`repro.telemetry.pull` — :func:`serve_metrics`: a stdlib-only
  ``GET /metrics`` HTTP endpoint rendering a registry's ``exposition()``
  for real Prometheus scraping (``Engine(metrics_port=...)`` /
  ``serve_bench --metrics-port``).

Enable globally (e.g. in a bench or service entry point)::

    from repro import telemetry
    telemetry.enable(jsonl="telemetry.jsonl")     # counters + event stream
    ...
    print(telemetry.registry().exposition())      # Prometheus text format
    snap = telemetry.registry().snapshot()        # JSON-ready dict
"""
from repro.telemetry.metrics import (
    DEFAULT_BUCKETS_MS,
    Counter,
    Gauge,
    Histogram,
    JsonlSink,
    NOOP,
    Registry,
    counter_value,
    disable,
    emit_event,
    enable,
    enabled,
    gauge_stats,
    registry,
    sink,
)
from repro.telemetry.pull import MetricsServer, serve_metrics
from repro.telemetry.trace import SCOPES, SpanHandle, named_scope, span

__all__ = [
    "DEFAULT_BUCKETS_MS",
    "Counter",
    "Gauge",
    "Histogram",
    "JsonlSink",
    "MetricsServer",
    "NOOP",
    "Registry",
    "SCOPES",
    "SpanHandle",
    "counter_value",
    "disable",
    "emit_event",
    "enable",
    "enabled",
    "gauge_stats",
    "named_scope",
    "registry",
    "serve_metrics",
    "sink",
    "span",
]
