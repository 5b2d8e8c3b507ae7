"""Unified observability: span tracing, mergeable metrics, exposition.

- ``metrics``: process-wide registry of counters/gauges/fixed-bucket
  histograms whose snapshots merge across processes (fleet view).
- ``trace``: ``span``, the one way the program records a span. Spans
  are always on and always on the profiler's clock: each is a
  ``jax.profiler`` TraceMe (one check when no capture runs), so a
  capture holds them beside the device's operations, with their
  arguments (uid lists joined by spaces). With a ``Tracer`` attached the
  same span is also a Chrome event with a cross-process trace id, for
  Chrome-trace/Perfetto JSON export. ``host_sync`` spans a blocking
  device-to-host read and counts it in ``serve.host_syncs``.
- ``export``: stdlib HTTP endpoint (Prometheus text + JSON + /health)
  and snapshot files next to checkpoints.
- ``health``: rule engine over registry series — structured
  ``HealthEvent`` log + per-process ``ok``/``degraded``/``critical``
  verdicts that merge across a fleet.
- ``profile``: optional ``jax.profiler`` capture (``--profile-dir``);
  it adds no annotation of its own, the spans above are what it shows.
- ``recorder``: bounded flight recorder — request digests, journal
  tail, cadenced state fingerprints — flushed to atomic incident
  bundles on health-verdict escalations.
- ``forensics``: offline bundle replay, fingerprint verification and
  first-bad-event bisection (``python -m repro.obs.forensics``).
"""

from .export import prometheus_text, start_metrics_server, write_snapshot
from .forensics import IncidentBundle, analyze, load_bundle
from .health import (
    HealthEvent,
    HealthMonitor,
    HealthRule,
    default_rules,
    merge_health,
)
from .metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_buckets,
    merge,
    quantile,
    registry,
)
from .profile import ProfileHooks
from .recorder import FlightRecorder
from .trace import Span, Tracer, host_sync, span

__all__ = [
    "Counter",
    "FlightRecorder",
    "Gauge",
    "HealthEvent",
    "HealthMonitor",
    "HealthRule",
    "Histogram",
    "IncidentBundle",
    "MetricsRegistry",
    "ProfileHooks",
    "Span",
    "Tracer",
    "analyze",
    "default_buckets",
    "default_rules",
    "host_sync",
    "load_bundle",
    "merge",
    "merge_health",
    "prometheus_text",
    "quantile",
    "registry",
    "span",
    "start_metrics_server",
    "write_snapshot",
]
