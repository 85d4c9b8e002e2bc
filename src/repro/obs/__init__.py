"""repro.obs: dependency-free observability for the serving stack.

Three parts (see ``docs/observability.md`` for the naming scheme and
operator quickstart):

- :mod:`repro.obs.metrics` -- thread-safe counter/gauge/histogram
  registry; the single backing store ``ServingStats`` and the K-cache
  stats are views over.
- :mod:`repro.obs.trace` -- per-request span trees + structured event
  log, exportable as Chrome trace-event JSON (Perfetto) and JSONL, and
  :func:`span`, the program's stages on the profiler's clock.
- :mod:`repro.obs.export` -- Prometheus text exposition, a stdlib HTTP
  scrape endpoint, and a periodic JSONL event flusher.

The whole package is stdlib-only at import (``span`` loads jax on first
use) and bitwise-neutral: recorders never touch arrays, and
observability-off is the shared :data:`NULL_TRACER` no-op with zero
hot-path cost.
"""
from .export import JsonlExporter, MetricsServer, render_prometheus
from .metrics import (DEFAULT_SIZE_BUCKETS, DEFAULT_TIME_BUCKETS, Counter,
                      Gauge, Histogram, MetricsRegistry)
from .trace import NULL_TRACER, NullTracer, Tracer, span

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "DEFAULT_TIME_BUCKETS",
    "DEFAULT_SIZE_BUCKETS",
    "Tracer",
    "NullTracer",
    "NULL_TRACER",
    "span",
    "render_prometheus",
    "MetricsServer",
    "JsonlExporter",
]
