"""Unified observability subsystem (ISSUE 4).

One spine for every component's telemetry:

- :mod:`obs.metrics` — process-wide registry of labeled Counters /
  Gauges / fixed-bucket Histograms with per-thread cells and mergeable
  snapshots (``DIFACTO_OBS=off`` flips it to a no-op);
- :mod:`obs.trace` — nestable spans emitting Chrome trace-event JSON
  (``DIFACTO_TRACE=<path>``; open the file in Perfetto), with ids that
  survive the producer process boundary, and annotating whatever
  ``jax.profiler`` session is live in the process;
- :mod:`obs.stage` — :class:`stage`, the one way to time a boundary: a
  span whose duration also lands in ``stage_seconds_total{stage}``;
- :mod:`obs.names` — the leg scopes, stages and spans by name;
- :mod:`obs.export` — Prometheus text renderer (serve's ``#metrics``)
  and the periodic JSONL flusher (``metrics_path`` training knob);
- :mod:`obs.proc` — producer-worker snapshot publishing/absorption, so
  cross-process counters are exact.

See docs/observability.md for the metric catalog and span conventions.
"""

from . import names, trace  # noqa: F401
from .export import (MetricsFlusher, merged_snapshot,  # noqa: F401
                     render_prometheus)
from .metrics import (DEFAULT_BOUNDS, NOOP, REGISTRY,  # noqa: F401
                      Counter, Gauge, Histogram, Registry, counter,
                      enabled, gauge, hist_quantiles, histogram,
                      merge_into)
from .stage import stage, watch_compiles  # noqa: F401

__all__ = [
    "REGISTRY", "Registry", "Counter", "Gauge", "Histogram", "NOOP",
    "DEFAULT_BOUNDS", "counter", "gauge", "histogram", "enabled",
    "hist_quantiles", "merge_into", "render_prometheus",
    "merged_snapshot", "MetricsFlusher", "trace", "names", "stage",
    "watch_compiles",
]
