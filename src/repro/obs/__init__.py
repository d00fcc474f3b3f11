"""Unified observability layer (docs/observability.md).

Three pillars:

* :mod:`repro.obs.trace` — nested spans, ring-buffer flight recorder,
  deterministic Perfetto ``trace_event`` export.
* :mod:`repro.obs.metrics` — counter/gauge/histogram registry with a
  Prometheus text writer and ``as_metrics()`` stats adapters.
* :mod:`repro.obs.explain` — TuningDB-backed decision audit reports.

This package init re-exports only the stdlib-pure pillars: core modules
import ``repro.obs.trace``/``repro.obs.metrics`` from inside ``repro.core``
and ``repro.runtime``, so importing :mod:`repro.obs.explain` here (it
imports ``repro.core.db``) would create an import cycle — consumers import
it lazily (``from repro.obs import explain``).
"""
from .metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    parse_prometheus,
    snapshot_stats,
)
from .trace import (
    DeferredRegion,
    Region,
    TickTimer,
    Tracer,
    current_tracer,
    set_tracer,
    use_tracer,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "parse_prometheus",
    "snapshot_stats",
    "DeferredRegion",
    "Region",
    "TickTimer",
    "Tracer",
    "current_tracer",
    "set_tracer",
    "use_tracer",
]
