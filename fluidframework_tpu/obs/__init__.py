"""Cross-layer observability: op tracing, the unified metrics
registry, the flight recorder — and the interpretation layer on top
of them: SLOs, the continuous profiler, and span export.

Pillars (docs/OBSERVABILITY.md):

- ``obs.trace`` — the canonical hop table + :func:`stamp`; every
  layer stamps an op's ``traces`` list through it, so a single op's
  submit→ack path is reconstructable (``breakdown`` /
  ``format_breakdown``).
- ``obs.metrics`` — ONE process-wide :data:`REGISTRY` of counters /
  gauges / histograms with Prometheus text exposition and a JSON
  snapshot; ingress serves it over the ``metrics`` frame, bench
  snapshots it into every stage record.
- ``obs.flight_recorder`` — fixed-size lock-free ring of recent
  dispatch-loop / transport events, dumped automatically on faults.
- ``obs.slo`` — declarative objectives over registry families,
  graded with multi-window burn rates; breach dumps the recorders.
- ``obs.profiler`` — always-on sampling host profiler with
  per-component (thread-name) attribution + opt-in jax device-trace
  hooks (``set_device_trace``).
- ``obs.spans`` — the hop tables as OTLP-JSON span trees for
  standard trace viewers.
- ``obs.federation`` — the FLEET half of the registry: merge leader /
  follower / partition-worker registries into one federated view
  (sum counters, node-labelled gauges, bucket-wise histogram merge)
  served over the ``fleet-metrics`` frame and ``--dump-fleet``.
- ``obs.timeline`` — the causally-ordered cross-node event log
  (lease lifecycle, epoch fences, promotions, anti-entropy, mesh
  migrations) that decomposes failover into named phases and exports
  the incident as an OTLP span tree.

This package sits just above ``protocol`` in the layer map so every
other layer may depend on it; it depends on nothing above.
"""
from __future__ import annotations

import weakref

from .federation import FederatedView
from .flight_recorder import FlightRecorder
from .metrics import REGISTRY, MetricsRegistry, get_registry
from .profiler import ContinuousProfiler, device_trace, set_device_trace
from .slo import Objective, SloEngine
from .spans import (
    FileSpanExporter,
    op_to_otlp,
    otlp_to_hops,
    timeline_to_otlp,
)
from .timeline import TIMELINE_KINDS, FleetTimeline
from .trace import (
    CANONICAL_HOPS,
    breakdown,
    format_breakdown,
    hop_name,
    stamp,
    total_ms,
)

__all__ = [
    "CANONICAL_HOPS", "ContinuousProfiler", "FederatedView",
    "FileSpanExporter", "FleetTimeline", "FlightRecorder",
    "MetricsRegistry", "Objective", "REGISTRY", "SloEngine",
    "TIMELINE_KINDS", "breakdown", "device_trace",
    "format_breakdown", "get_registry", "hop_name", "op_to_otlp",
    "otlp_to_hops", "register_closeable", "set_device_trace",
    "shutdown", "stamp", "timeline_to_otlp", "total_ms",
]

# ----------------------------------------------------------------------
# shutdown path: telemetry aggregators (SampledTelemetryHelper and
# friends) register here so their TAIL measurements flush at teardown
# instead of being silently dropped — weakrefs, so registration never
# extends an owner's lifetime.

_closeables: "weakref.WeakSet" = weakref.WeakSet()


def register_closeable(obj) -> None:
    """Register an object with a ``close()`` method to be closed (and
    thereby flushed) by :func:`shutdown`."""
    _closeables.add(obj)


def shutdown() -> None:
    """Close every registered aggregator (idempotent; close() on these
    is required to be re-entrant safe)."""
    for obj in list(_closeables):
        obj.close()
    # closed objects may be re-registered by a later session; keep the
    # set — close() is idempotent on all registrants by contract
