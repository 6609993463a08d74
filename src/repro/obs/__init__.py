"""Observability plane for the in-network sort dataplane.

Three layers answer three different questions about a pipeline run:

* :mod:`repro.obs.trace` — *where did the time go?*  Hierarchical spans
  (ingress → epoch → hop or epoch stages → server ingest/merge/tournament
  levels → result) that reach a running JAX profiler's trace on the
  device's clock; a recording :class:`Tracer` also keeps them for
  Chrome-trace-event JSON export viewable in Perfetto, and the
  :class:`NullTracer` default keeps nothing.
* :mod:`repro.obs.metrics` — *what did the dataplane's state look like?*
  A :class:`MetricsRegistry` of counters/gauges/histograms/series (keys
  in/out per hop, segment occupancy, run-length histogram, reorder-depth
  timeline, arena fill, control-plane handoffs) snapshotable into
  ``PipelineResult.telemetry``.
* :mod:`repro.obs.telemetry` — *what did each key experience?*  INT-style
  per-hop metadata columns (:class:`IntColumns`: hop id, queue depth,
  rank ticks) stamped onto the ``WireBatch`` and riding the wire to
  egress, mirroring how programmable switches export state in-band.

Recording is opt-in: the dataplane's default arguments are
``tracer=None`` / ``metrics=None`` / ``int_telemetry=False``, and the
pipeline's output is byte-identical with observability on or off (gated
by ``tests/test_obs_transparency.py`` and the CI overhead gate).
"""

from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    Series,
    default_registry,
)
from repro.obs.telemetry import INT_FIELDS, IntColumns, int_summary
from repro.obs.trace import NULL_TRACER, NullTracer, Span, Tracer

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "INT_FIELDS",
    "IntColumns",
    "MetricsRegistry",
    "NULL_TRACER",
    "NullTracer",
    "Series",
    "Span",
    "Tracer",
    "default_registry",
    "int_summary",
]
