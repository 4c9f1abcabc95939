"""Observability subsystem: metrics, spans, throughput artifacts.

The measurement layer every perf PR is judged with:

* :mod:`repro.obs.metrics` — a typed metrics registry (counters,
  gauges, fixed-bucket histograms with quantile estimation), labeled by
  ``(p, refine, policy, devices)``, with snapshot/merge/diff semantics
  and Prometheus-text + JSON export.  ``ElasticityService.stats`` is a
  read-only view over one of these.
* :mod:`repro.obs.spans` — per-request lifecycle spans and the
  service's host spans on the profiler's clock, exportable as a
  JSON-lines event log and a Chrome ``trace_event`` file viewable in
  Perfetto.
* :mod:`repro.obs.throughput` — kernel-level operator apply throughput
  (DoF/s, effective GB/s against the streaming-bytes model) on the
  batched path; feeds ``benchmarks/operator_sweep.py`` and the
  ``BENCH_*.json`` perf trajectory.
* :mod:`repro.obs.schema` — a dependency-free JSON-schema validator for
  the ``BENCH_*.json`` artifact schemas checked into
  ``benchmarks/schemas/``.
"""

from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_latency_edges,
    merge_snapshots,
    diff_snapshots,
)
from repro.obs.spans import Span, SpanRecorder
from repro.obs.schema import SchemaError, validate_json

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "default_latency_edges",
    "merge_snapshots",
    "diff_snapshots",
    "Span",
    "SpanRecorder",
    "SchemaError",
    "validate_json",
]
