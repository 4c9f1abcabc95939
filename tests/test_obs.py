"""Observability subsystem tests.

Four layers, mirroring the obs package:

* metrics registry — counter/gauge/histogram semantics, exact
  snapshot round-trips, merge/diff algebra, Prometheus text format,
  histogram bucket invariants (property-based where hypothesis is
  available);
* spans — injected-clock lifecycle (no span left open, the per-ticket
  identity queue_wait + solve == submit-to-retire wall),
  Chrome trace / JSON-lines export;
* service integration — the migrated ``stats`` view is value-identical
  to the registry counters (at 1 device and, in the multidevice lane,
  8), span counts reconcile EXACTLY with SchedulerTrace decisions
  and the registry counters, a recorder adds no device sync and changes
  no scheduling decision, and the host spans and device scopes reach a
  profiler trace;
* artifact schemas — the dependency-free validator enforces the
  checked-in BENCH_*.json contracts.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np
import pytest

from repro.obs.metrics import (
    Histogram,
    MetricsRegistry,
    default_latency_edges,
    diff_snapshots,
    merge_snapshots,
)
from repro.obs.schema import SchemaError, validate_json, validation_errors
from repro.obs.spans import SpanRecorder

from tests._hypothesis_compat import given, settings, st


# ---------------------------------------------------------------------------
# metrics: counters / gauges / registry semantics
# ---------------------------------------------------------------------------
class TestRegistry:
    def test_counter_accumulates_and_rejects_negative(self):
        reg = MetricsRegistry()
        c = reg.counter("requests_total", "n", p=2)
        c.inc()
        c.inc(3.0)
        assert reg.value("requests_total", p=2) == 4.0
        with pytest.raises(ValueError, match=">= 0"):
            c.inc(-1)

    def test_gauge_set_inc_dec(self):
        reg = MetricsRegistry()
        g = reg.gauge("inflight", "n")
        g.set(5)
        g.inc()
        g.dec(2)
        assert reg.value("inflight") == 4.0

    def test_label_sets_are_independent_and_total_sums(self):
        reg = MetricsRegistry()
        reg.counter("chunks_total", "n", p=1).inc(2)
        reg.counter("chunks_total", "n", p=2).inc(5)
        assert reg.value("chunks_total", p=1) == 2.0
        assert reg.value("chunks_total", p=2) == 5.0
        assert reg.total("chunks_total") == 7.0
        # never-touched label set of a known family reads 0; unknown
        # family totals 0 (callers aggregate optimistically)
        assert reg.value("chunks_total", p=9) == 0.0
        assert reg.total("nope_total") == 0.0

    def test_kind_conflict_is_an_error(self):
        reg = MetricsRegistry()
        reg.counter("x_total", "n")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("x_total", "n")

    def test_histogram_edge_conflict_is_an_error(self):
        reg = MetricsRegistry()
        reg.histogram("lat", "s", edges=(1.0, 2.0))
        with pytest.raises(ValueError, match="different"):
            reg.histogram("lat", "s", edges=(1.0, 3.0))
        # same edges: fine (same family, new label set)
        reg.histogram("lat", "s", edges=(1.0, 2.0), p=4)

    def test_invalid_metric_name_rejected(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError, match="invalid metric name"):
            reg.counter("bad name", "n")

    def test_default_latency_edges_cover_serving_range(self):
        edges = default_latency_edges()
        assert all(a < b for a, b in zip(edges, edges[1:]))
        assert edges[0] <= 1e-3 and edges[-1] >= 100.0
        assert all(math.isfinite(e) for e in edges)


# ---------------------------------------------------------------------------
# metrics: histogram invariants
# ---------------------------------------------------------------------------
class TestHistogram:
    def test_le_convention(self):
        # Prometheus le convention: bucket i counts v <= edges[i].
        h = Histogram(edges=(1.0, 2.0, 4.0))
        for v in (0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 5.0):
            h.observe(v)
        assert h.counts == [2, 2, 2, 1]  # (<=1], (1,2], (2,4], (4,inf)
        assert h.count == 7
        assert h.vmin == 0.5 and h.vmax == 5.0

    def test_quantile_clamps_to_observed_range(self):
        h = Histogram(edges=(1.0, 100.0))
        h.observe(40.0)
        # one sample in a huge bucket: the estimate must be the sample,
        # not the bucket midpoint
        assert h.quantile(0.5) == 40.0
        assert h.quantile(0.0) == 40.0
        assert h.quantile(1.0) == 40.0

    def test_quantile_empty_and_bad_q(self):
        h = Histogram(edges=(1.0,))
        assert math.isnan(h.quantile(0.5))
        h.observe(0.5)
        with pytest.raises(ValueError):
            h.quantile(1.5)

    def test_edges_must_increase_and_be_finite(self):
        with pytest.raises(ValueError, match="strictly increasing"):
            Histogram(edges=(1.0, 1.0))
        with pytest.raises(ValueError, match="finite"):
            Histogram(edges=(1.0, math.inf))
        with pytest.raises(ValueError, match="at least one"):
            Histogram(edges=())

    def test_quantiles_monotone_against_numpy(self):
        rng = np.random.default_rng(3)
        vals = rng.lognormal(mean=-2.0, sigma=1.0, size=500)
        h = Histogram(default_latency_edges())
        for v in vals:
            h.observe(v)
        qs = [0.1, 0.5, 0.9, 0.99]
        est = h.quantiles(qs)
        assert est == sorted(est)
        # bucket resolution is ~33%/bucket: estimates must land within
        # one bucket of numpy's exact percentiles
        for q, e in zip(qs, est):
            exact = float(np.percentile(vals, 100 * q))
            assert e / exact < 10 ** (1 / 8) * 1.05
            assert exact / e < 10 ** (1 / 8) * 1.05

    @settings(max_examples=50, deadline=None)
    @given(
        st.lists(
            st.floats(
                min_value=1e-6,
                max_value=1e3,
                allow_nan=False,
                allow_infinity=False,
            ),
            min_size=1,
            max_size=100,
        )
    )
    def test_bucket_invariants_property(self, values):
        h = Histogram(default_latency_edges())
        for v in values:
            h.observe(v)
        # conservation: every observation lands in exactly one bucket
        assert sum(h.counts) == h.count == len(values)
        assert h.vmin == min(values) and h.vmax == max(values)
        assert np.isclose(h.sum, sum(values))
        # every quantile estimate stays inside the observed range
        for q in (0.0, 0.25, 0.5, 0.75, 0.9, 1.0):
            assert h.vmin <= h.quantile(q) <= h.vmax


# ---------------------------------------------------------------------------
# metrics: snapshot / merge / diff / export
# ---------------------------------------------------------------------------
def _loaded_registry():
    reg = MetricsRegistry(clock=lambda: 123.0)
    reg.counter("service_chunks_total", "chunks", p=1, policy="fixed").inc(7)
    reg.counter("service_chunks_total", "chunks", p=2, policy="fixed").inc(3)
    reg.gauge("inflight", "rows", p=1).set(2)
    h = reg.histogram("lat_seconds", "latency", p=1)
    for v in (0.002, 0.4, 1.7, 22.0):
        h.observe(v)
    return reg


class TestSnapshots:
    def test_round_trip_exact(self):
        reg = _loaded_registry()
        snap = reg.snapshot()
        again = MetricsRegistry.from_snapshot(snap).snapshot()
        assert again == snap
        # the JSON round-trip is exact too (plain data only)
        assert json.loads(json.dumps(snap)) == snap

    def test_from_snapshot_restores_live_cells(self):
        reg = MetricsRegistry.from_snapshot(_loaded_registry().snapshot())
        assert reg.total("service_chunks_total") == 10.0
        h = reg.get_histogram("lat_seconds", p=1)
        assert h.count == 4 and h.vmin == 0.002 and h.vmax == 22.0
        # restored registry keeps accumulating
        reg.counter("service_chunks_total", p=1, policy="fixed").inc()
        assert reg.total("service_chunks_total") == 11.0

    def test_unknown_schema_rejected(self):
        with pytest.raises(ValueError, match="schema"):
            MetricsRegistry.from_snapshot({"schema": "bogus/v9", "families": {}})

    def test_merge_adds_counters_and_buckets(self):
        a = _loaded_registry().snapshot()
        m = merge_snapshots(a, a)
        reg = MetricsRegistry.from_snapshot(m)
        assert reg.total("service_chunks_total") == 20.0
        h = reg.get_histogram("lat_seconds", p=1)
        assert h.count == 8 and h.sum == pytest.approx(2 * (0.002 + 0.4 + 1.7 + 22.0))
        assert h.vmin == 0.002 and h.vmax == 22.0
        # gauges take the right-hand snapshot's value, not the sum
        assert reg.value("inflight", p=1) == 2.0

    def test_merge_disjoint_label_sets(self):
        a = MetricsRegistry()
        a.counter("c_total", "n", p=1).inc(1)
        b = MetricsRegistry()
        b.counter("c_total", "n", p=2).inc(5)
        reg = MetricsRegistry.from_snapshot(
            merge_snapshots(a.snapshot(), b.snapshot())
        )
        assert reg.value("c_total", p=1) == 1.0
        assert reg.value("c_total", p=2) == 5.0

    def test_diff_is_the_window_between_snapshots(self):
        reg = _loaded_registry()
        before = reg.snapshot()
        reg.counter("service_chunks_total", "chunks", p=1, policy="fixed").inc(5)
        reg.get_histogram("lat_seconds", p=1).observe(0.1)
        window = diff_snapshots(reg.snapshot(), before)
        w = MetricsRegistry.from_snapshot(window)
        assert w.value("service_chunks_total", p=1, policy="fixed") == 5.0
        assert w.value("service_chunks_total", p=2, policy="fixed") == 0.0
        assert w.get_histogram("lat_seconds", p=1).count == 1

    def test_diff_rejects_backwards_counters(self):
        a = MetricsRegistry()
        a.counter("c_total", "n").inc(5)
        big = a.snapshot()
        b = MetricsRegistry()
        b.counter("c_total", "n").inc(2)
        with pytest.raises(ValueError, match="backwards"):
            diff_snapshots(b.snapshot(), big)

    def test_prometheus_text_format(self):
        text = _loaded_registry().to_prometheus_text()
        assert "# TYPE service_chunks_total counter" in text
        assert 'service_chunks_total{p="1",policy="fixed"} 7' in text
        assert "# TYPE lat_seconds histogram" in text
        assert '# HELP lat_seconds latency' in text
        # cumulative le buckets ending in +Inf == count
        assert 'lat_seconds_bucket{p="1",le="+Inf"} 4' in text
        assert 'lat_seconds_count{p="1"} 4' in text
        # cumulative: the largest finite bucket holds <= the total count
        lines = [
            ln for ln in text.splitlines() if ln.startswith("lat_seconds_bucket")
        ]
        cums = [int(ln.rsplit(" ", 1)[1]) for ln in lines]
        assert cums == sorted(cums)

    def test_to_json_stamps_injected_clock(self):
        doc = json.loads(_loaded_registry().to_json())
        assert doc["generated_unix"] == 123.0
        assert doc["schema"] == "repro.obs.metrics/v1"


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------
class _FakeClock:
    """Deterministic clock: advances 1.0 per call."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


class TestSpans:
    def test_begin_end_lifecycle(self):
        rec = SpanRecorder(clock=_FakeClock())
        sid = rec.begin("prep", cat="flight", tid=3, key="k")
        assert rec.open_count == 1
        span = rec.end(sid, rows=2)
        assert rec.open_count == 0
        assert span.start == 1.0 and span.end == 2.0 and span.duration == 1.0
        assert span.args == {"key": "k", "rows": 2}
        assert rec.count("prep") == 1 and rec.count() == 1

    def test_clear_refuses_open_spans(self):
        rec = SpanRecorder(clock=_FakeClock())
        rec.begin("x")
        with pytest.raises(RuntimeError, match="still open"):
            rec.clear()

    def test_chrome_trace_events(self, tmp_path):
        rec = SpanRecorder(clock=_FakeClock())
        rec.thread_name(0, "engine")
        rec.emit("a", cat="c", tid=0, start=10.0, end=10.5, n=1)
        rec.emit("b", cat="c", tid=1, start=10.25, end=11.0)
        events = rec.to_events()
        meta = [e for e in events if e["ph"] == "M"]
        xs = [e for e in events if e["ph"] == "X"]
        assert meta[0]["args"]["name"] == "engine"
        # microseconds, rebased to the earliest span start
        assert xs[0]["ts"] == 0.0 and xs[0]["dur"] == pytest.approx(0.5e6)
        assert xs[1]["ts"] == pytest.approx(0.25e6)
        path = tmp_path / "trace.json"
        rec.to_chrome_trace(str(path))
        doc = json.loads(path.read_text())
        assert len(doc["traceEvents"]) == 3
        assert doc["otherData"]["schema"] == "repro.obs.spans/v1"

    def test_jsonl_round_trip(self, tmp_path):
        rec = SpanRecorder(clock=_FakeClock())
        rec.emit("a", tid=2, start=1.0, end=2.0, k=3)
        path = tmp_path / "events.jsonl"
        rec.to_jsonl(str(path))
        rows = [json.loads(ln) for ln in path.read_text().splitlines()]
        assert rows == [
            {
                "name": "a",
                "cat": "",
                "tid": 2,
                "start": 1.0,
                "end": 2.0,
                "dur": 1.0,
                "args": {"k": 3},
            }
        ]


# ---------------------------------------------------------------------------
# service integration
# ---------------------------------------------------------------------------
def _mixed_requests(n, p=1, refine=1):
    from repro.serve.elasticity_service import SolveRequest

    return [
        SolveRequest(
            p=p,
            refine=refine,
            materials={1: (50.0 + 5 * (i % 2), 50.0), 2: (1.0, 1.0)},
            traction=(0.0, 0.0, -1e-2 * (1 + 0.1 * (i % 3))),
            rel_tol=1e-8 if i % 3 == 0 else 1e-4,
        )
        for i in range(n)
    ]


LEGACY_KEYS = {
    "cache_hits", "cache_misses", "generations", "chunks",
    "chunk_iters_dispatched", "wasted_iters", "refills", "rebuckets",
    "prep_calls", "prep_row_copies", "precision_fallbacks",
    # recovery counters (PR 9): checkpoint writes, restores, and step
    # watchdog fires ride the same registry/stats surface
    "checkpoints_written", "restores", "watchdog_fires",
}


class TestServiceIntegration:
    def test_stats_view_matches_registry(self):
        from repro.serve.elasticity_service import ElasticityService

        svc = ElasticityService(max_batch=4, chunk_iters=6)
        reports = svc.solve_continuous(_mixed_requests(6))
        assert all(r.converged for r in reports)
        assert set(svc.stats) == LEGACY_KEYS
        legacy = dict(svc.stats)
        for k in LEGACY_KEYS:
            assert legacy[k] == int(
                svc.registry.total(f"service_{k}_total")
            ), k
            assert isinstance(legacy[k], int)
        # the view is read-only: it has no __setitem__
        with pytest.raises(TypeError):
            svc.stats["chunks"] = 0

    def test_counters_carry_uniform_labels(self):
        from repro.serve.elasticity_service import ElasticityService

        svc = ElasticityService(max_batch=2, chunk_iters=6)
        svc.solve_continuous(_mixed_requests(2))
        v = svc.registry.value(
            "service_chunks_total", p=1, refine=1, policy="fixed", devices=1,
            precision="f64",
        )
        assert v == svc.stats["chunks"] > 0

    def test_span_trace_counter_reconciliation(self):
        """The acceptance invariant: span counts == SchedulerTrace
        decision count == registry counters, exactly."""
        from repro.serve.elasticity_service import ElasticityService

        rec = SpanRecorder()
        svc = ElasticityService(max_batch=4, chunk_iters=6, spans=rec)
        n = 6
        reports = svc.solve_continuous(_mixed_requests(n))
        assert len(reports) == n
        assert rec.open_count == 0, [s.name for s in rec.open_spans()]
        assert (
            rec.count("dispatch")
            == len(svc.trace.decisions)
            == svc.stats["chunks"]
        )
        assert rec.count("queue_wait") == svc.stats["refills"] == n
        assert rec.count("solve") == n
        # one retire pass per flight per step; an admit follows each
        # pass that leaves the flight work, and every step is recorded
        steps = rec.count("step")
        assert steps == svc._step_index
        assert rec.count("retire") >= steps - 1
        assert (rec.count("dispatch") <= rec.count("admit")
                <= rec.count("retire"))
        # prep spans: one per step that reset rows (refills/rebuckets)
        assert rec.count("prep") >= 1
        assert sum(s.args["rows_reset"] for s in rec.by_name("prep")) >= n
        assert rec.count("build") == svc.stats["cache_misses"] == 1
        # dispatch args reconcile with the trace decisions
        for span, dec in zip(rec.by_name("dispatch"), svc.trace.decisions):
            assert span.args["chunk"] == dec.chunk
            assert span.args["bucket"] == dec.bucket
            assert span.args["live"] == len(dec.live_slots)

    def test_injected_clock_lifecycle_identity(self):
        """With a deterministic clock: no span left open, every span
        well-ordered, and per ticket the queue_wait span (submit to
        admit) plus the solve span (admit to retire) sums EXACTLY to the
        submit-to-retire wall, with nothing left over: a recorder no
        longer splits a request's time into fenced compute and the
        rest."""
        from repro.serve.elasticity_service import ElasticityService

        clock = _FakeClock()
        rec = SpanRecorder(clock=clock)
        svc = ElasticityService(
            max_batch=2, chunk_iters=6, spans=rec, clock=clock
        )
        reports = svc.solve_continuous(_mixed_requests(3))
        assert all(r.converged for r in reports)
        assert rec.open_count == 0
        assert all(s.end >= s.start for s in rec.spans)
        solves = {s.args["ticket"]: s for s in rec.by_name("solve")}
        waits = {s.args["ticket"]: s for s in rec.by_name("queue_wait")}
        assert sorted(solves) == sorted(waits) == [0, 1, 2]
        for ticket, s in solves.items():
            w = waits[ticket]
            assert set(s.args) == {
                "ticket", "iterations", "converged", "queue_wait"
            }
            assert s.args["queue_wait"] == w.duration >= 0
            # submit -> admit -> retire: the two spans meet at admission
            assert w.end == s.start
            assert w.duration + s.duration == s.end - w.start
        assert [r.ticket for r in reports] == [0, 1, 2]
        # the host spans are stamped by the same clock and nest in steps
        steps = rec.by_name("step")
        for name in ("retire", "admit", "prep", "dispatch"):
            for sp in rec.by_name(name):
                assert any(st.start < sp.start <= sp.end < st.end
                           for st in steps), name

    def test_latency_summary_quantiles(self):
        from repro.serve.elasticity_service import ElasticityService

        svc = ElasticityService(max_batch=4, chunk_iters=6)
        assert svc.latency_summary() == {}
        n = 4
        svc.solve_continuous(_mixed_requests(n))
        lat = svc.latency_summary()
        assert lat["count"] == n
        assert 0 < lat["p50"] <= lat["p90"] <= lat["p99"]
        h = svc.registry.merged_histogram("request_latency_seconds")
        assert h.count == n

    def test_generational_path_observability(self):
        from repro.serve.elasticity_service import ElasticityService

        rec = SpanRecorder()
        svc = ElasticityService(max_batch=4, spans=rec)
        n = 5  # 2 generations: 4 + 1
        reports = svc.solve(_mixed_requests(n))
        assert all(r.converged for r in reports)
        assert svc.stats["generations"] == 2 == rec.count("generation")
        assert (
            svc.registry.merged_histogram("request_latency_seconds").count
            == n
        )

    def test_no_fence_when_spans_disabled(self):
        """No chunk is ever fenced, recorder or not: no chunk_device
        histogram family or span appears, and the recorder has no fence
        to ask for (device time comes from a profiler trace)."""
        from repro.serve.elasticity_service import ElasticityService

        rec = SpanRecorder()
        assert not hasattr(rec, "fence")
        for spans in (None, rec):
            svc = ElasticityService(max_batch=2, chunk_iters=6, spans=spans)
            svc.solve_continuous(_mixed_requests(2))
            assert svc.registry.get_histogram(
                "chunk_device_seconds", p=1, refine=1, policy="fixed",
                devices=1, precision="f64",
            ) is None
        assert rec.count("dispatch") > 0
        assert rec.count("chunk_device") == 0

    def test_shared_registry_across_services(self):
        """Two services can share one registry (merge-at-source); totals
        accumulate across both."""
        from repro.serve.elasticity_service import ElasticityService

        reg = MetricsRegistry()
        a = ElasticityService(max_batch=2, chunk_iters=6, registry=reg)
        b = ElasticityService(max_batch=2, chunk_iters=6, registry=reg)
        a.solve_continuous(_mixed_requests(2))
        chunks_a = reg.total("service_chunks_total")
        b.solve_continuous(_mixed_requests(2))
        assert reg.total("service_chunks_total") > chunks_a
        assert a.stats["chunks"] == b.stats["chunks"]  # shared view

    @pytest.mark.multidevice
    def test_stats_view_differential_8_devices(self):
        """The migrated stats view stays value-identical to the registry
        under scenario sharding, and span counts still reconcile."""
        import jax

        from repro.distributed.sharding import scenario_mesh
        from repro.serve.elasticity_service import ElasticityService

        if jax.device_count() < 8:
            pytest.skip(
                f"needs 8 devices, have {jax.device_count()} "
                "(run with REPRO_HOST_DEVICES=8)"
            )
        rec = SpanRecorder()
        svc = ElasticityService(
            max_batch=8, chunk_iters=6, mesh=scenario_mesh(8), spans=rec
        )
        n = 6
        reports = svc.solve_continuous(_mixed_requests(n))
        assert all(r.converged for r in reports)
        for k in LEGACY_KEYS:
            assert svc.stats[k] == int(
                svc.registry.total(f"service_{k}_total")
            ), k
        assert svc.registry.value(
            "service_chunks_total",
            p=1, refine=1, policy="fixed", devices=8, precision="f64",
        ) == svc.stats["chunks"]
        assert rec.open_count == 0
        assert rec.count("chunk_dispatch") == svc.stats["chunks"]
        assert rec.count("solve") == n


# ---------------------------------------------------------------------------
# benchmark consolidation + artifact schemas
# ---------------------------------------------------------------------------
class TestArtifactSchemas:
    def test_validator_reports_paths(self):
        schema = {
            "type": "object",
            "required": ["rows"],
            "properties": {
                "rows": {
                    "type": "array",
                    "items": {
                        "type": "object",
                        "required": ["dofs_per_s"],
                        "properties": {
                            "dofs_per_s": {
                                "type": "number",
                                "exclusiveMinimum": 0,
                            }
                        },
                    },
                }
            },
        }
        errs = validation_errors(
            {"rows": [{"dofs_per_s": 1.0}, {"dofs_per_s": -2.0}, {}]}, schema
        )
        assert any("rows[1].dofs_per_s" in e for e in errs)
        assert any("rows[2]" in e and "dofs_per_s" in e for e in errs)
        with pytest.raises(SchemaError, match="rows"):
            validate_json({"rows": [{}]}, schema)

    def test_validator_type_discipline(self):
        assert validation_errors(3, {"type": "integer"}) == []
        assert validation_errors(3.0, {"type": "integer"}) == []
        assert validation_errors(True, {"type": "integer"}) != []
        assert validation_errors(True, {"type": "boolean"}) == []
        assert validation_errors(3, {"type": "number"}) == []
        assert validation_errors(None, {"type": ["number", "null"]}) == []
        assert validation_errors(float("nan"), {"type": "number"}) != []
        assert validation_errors("x", {"enum": ["memory", "compute"]}) != []
        assert (
            validation_errors(
                {"a": 1, "b": 2},
                {
                    "type": "object",
                    "properties": {"a": {}},
                    "additionalProperties": False,
                },
            )
            != []
        )

    def test_checked_in_schemas_are_loadable(self):
        import os

        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        sdir = os.path.join(here, "benchmarks", "schemas")
        names = sorted(os.listdir(sdir))
        assert names == [
            "bench_operator_sweep.schema.json",
            "bench_serving.schema.json",
        ]
        for n in names:
            with open(os.path.join(sdir, n)) as f:
                schema = json.load(f)
            assert schema["type"] == "object"
            assert "rows" in schema["properties"]

    def test_operator_throughput_row_matches_schema(self):
        """One real measured row (tiny: p=1, refine=0, batch=1)
        validates against the checked-in artifact row schema — the
        producer and the contract cannot drift."""
        import os

        from repro.launch.roofline import place_measured
        from repro.obs.throughput import operator_throughput

        row = operator_throughput(
            1, 0, 1, repeats=1, min_time_s=0.0
        )
        placed = place_measured(
            flops_per_apply=row["flops_per_apply"],
            bytes_per_apply=row["bytes_per_apply"],
            t_apply_s=row["t_apply_s"],
        )
        row["v5e_roof_fraction"] = placed.fraction
        row["v5e_bound"] = placed.bound
        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(
            os.path.join(
                here, "benchmarks", "schemas",
                "bench_operator_sweep.schema.json",
            )
        ) as f:
            schema = json.load(f)
        validate_json(row, schema["properties"]["rows"]["items"])
        # physical sanity: DoF/s and the models agree with each other
        assert row["dofs_per_s"] == pytest.approx(
            row["dofs"] / row["t_apply_s"]
        )
        assert row["oi_model"] == pytest.approx(
            row["flops_per_apply"] / row["bytes_per_apply"]
        )

    def test_streaming_bytes_model_matches_fig6(self):
        """obs.throughput and fig6_roofline must use the SAME
        streaming-bytes model."""
        from repro.obs.throughput import streaming_bytes_per_elem

        for p in (1, 2, 4, 8):
            D, Q = p + 1, p + 2
            assert streaming_bytes_per_elem(p, 8) == 8 * (
                2 * 3 * D**3 + 2 * Q**3
            )

    def test_latency_percentiles_consolidated(self):
        """The benchmark's percentile helper must agree with the obs
        histogram quantiles (same estimator, not np.percentile)."""
        from benchmarks.batched_throughput import _latency_percentiles

        vals = [0.01, 0.02, 0.03, 0.5, 1.2, 3.0, 7.7, 20.0]
        p50, p95 = _latency_percentiles(vals)
        h = Histogram(default_latency_edges())
        for v in vals:
            h.observe(v)
        assert p50 == h.quantile(0.5)
        assert p95 == h.quantile(0.95)


# ---------------------------------------------------------------------------
# recording cost: no sync, no scheduling change
# ---------------------------------------------------------------------------
def test_recorder_adds_no_sync_and_no_scheduling_change(monkeypatch):
    """What a recorder may cost, checked deterministically: attached to
    the service it calls ``jax.block_until_ready`` zero times, and the
    scheduler's decisions (chunk lengths, refills, buckets, consumed
    iterations) equal those of the recorder-free run."""
    import jax

    from repro.serve.elasticity_service import ElasticityService

    calls = []
    real = jax.block_until_ready

    def counting(x):
        calls.append(1)
        return real(x)

    def run(spans):
        svc = ElasticityService(max_batch=4, chunk_iters=3, spans=spans)
        reports = svc.solve_continuous(_mixed_requests(6, p=1, refine=1))
        assert all(r.converged for r in reports)
        return svc.trace.decisions, [r.iterations for r in reports]

    base = run(None)
    monkeypatch.setattr(jax, "block_until_ready", counting)
    rec = SpanRecorder()
    observed = run(rec)
    assert calls == []
    assert rec.count("dispatch") == len(observed[0]) > 1
    assert observed == base


# ---------------------------------------------------------------------------
# the profiler trace: host spans and device scopes
# ---------------------------------------------------------------------------
def _host_events(logdir):
    """(name, start seconds on the profiler's clock) of every host event
    of the trace under ``logdir``."""
    from jax.profiler import ProfileData

    (path,) = Path(logdir).rglob("*.xplane.pb")
    data = ProfileData.from_file(str(path))
    t0 = None
    for plane in data.planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            t0 = stats["profile_start_time"] * 1e-9
    assert t0 is not None, "trace has no profile_start_time"
    return [
        (ev.name, t0 + ev.start_ns * 1e-9)
        for plane in data.planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
    ]


def test_service_spans_reach_the_profiler_trace(tmp_path):
    """Under ``jax.profiler``, a continuous solve and a generational one
    put every ``service.*`` span of the docs' taxonomy into the host
    plane, and each recorder span starts within 1 ms of its annotation:
    the recorder runs on the profiler's clock."""
    import jax

    from repro.serve.elasticity_service import (
        SERVICE_SPANS,
        ElasticityService,
    )

    doc = (Path(__file__).resolve().parents[1] / "docs"
           / "OBSERVABILITY.md").read_text()
    for name in SERVICE_SPANS:
        assert f"`service.{name}`" in doc, name
    rec = SpanRecorder()
    svc = ElasticityService(max_batch=2, chunk_iters=2, spans=rec)
    jax.profiler.start_trace(str(tmp_path))
    try:
        svc.solve_continuous(_mixed_requests(3, p=1, refine=0))
        svc.solve(_mixed_requests(1, p=1, refine=0))
    finally:
        jax.profiler.stop_trace()
    host = _host_events(tmp_path)
    for name in SERVICE_SPANS:
        starts = sorted(t for n, t in host if n == f"service.{name}")
        spans = sorted(s.start for s in rec.by_name(name))
        assert len(starts) == len(spans) > 0, name
        worst = max(abs(a - b) for a, b in zip(starts, spans))
        assert worst < 1e-3, (name, worst)


def test_step_program_carries_every_scope():
    """The lowered step programs (start and resume) and the prep program
    of a small mixed-precision solver carry every scope name the device
    trace is split by."""
    import jax

    from repro.core.operators import PA_APPLY
    from repro.fem.mesh import beam_hex
    from repro.solvers import batched as b
    from repro.solvers.chebyshev import PREP_POWER
    from repro.solvers.gmg import VCYCLE_COARSE, VCYCLE_PARTS, vcycle_scope

    solver = b.BatchedGMGSolver(beam_hex(), 1, 2, precision="mixed")
    s = 1

    def sds(a):
        return jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype)

    state = jax.tree.map(sds, solver.empty_state(s))
    prep = jax.tree.map(sds, solver.empty_prep(s))
    f64 = np.float64
    args = (jax.ShapeDtypeStruct((s, 3), f64), jax.ShapeDtypeStruct((s,), f64),
            jax.ShapeDtypeStruct((s,), np.bool_), state, prep,
            jax.ShapeDtypeStruct((), np.int32))
    start, resume = (solver._jit_chunk[k].lower(*args).as_text(debug_info=True)
                     for k in (True, False))
    levels = len(solver.spaces)
    vcycle = [VCYCLE_COARSE] + [vcycle_scope(lv, part)
                                for lv in range(1, levels)
                                for part in VCYCLE_PARTS]
    loop = [b.PCG_APPLY, b.PCG_PRECOND, b.PCG_VECTOR, b.PCG_AUDIT, PA_APPLY]
    for name in loop + vcycle:
        assert name in start and name in resume, name
    assert b.PCG_INIT in start and b.PCG_INIT not in resume
    fields = jax.ShapeDtypeStruct((s, solver.fine_space.nelem), f64)
    prepare = solver._jit_prepare.lower(
        fields, fields, jax.ShapeDtypeStruct((s,), np.bool_), prep
    ).as_text(debug_info=True)
    for name in (b.PREP, PREP_POWER, b.PREP_COARSE, PA_APPLY):
        assert name in prepare, name
