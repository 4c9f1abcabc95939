"""The trace reduction: busy union, idle share, gaps and per-program
times, on synthetic intervals and on a small trace recorded on a TPU v5e
(``bench/tools/record_trace.py``)."""

from pathlib import Path

import pytest

from bench.lib import trace as tr

DATA = Path(__file__).resolve().parent / "data"
SAMPLE = DATA / "tpu_v5e_sample.xplane.pb"


def test_union_merges_overlaps_and_clips():
    ev = [("a", 0.0, 2.0), ("b", 1.0, 3.0), ("c", 5.0, 6.0), ("d", 9.0, 12.0)]
    assert tr.union(ev, 0.0, 10.0) == pytest.approx(3.0 + 1.0 + 1.0)
    assert tr.union(ev, 2.5, 5.5) == pytest.approx(0.5 + 0.5)
    assert tr.union([], 0.0, 1.0) == 0.0


def test_gaps_are_the_complement():
    ev = [("a", 1.0, 2.0), ("b", 1.5, 4.0), ("c", 6.0, 7.0)]
    g = tr.gaps(ev, 0.0, 10.0)
    assert g == [(0.0, 1.0), (4.0, 6.0), (7.0, 10.0)]
    assert sum(e - s for s, e in g) + tr.union(ev, 0, 10) == pytest.approx(10)


def test_idle_gaps_are_named_by_the_innermost_host_span():
    t = tr.Trace(
        ops={"/device:TPU:0": [("op", 1.0, 2.0), ("op", 5.0, 9.0)]},
        host=[("bench.window", 0.0, 10.0), ("bench.step", 2.0, 4.5),
              ("python_thing", 0.0, 10.0)],
    )
    assert t.busy(0.0, 10.0) == pytest.approx(5.0)
    assert t.idle_gaps(0.0, 10.0) == [
        ("bench.step", pytest.approx(3.0)),
        ("bench.window", pytest.approx(1.0)),
        ("bench.window", pytest.approx(1.0)),
    ]


def test_busy_averages_over_devices():
    t = tr.Trace(ops={"/device:TPU:0": [("x", 0.0, 4.0)],
                      "/device:TPU:1": [("x", 0.0, 2.0)]})
    assert t.busy(0.0, 10.0) == pytest.approx(3.0)
    assert t.op_seconds(0.0, 10.0) == {"x": pytest.approx(3.0)}


def test_self_times_leave_out_nested_operations():
    ev = [("loop", 0.0, 10.0), ("a", 1.0, 3.0), ("b", 4.0, 9.0),
          ("c", 5.0, 6.0), ("d", 11.0, 12.0)]
    got = {n: t for n, _, t in tr.self_times(ev)}
    assert got == pytest.approx({"loop": 3.0, "a": 2.0, "b": 4.0, "c": 1.0,
                                 "d": 1.0})
    t = tr.Trace(ops={"/device:TPU:0": ev})
    assert sum(t.op_seconds(0, 20).values()) == pytest.approx(t.busy(0, 20))


def test_recorded_tpu_trace():
    """Three runs of one program on a v5e: one device plane, the three
    runs on the modules line, the operations named by their HLO names."""
    t = tr.load(str(SAMPLE))
    assert list(t.ops) == ["/device:TPU:0"]
    runs = t.module_times("jit_bench_sample")
    assert len(runs) == 3 and all(1e-5 < r < 1e-3 for r in runs)
    ops = t.ops["/device:TPU:0"]
    lo, hi = min(s for _, s, _ in ops), max(e for _, _, e in ops)
    busy = t.busy(lo, hi)
    assert 0.9 * sum(runs) <= busy <= sum(runs)
    per_op = t.op_seconds(lo, hi)
    assert set(per_op) == {"copy-start", "copy-done", "fusion"}
    assert sum(per_op.values()) == pytest.approx(busy)
    wlo, whi = t.span("bench.window")
    assert whi - wlo > sum(runs)
    assert abs(wlo - lo) < 5e-3  # host and device clocks, within 5 ms
