"""The split of a traced window by layer (``bench/lib/scopes.py``): scope
paths from a TPU trace's ``tf_op`` and from a program's HLO, outermost
``pcg.*`` attribution with self time, idle named by the innermost
service span, and the readers on the committed v5e sample."""

from pathlib import Path

import pytest

from bench.lib import harness, scopes
from bench.lib import trace as tr

SAMPLE = Path(__file__).resolve().parent / "data" / "tpu_v5e_sample.xplane.pb"
NEW = ("pcg_apply_s", "vcycle_s", "pcg_vector_s", "pcg_audit_s",
       "service_idle_s")
OLD = ("device_idle",)


def test_tf_op_is_read_from_the_tpu_sample():
    paths = scopes.xspace_op_paths(str(SAMPLE))
    run = tr.load(str(SAMPLE)).modules["/device:TPU:0"][0][0]
    assert run == "jit_bench_sample(6189173901579575195)"
    assert paths == {"/device:TPU:0": {
        (scopes.program_id(run), "fusion"): "jit(bench_sample)/dot_general"}}


@pytest.mark.parametrize("path, layer", [
    ("jit(_chunk_resume)/while/body/pcg.apply/pa.apply/mul", "pcg.apply"),
    ("jit(_chunk_start)/pcg.init/vcycle.L3.smooth/pa.apply/add", "pcg.init"),
    ("jit(_chunk_resume)/pcg.audit/pcg.precond/vcycle.coarse/dot", "pcg.audit"),
    ("jit(_prepare_impl)/prep/prep.power/while/body/pa.apply/mul", "prep"),
    ("jit(f)/vmap(pcg.vector)/reduce_sum", "pcg.vector"),
    ("jit(_chunk_resume)/while", "unscoped"),
    ("", "unscoped"),
])
def test_layer_is_the_outermost_scope(path, layer):
    assert scopes.layer_of(path) == layer


def test_hlo_paths_of_a_compiled_program():
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("pcg.apply"):
            y = jnp.sin(x) @ x
        with jax.named_scope("pcg.vector"):
            return y * 2.0 + 1.0

    text = jax.jit(f).lower(jnp.ones((8, 8))).compile().as_text()
    layers = {scopes.layer_of(p) for p in scopes.hlo_op_paths(text).values()}
    assert {"pcg.apply", "pcg.vector"} <= layers
    assert scopes.module_name("jit_f(6189173901579575195)") == "jit_f"
    assert scopes.program_id("jit_f(6189173901579575195)") == 6189173901579575195


def nested_trace():
    """One program run of 10 s on one plane: a while loop (0-8 s) whose
    body holds an apply (1-4 s) and a V-cycle (4-7 s), then an init-only
    op before it and an unnamed copy after; a second plane runs half."""
    ops = [("init.1", 0.0, 0.5), ("while.2", 0.5, 8.0),
           ("fusion.3", 1.0, 4.0), ("fusion.4", 4.0, 7.0),
           ("copy.5", 8.0, 9.0)]
    paths = {"jit__chunk_start": {
        "init.1": "jit(_chunk_start)/pcg.init/vcycle.L2.smooth/pa.apply/mul",
        "while.2": "jit(_chunk_start)/while",
        "fusion.3": "jit(_chunk_start)/while/body/pcg.apply/pa.apply/add",
        "fusion.4": "jit(_chunk_start)/while/body/pcg.precond/vcycle.L2.smooth",
    }}
    t = tr.Trace(
        ops={"/device:TPU:0": ops, "/device:TPU:1": ops[:1]},
        modules={"/device:TPU:0": [("jit__chunk_start(42)", 0.0, 10.0)],
                 "/device:TPU:1": [("jit__chunk_start(42)", 0.0, 10.0)]})
    return t, paths


def test_outermost_scope_and_self_time():
    t, paths = nested_trace()
    per_layer, top = scopes.device_seconds(t, 0.0, 20.0, paths)
    # averaged over the two planes; the while keeps only its own 1.5 s
    assert per_layer == pytest.approx({
        "pcg.init": (0.5 + 0.5) / 2, "pcg.apply": 3.0 / 2,
        "pcg.precond": 3.0 / 2, "pcg.vector": 0.0, "pcg.audit": 0.0,
        "prep": 0.0, "unscoped": (1.5 + 1.0) / 2})
    assert top[0][:4] == ["fusion.3", "jit__chunk_start", 1.5, "pcg.apply"]


def test_scope_totals_plus_unscoped_equal_busy():
    t, paths = nested_trace()
    per_layer, _ = scopes.device_seconds(t, 0.0, 20.0, paths)
    assert sum(per_layer.values()) == pytest.approx(t.busy(0.0, 20.0))
    # an op of another program, or one the map lacks, is unscoped
    per_layer, _ = scopes.device_seconds(t, 0.0, 20.0, {})
    assert per_layer["unscoped"] == pytest.approx(t.busy(0.0, 20.0))


def test_idle_is_named_by_the_innermost_service_span():
    t = tr.Trace(
        ops={"/device:TPU:0": [("a", 1.0, 2.0), ("b", 2.5, 3.0),
                               ("c", 6.0, 9.0)]},
        modules={"/device:TPU:0": [("jit_p(1)", 1.0, 3.0),
                                   ("jit_p(1)", 6.0, 9.0)]},
        host=[("bench.window", 0.0, 10.0), ("bench.step", 0.0, 8.5),
              ("service.step", 0.5, 8.0), ("service.retire", 3.2, 4.0),
              ("service.prep", 4.5, 5.0), ("python_thing", 0.0, 10.0)])
    got = scopes.idle_seconds(t, 0.0, 10.0)
    assert got == pytest.approx({
        "bench.step": 0.5,  # 0-0.5, before the service's step opened
        "service.step": 0.5 + 0.2 + 0.5 + 1.0,  # 0.5-1, 3-3.2, 4-4.5, 5-6
        "in_program": 0.5,  # 2-2.5, between two ops of one run
        "service.retire": 0.8,
        "service.prep": 0.5,
        "bench.window": 1.0,  # 9-10
    })
    assert sum(got.values()) + t.busy(0, 10) == pytest.approx(10.0)
    assert scopes.service_idle(got) == pytest.approx(3.5)
    assert scopes.service_idle({}) is None


def sample_run(monkeypatch, paths):
    """A run whose window is the committed v5e sample's, with the
    program's paths taken from ``paths``."""
    t = tr.load(str(SAMPLE))
    run = harness.Run(config={}, mix={}, device={"platform": "tpu"})
    run.window_trace, run.window_span = t, t.span("bench.window")
    lo, hi = run.window_span
    run.window_s, run.credits = hi - lo, 1.0
    monkeypatch.setattr(scopes, "program_paths", lambda run: paths)
    return run


def read(names, run):
    return {n: harness.metric_module(n).read(run) for n in names}


def test_readers_on_the_tpu_sample(monkeypatch):
    """Every existing metric reads the same value whether the new readers
    ran first or not; the new ones find the sample's one scoped fusion
    and no service span."""
    fresh = sample_run(monkeypatch, {})
    before = read(OLD, fresh)
    run = sample_run(monkeypatch, {"jit_bench_sample": {
        "fusion": "jit(bench_sample)/while/body/pcg.apply/dot_general"}})
    new = read(NEW, run)
    assert read(OLD, run) == before and before["device_idle"] > 0
    lo, hi = run.window_span
    fusion = run.window_trace.op_seconds(lo, hi)["fusion"]
    assert new == {"pcg_apply_s": pytest.approx(fusion), "vcycle_s": 0.0,
                   "pcg_vector_s": 0.0, "pcg_audit_s": 0.0,
                   "service_idle_s": None}
    split = run.extra["scope_seconds"]
    layers = sum(split[k] for k in scopes.LAYERS + (scopes.UNSCOPED,))
    assert layers == pytest.approx(split["busy"])


def test_readers_are_silent_on_a_program_without_scopes(monkeypatch):
    """On a program that names no scopes (the parent of this change) the
    new readers return nothing, and do not raise."""
    run = sample_run(monkeypatch, {"jit_bench_sample": {
        "fusion": "jit(bench_sample)/dot_general"}})
    assert read(NEW, run) == dict.fromkeys(NEW)
    monkeypatch.setattr(scopes, "_program_has_scopes", lambda: False)
    run = sample_run(monkeypatch, {})
    assert read(NEW, run) == dict.fromkeys(NEW)
