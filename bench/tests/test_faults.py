"""A whole run on the CPU at a small size, with the look for a chip
skipped, where the timed path is broken underneath: ``correct`` must
come out false.  Also the control: the program's float32 policy in the
place of the configuration's float64 Krylov must fail the cell's limit
where float32's floor binds.

The single-row, single-chip cells cannot leave half of a batch out or
skip an exchange between chips, so those faults have no case here.
"""

import dataclasses
import json
import time

from bench.lib import harness, traffic
from bench.run import ROOT, cell_files

CELL = "beam_p8_6m.single"


def tiny(precision=None):
    cell, config, limits, e2e, layers = cell_files(CELL)
    config = dict(config, p=2, refine=1)
    if precision:
        config["service"] = dict(config["service"], precision=precision)
    return cell, config, limits, e2e, layers


def run(cell, config, limits, e2e, layers, seconds=2.0, seed=2**31 + 5):
    return harness.run_cell(cell, config, limits, e2e, layers, seed=seed,
                            seconds=seconds, trace=False,
                            t_start=time.perf_counter(), platforms=("cpu",))


def plant(monkeypatch, fault):
    """Break BatchedGMGSolver.run_chunk once the warm-up has run."""
    from repro.solvers.batched import BatchedGMGSolver

    real_chunk = BatchedGMGSolver.run_chunk
    real_warm = harness.Session.warm_up

    def warm_up(self, tr):
        real_warm(self, tr)

        def broken(solver, *a, **k):
            return fault(real_chunk, solver, *a, **k)

        monkeypatch.setattr(BatchedGMGSolver, "run_chunk", broken)

    monkeypatch.setattr(harness.Session, "warm_up", warm_up)


def test_sound_run_is_correct(no_cache, tpu_policies):
    out = run(*tiny())
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] >= 2
    assert out["limits"]["residual_x_tol"]["value"] < 100
    assert list(out["metrics"]) == ["solve_s", "setup_s"]
    assert list(out)[-1] == "limits"


def test_answer_altered_where_produced(no_cache, tpu_policies, monkeypatch):
    import jax
    import jax.numpy as jnp

    scale = jax.jit(lambda x: x * 1.1)
    # Built now, so that the planted fault compiles nothing in the window.
    scale(jnp.zeros((1, 33 * 5 * 5, 3), jnp.float64))

    def scaled(real, solver, *a, **k):
        state, consumed = real(solver, *a, **k)
        return dataclasses.replace(state, x=scale(state.x)), consumed

    plant(monkeypatch, scaled)
    out = run(*tiny())
    assert out["correct"] is False
    assert out["limits"]["residual_x_tol"]["value"] > 5e4


def test_step_returns_its_state_unchanged(no_cache, tpu_policies, monkeypatch):
    import numpy as np

    def frozen(real, solver, tractions, rel_tol, mask, state, prep, k,
               **kw):
        return state, np.zeros(np.shape(state.iters), np.int32)

    plant(monkeypatch, frozen)
    monkeypatch.setattr(harness, "FINISH_S", 2.0)
    out = run(*tiny())
    assert out["correct"] is False
    # Rows come back with the state they went in with: no answer.
    numbers = out["limits"]
    assert (numbers["residual_x_tol"]["value"] > 1e5
            or numbers["unanswered"]["value"] >= 1)


def test_float32_control_fails_where_its_floor_binds(no_cache, tpu_policies, monkeypatch):
    """At this size float32's floor lies below 1e-6, where the cells ask
    (at the cells' size it lies near 1e-3 on the chip), so both sides
    solve the small beam to 1e-10 here."""
    mix = dict(traffic.load_mix("single"), rel_tol=1e-10)
    monkeypatch.setattr(traffic, "load_mix", lambda name: mix)
    program = run(*tiny())
    control = run(*tiny("f32"))
    lim = program["limits"]["residual_x_tol"]["limit"]
    assert program["correct"] is True
    assert control["correct"] is False
    assert control["limits"]["residual_x_tol"]["value"] > 3 * lim


def test_no_chip_no_result():
    """Off a TPU the command exits non-zero and prints no result line."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "no accelerator" in p.stderr


def test_every_name_resolves():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell_files(w["name"])
        traffic.load_mix(w["traffic"])
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert hasattr(harness.metric_module(m["name"]), "read")
