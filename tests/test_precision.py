"""Precision-policy suite: dtype invariance of the prep/state pytrees,
f32/mixed-vs-f64 differentials, the honest mixed-tolerance acceptance
run, engineered stagnation -> automatic f64 fallback (solver and
service level) or, where f64 cannot run (a TPU, steered here), a
stalled unconverged report, and the policy axis of the compile cache.

Run alone by the ``precision`` CI lane
(``pytest -q tests/test_precision.py -m "not slow"``); the slow-marked
acceptance test rides in the full lane.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import precision as precision_mod
from repro.core.precision import (
    PRECISION_POLICIES,
    PrecisionPolicy,
    resolve_precision,
)
from repro.kernels.pa_elasticity import ops
from repro.fem.mesh import beam_hex
from repro.launch.solve import solve_beam
from repro.serve.elasticity_service import ElasticityService, SolveRequest
from repro.solvers.batched import BatchedGMGSolver
from repro.solvers.chebyshev import ChebyshevSmoother

MATS = {1: (50.0, 50.0), 2: (1.0, 1.0)}
TR = (0.0, 0.0, -1e-2)


def _true_rel_mnorm(f64_solver, mats, tractions, x):
    """Per-row honest convergence measure: sqrt((M r, r) / (M b, b))
    with r = b - A x, everything (operator, preconditioner, arithmetic)
    at f64 — the same B-norm the solver's rel_tol thresholds live in,
    recomputed from scratch so recurrence drift cannot hide."""
    assert f64_solver.precision.name == "f64"
    s = len(mats)
    lam, mu = f64_solver.pack_materials(mats)
    prep = f64_solver.prepare(
        lam, mu, np.ones(s, bool), f64_solver.empty_prep(s)
    )
    _, _, A, M = f64_solver._build_from_prep(prep)
    b = f64_solver._rhs(jnp.asarray(np.asarray(tractions), jnp.float64))
    r = b - A(jnp.asarray(np.asarray(x), jnp.float64))

    def mnorm(v):
        return np.sqrt(
            np.asarray(jnp.sum((M(v) * v).reshape(s, -1), axis=1))
        )

    return mnorm(r) / mnorm(b)


# -- policy resolution -------------------------------------------------------


def test_policy_registry_dtypes():
    f64 = PRECISION_POLICIES["f64"]
    f32 = PRECISION_POLICIES["f32"]
    mixed = PRECISION_POLICIES["mixed"]
    bf16 = PRECISION_POLICIES["mixed-bf16"]
    assert f64.uniform and not f64.reduced
    assert f32.uniform and f32.reduced
    assert not mixed.uniform and mixed.reduced
    assert (mixed.solve_dtype, mixed.precond_dtype, mixed.coarse_dtype) == (
        jnp.float64, jnp.float32, jnp.float32,
    )
    # bf16 smooths in bf16 but NEVER factors in it (too few mantissa
    # bits for a Cholesky): the coarse tier stays f32.
    assert bf16.precond_dtype == jnp.bfloat16
    assert bf16.coarse_dtype == jnp.float32


def test_resolve_precision_names_dtypes_and_conflicts():
    assert resolve_precision("mixed") is PRECISION_POLICIES["mixed"]
    # legacy dtype spelling -> the matching uniform policy
    assert resolve_precision(None, jnp.float32) is PRECISION_POLICIES["f32"]
    assert resolve_precision(None, np.float64) is PRECISION_POLICIES["f64"]
    assert resolve_precision(None) is PRECISION_POLICIES["f64"]
    # a policy object passes through untouched
    pol = PRECISION_POLICIES["mixed"]
    assert resolve_precision(pol) is pol
    with pytest.raises(ValueError):
        resolve_precision("float16")  # unknown name
    with pytest.raises(ValueError):
        resolve_precision("mixed", jnp.float32)  # conflicting dtype


# -- pytree dtype invariance (the bugfix-sweep regressions) ------------------


def test_pad_scenarios_respects_solver_dtype():
    """Regression: pad_scenarios used to cast tractions/tolerances to a
    hard-coded np.float64, silently promoting (and re-tracing) every
    non-f64 solve."""
    s32 = BatchedGMGSolver(beam_hex(), 0, 1, precision="f32")
    mats, tr, rel, n = s32.pad_scenarios(
        [MATS], [TR], 1e-6, n=4
    )
    assert n == 1 and len(mats) == 4
    assert tr.dtype == np.float32 and tr.shape == (4, 3)
    assert rel.dtype == np.float32 and rel.shape == (4,)
    # padding rows are born converged: zero traction, reused materials
    np.testing.assert_array_equal(tr[1:], 0.0)

    s64 = BatchedGMGSolver(beam_hex(), 0, 1)
    _, tr64, rel64, _ = s64.pad_scenarios([MATS], [TR], 1e-6, n=2)
    assert tr64.dtype == np.float64 and rel64.dtype == np.float64


@pytest.mark.parametrize("policy", ["f32", "mixed"])
def test_prep_leaves_carry_policy_dtypes(policy):
    s = BatchedGMGSolver(beam_hex(), 0, 2, precision=policy)
    pol = s.precision
    prep = s.empty_prep(2)
    for name in ("lam_w", "mu_w", "dinv", "lmax"):
        for leaf in prep[name]:
            assert leaf.dtype == pol.precond_dtype, (policy, name)
    assert prep["chol"].dtype == pol.coarse_dtype
    if pol.solve_dtype != pol.precond_dtype:  # split fine level
        assert prep["lam_w_solve"].dtype == pol.solve_dtype
        assert prep["mu_w_solve"].dtype == pol.solve_dtype
    else:
        assert "lam_w_solve" not in prep
    # prepare() must preserve every dtype (a promotion here would
    # re-trace run_chunk against a different pytree signature)
    lam, mu = s.pack_materials([MATS, MATS])
    out = s.prepare(lam, mu, np.ones(2, bool), prep)
    for k, v in prep.items():
        got = out[k] if not isinstance(v, tuple) else out[k][0]
        want = v if not isinstance(v, tuple) else v[0]
        assert jnp.asarray(got).dtype == jnp.asarray(want).dtype, (policy, k)


@pytest.mark.parametrize("policy", ["f64", "f32", "mixed"])
def test_state_leaves_carry_solve_dtype(policy):
    """Every float leaf of the resumable Krylov state lives at the
    policy's SOLVE dtype (the honest-accounting tier); the masks and
    counters stay int32/bool."""
    s = BatchedGMGSolver(beam_hex(), 0, 1, precision=policy)
    st = s.empty_state(2)
    sdt = np.dtype(s.precision.solve_dtype)
    for fld in dataclasses.fields(st):
        leaf = np.asarray(getattr(st, fld.name))
        if fld.name in ("iters", "stall"):
            assert leaf.dtype == np.int32, fld.name
        elif fld.name in ("active", "stalled"):
            assert leaf.dtype == np.bool_, fld.name
        else:
            assert leaf.dtype == sdt, (policy, fld.name)


def test_chebyshev_coefficients_follow_block_dtype():
    """Regression: the Chebyshev recurrence coefficients must live in
    the vector-block dtype, not lmax's — an f64 lmax against f32 blocks
    silently promoted every d/z update.  Also: a zero slipping into the
    diagonal must not poison dinv with inf."""
    n = 8
    A = lambda x: 2.0 * x
    # f64 lmax over an f32 block (the mixed hierarchy's shape): the
    # recurrence must stay f32 end to end
    sm = ChebyshevSmoother(
        A=A,
        dinv=0.5 * jnp.ones((n, 3), jnp.float32),
        lmax=jnp.asarray(1.0, jnp.float64),
    )
    out32 = sm(jnp.ones((n, 3), jnp.float32))
    assert out32.dtype == jnp.float32
    assert bool(jnp.isfinite(out32).all())
    # zero-diagonal guard: setup() must not produce inf in dinv
    diag = jnp.ones((n, 3), jnp.float64).at[0, 0].set(0.0)
    sm2 = ChebyshevSmoother.setup(A, diag, (n, 3), jnp.float64)
    assert bool(jnp.isfinite(sm2.dinv).all())
    out64 = sm2(jnp.ones((n, 3), jnp.float64))
    assert out64.dtype == jnp.float64 and bool(jnp.isfinite(out64).all())


def test_stall_detector_armed_only_for_reduced_policies():
    """The f64 program must stay bit-identical to the pre-stagnation
    build: stall_iters=0 compiles the detector out entirely."""
    assert BatchedGMGSolver(beam_hex(), 0, 1).stall_iters == 0
    assert BatchedGMGSolver(beam_hex(), 0, 1, precision="f32").stall_iters > 0
    assert (
        BatchedGMGSolver(beam_hex(), 0, 1, precision="mixed").stall_iters > 0
    )


# -- differentials against the f64 oracle ------------------------------------


def test_f32_matches_f64_at_loose_tolerance():
    mats = [MATS, {1: (10.0, 8.0), 2: (2.0, 1.5)}]
    trs = [TR, (0.0, 5e-3, -5e-3)]
    s64 = BatchedGMGSolver(beam_hex(), 0, 1)
    s32 = BatchedGMGSolver(beam_hex(), 0, 1, precision="f32")
    r64 = s64.solve(mats, trs, 1e-5)
    r32 = s32.solve(mats, trs, 1e-5)
    assert bool(r64.converged.all()) and bool(r32.converged.all())
    assert not bool(r32.fallback.any())  # 1e-5 is above the f32 floor
    assert r32.x.dtype == jnp.float32
    # honest check at f64: the f32 answer really sits at <= 1e-5
    rel = _true_rel_mnorm(s64, mats, trs, r32.x)
    assert (rel <= 1e-5).all(), rel


def test_mixed_matches_f64_iterations_and_tolerance():
    mats = [MATS, {1: (10.0, 8.0), 2: (2.0, 1.5)}]
    trs = [TR, (0.0, 5e-3, -5e-3)]
    s64 = BatchedGMGSolver(beam_hex(), 0, 1)
    smx = BatchedGMGSolver(beam_hex(), 0, 1, precision="mixed")
    r64 = s64.solve(mats, trs, 1e-8)
    rmx = smx.solve(mats, trs, 1e-8)
    assert bool(rmx.converged.all()) and not bool(rmx.fallback.any())
    assert rmx.x.dtype == jnp.float64  # outer Krylov at solve dtype
    rel = _true_rel_mnorm(s64, mats, trs, rmx.x)
    assert (rel <= 1e-8).all(), rel
    it64, itmx = np.asarray(r64.iterations), np.asarray(rmx.iterations)
    assert (itmx <= (1.3 * it64).astype(int) + 1).all(), (it64, itmx)


def test_scalar_solve_beam_precision_axis():
    f64 = solve_beam(1, 0, rel_tol=1e-6)
    mix = solve_beam(1, 0, rel_tol=1e-6, precision="mixed")
    assert f64.precision == "f64" and mix.precision == "mixed"
    assert mix.final_rel_norm <= 1e-6  # f64 residual accounting
    assert mix.iterations <= int(1.3 * f64.iterations) + 1


@pytest.mark.slow
def test_mixed_tolerance_batch16_acceptance():
    """The PR's acceptance run: a 16-row mixed-tolerance, mixed-material
    corpus under the ``mixed`` policy converges EVERY row to its
    requested tolerance — verified against a from-scratch f64 residual,
    not the solver's own recurrence — within 1.3x the f64 iteration
    count, with no fallback engaged."""
    rng = np.random.default_rng(7)
    ne = beam_hex().nelem * 8  # refine=1
    mats, trs, tols = [], [], []
    for i in range(16):
        if i % 3 == 0:
            ramp = np.linspace(50.0, 1.0, ne) * (1.0 + 0.1 * i)
            mats.append((ramp, 0.8 * ramp))
        else:
            mats.append({1: (50.0 / (i + 1), 50.0), 2: (1.0, 1.0 + 0.2 * i)})
        trs.append((0.0, float(rng.uniform(-5e-3, 5e-3)), -1e-2))
        tols.append(float(10.0 ** rng.uniform(-10, -4)))
    s64 = BatchedGMGSolver(beam_hex(), 1, 1)
    smx = BatchedGMGSolver(beam_hex(), 1, 1, precision="mixed")
    r64 = s64.solve(mats, trs, tols)
    rmx = smx.solve(mats, trs, tols)
    assert bool(r64.converged.all())
    assert bool(rmx.converged.all())
    assert not bool(rmx.fallback.any())
    rel = _true_rel_mnorm(s64, mats, trs, rmx.x)
    assert (rel <= np.asarray(tols)).all(), (rel, tols)
    it64 = np.asarray(r64.iterations)
    itmx = np.asarray(rmx.iterations)
    assert (itmx <= (1.3 * it64).astype(int) + 1).all(), (it64, itmx)


# -- engineered stagnation -> f64 fallback -----------------------------------


def test_solver_level_stagnation_falls_back_to_f64():
    """A tolerance below the f32 residual floor stalls (or audits as
    dishonest); solve() re-solves exactly that row on the f64 twin and
    merges it back with honest accounting."""
    s32 = BatchedGMGSolver(beam_hex(), 0, 1, precision="f32")
    res = s32.solve([MATS, MATS], [TR, TR], [1e-4, 1e-13])
    fb = np.asarray(res.fallback)
    assert not fb[0] and fb[1]  # only the impossible row fell back
    assert bool(res.converged.all())
    assert res.x.dtype == jnp.float64  # merged result promoted
    # honest cost accounting: the fallback row paid both passes
    assert int(res.iterations[1]) > int(res.iterations[0])
    # 1e-13 sits below even f64's recurrence-drift floor for this
    # system, so the interesting honest claim is that the f64 re-solve
    # pushed the TRUE residual orders of magnitude past the f32 floor
    # (~1e-4 in this norm), not that it literally reached 1e-13
    s64 = BatchedGMGSolver(beam_hex(), 0, 1)
    rel = _true_rel_mnorm(s64, [MATS, MATS], [TR, TR], res.x)
    assert rel[1] <= 1e-6


def test_service_level_stagnation_requeues_onto_f64():
    svc = ElasticityService(max_batch=2)
    reports = svc.solve_continuous([
        SolveRequest(p=1, refine=0, rel_tol=1e-4, precision="f32"),
        SolveRequest(p=1, refine=0, rel_tol=1e-13, precision="f32"),
    ])
    ok, hard = reports
    assert ok.precision == "f32" and not ok.fallback
    assert hard.precision == "f64" and hard.fallback
    assert all(r.converged for r in reports)
    assert all(r.final_rel_norm <= r.request.rel_tol for r in reports)
    assert svc.stats["precision_fallbacks"] >= 1


def test_generational_path_reports_fallback():
    svc = ElasticityService(max_batch=2, precision="f32")
    reports = svc.solve([
        SolveRequest(p=1, refine=0, rel_tol=1e-4),
        SolveRequest(p=1, refine=0, rel_tol=1e-13),
    ])
    assert [r.fallback for r in reports] == [False, True]
    assert all(r.converged for r in reports)
    assert all(r.precision == "f32" for r in reports)  # solver-level merge


# -- no f64 path (a TPU): refusal and stalled reports ------------------------


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer the backend checks to a TPU's answers: f64 emulated (so
    the ``f64`` policy is refused and ``mixed`` is the default)."""
    monkeypatch.setattr(precision_mod, "emulates_f64", lambda b=None: True)


@pytest.mark.parametrize(
    "build",
    [
        lambda: ElasticityService(precision="f64"),
        lambda: ElasticityService(max_batch=1).submit(
            SolveRequest(p=1, refine=0, precision="f64")
        ),
        lambda: BatchedGMGSolver(beam_hex(), 0, 1, precision="f64"),
        lambda: solve_beam(1, 0, dtype=jnp.float64),
    ],
    ids=["service", "submit", "solver", "solve_beam"],
)
def test_tpu_refuses_f64_naming_mixed(on_tpu, build):
    """The f64 V-cycle does not compile in a usable time for the p=8
    beam on a TPU: every entry point refuses it up front, naming the
    policy to use, and never lowers precision on its own."""
    with pytest.raises(ValueError, match="precision='mixed'"):
        build()


def test_tpu_default_policy_is_mixed(on_tpu):
    assert resolve_precision(None) is PRECISION_POLICIES["mixed"]
    svc = ElasticityService(max_batch=1)
    assert svc.precision.name == "mixed"
    assert svc.fallback_precision is None
    # an explicit dtype is a request, not a default: still refused
    with pytest.raises(ValueError, match="precision='mixed'"):
        ElasticityService(dtype=jnp.float64)


def test_compiled_lane_service_has_no_f64_fallback(on_tpu, monkeypatch):
    """A paop_pallas f32 service on the compiled lane is built (no
    kernel runs here) with no fallback policy, so a stalled row can only
    retire as a stalled report — never by building an f64 solver."""
    monkeypatch.setattr(ops, "backend_supports_compiled", lambda b=None: True)
    svc = ElasticityService(assembly="paop_pallas", precision="f32")
    assert svc.pallas_lane == "compiled"
    assert svc.fallback_precision is None
    with pytest.raises(ValueError, match="Mosaic has no float64"):
        svc.submit(SolveRequest(p=1, refine=0, precision="mixed"))


@pytest.mark.parametrize("path", ["continuous", "generational"])
def test_stall_without_f64_path_is_reported_not_raised(on_tpu, path):
    """A planted stall (1e-13 is below f32's floor) with no f64 path to
    fall back on: the service neither raises nor hides it — the row
    retires unconverged with ``stalled=True`` and its neighbour is
    untouched."""
    svc = ElasticityService(max_batch=2, precision="f32")
    reqs = [
        SolveRequest(p=1, refine=0, rel_tol=1e-4),
        SolveRequest(p=1, refine=0, rel_tol=1e-13),
    ]
    run = svc.solve_continuous if path == "continuous" else svc.solve
    ok, hard = run(reqs)
    assert ok.converged and not ok.stalled and not ok.fallback
    assert not hard.converged and hard.stalled and not hard.fallback
    assert hard.precision == "f32"
    assert hard.final_rel_norm > hard.request.rel_tol
    assert svc.stats["precision_fallbacks"] == 0
    assert svc.idle()


# -- the policy axis of the compile cache ------------------------------------


def test_policies_get_distinct_cache_entries_and_no_retrace():
    """Two policies never share a compiled program (their group_keys
    differ in the policy slot), while repeat requests of one policy hit
    the cache with zero re-traces."""
    svc = ElasticityService(max_batch=2)
    k64 = svc.group_key(SolveRequest(p=1, refine=0))
    k32 = svc.group_key(SolveRequest(p=1, refine=0, precision="f32"))
    kmx = svc.group_key(SolveRequest(p=1, refine=0, precision="mixed"))
    assert k64[:-1] == k32[:-1] == kmx[:-1]  # same discretization...
    assert len({k64, k32, kmx}) == 3  # ...distinct policy slot
    svc.solve([SolveRequest(p=1, refine=0)])
    svc.solve([SolveRequest(p=1, refine=0, precision="mixed")])
    assert len(svc._solvers) == 2
    assert {s.precision.name for s in svc._solvers.values()} == {
        "f64", "mixed",
    }
    misses = svc.stats["cache_misses"]
    solver = svc._solvers[kmx]
    traces0 = solver._jit_solve._cache_size()
    svc.solve([SolveRequest(p=1, refine=0, precision="mixed")])
    assert svc.stats["cache_misses"] == misses  # cache hit
    assert solver._jit_solve._cache_size() == traces0  # zero re-trace
    # the digest axis: identical materials under different policies must
    # not alias each other's prepared state
    from repro.serve.elasticity_service import _material_digest

    lam, mu = np.ones(3), np.ones(3)
    assert _material_digest(lam, mu, precision="f32") != _material_digest(
        lam, mu, precision="f64"
    )


def test_metrics_labels_carry_precision():
    svc = ElasticityService(max_batch=2)
    svc.solve([SolveRequest(p=1, refine=0, precision="f32")])
    snap = svc.registry.snapshot()
    cells = snap["families"]["service_cache_misses_total"]["cells"]
    assert any(c["labels"].get("precision") == "f32" for c in cells)
