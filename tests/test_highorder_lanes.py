"""High-order (p=4, p=6) lane differentials.

The fused kernel (``paop_pallas``) must compute what the einsum
``paop`` assembly computes.  These tests lock that down on the
interpreter lane at the three levels users touch:

* solver (``BatchedGMGSolver.solve``): identical iteration counts and
  matching solutions against the ``paop`` reference assembly;
* service (``ElasticityService``): the batched/generational path
  reports the same outcome for both assemblies, and
  ``service.pallas_lane`` reports the lane that runs;
* sharded (8 virtual devices): the kernel survives scenario-axis
  sharding.

The compiled lane exists only on a TPU: elsewhere a compiled request
raises (asserted here), ``tests/test_tpu_compile.py`` compiles it for a
described v5e, and ``chip_smoke.py`` checks its numbers on the chip.
Lane *resolution* plumbing is covered by fast tests that steer
``ops.backend_supports_compiled``.
"""

import jax
import numpy as np
import pytest

from repro.distributed.sharding import scenario_mesh
from repro.fem.mesh import beam_hex
from repro.kernels.pa_elasticity import ops
from repro.serve.elasticity_service import ElasticityService, SolveRequest
from repro.solvers.batched import BatchedGMGSolver

MATS = [
    {1: (50.0, 50.0), 2: (1.0, 1.0)},
    {1: (57.0, 51.3), 2: (1.5, 1.5)},
]
TRACTIONS = np.array([[0.0, 0.0, -1e-2], [0.0, 1e-3, -2e-2]])
TOLS = np.array([1e-8, 1e-8])
MAXITER = 400


def _solve(p, assembly, lane=None, mesh=None, mats=MATS, tr=TRACTIONS,
           tol=TOLS):
    solver = BatchedGMGSolver(
        beam_hex(), 0, p, assembly=assembly, pallas_lane=lane,
        maxiter=MAXITER, mesh=mesh,
    )
    return solver, solver.solve(mats, tr, tol)


def _assert_same_solve(res, ref, context, *, exact=False):
    np.testing.assert_array_equal(
        np.asarray(res.iterations), np.asarray(ref.iterations),
        err_msg=f"{context}: iteration counts diverged",
    )
    np.testing.assert_array_equal(
        np.asarray(res.converged), np.asarray(ref.converged),
        err_msg=f"{context}: convergence flags diverged",
    )
    if exact:
        np.testing.assert_array_equal(
            np.asarray(res.x), np.asarray(ref.x),
            err_msg=f"{context}: solutions diverged",
        )
    else:
        scale = float(np.abs(np.asarray(ref.x)).max()) or 1.0
        np.testing.assert_allclose(
            np.asarray(res.x), np.asarray(ref.x),
            atol=1e-10 * scale, rtol=0,
            err_msg=f"{context}: solutions diverged",
        )


# -- fast: lane resolution plumbing ------------------------------------------


def test_lane_plumbing_solver_and_service(monkeypatch):
    """The lane resolves ONCE at construction in every layer, and the
    stored value is the lane that runs.  A compiled request on a backend
    that cannot lower Pallas raises; on a TPU the compiled lane takes
    f32 policies only."""
    monkeypatch.setattr(ops, "backend_supports_compiled", lambda b=None: False)
    solver = BatchedGMGSolver(beam_hex(), 0, 1, assembly="paop_pallas")
    assert solver.pallas_lane == "interpret"  # auto follows the backend
    with pytest.raises(ValueError, match="needs a TPU backend"):
        ElasticityService(assembly="paop_pallas", pallas_lane="compiled")

    monkeypatch.setattr(ops, "backend_supports_compiled", lambda b=None: True)
    solver = BatchedGMGSolver(
        beam_hex(), 0, 1, assembly="paop_pallas", precision="f32"
    )
    assert solver.pallas_lane == "compiled"
    assert solver._base_ops[-1].pallas_lane == "compiled"
    svc = ElasticityService(assembly="paop_pallas", precision="f32")
    assert svc.pallas_lane == "compiled"
    assert svc.pallas_interpret is False
    with pytest.raises(ValueError, match="precision='f32'"):
        ElasticityService(assembly="paop_pallas")  # f64 default policy
    with pytest.raises(ValueError, match="precision='f32'"):
        svc.submit(SolveRequest(p=2, refine=0, precision="mixed"))
    # the legacy bool still pins the interpreter even when capable
    svc = ElasticityService(assembly="paop_pallas", pallas_interpret=True)
    assert svc.pallas_lane == "interpret"


def test_build_hierarchy_threads_lane(monkeypatch):
    """Unlike the deferred-materials batched solver, build_hierarchy
    APPLIES the operator at construction (smoother power iterations),
    so it must already run the resolved lane — a compiled request on an
    incapable backend raises before any level is applied, and the
    interpreter pin reaches every pallas level."""
    from repro.solvers.gmg import build_hierarchy

    monkeypatch.setattr(ops, "backend_supports_compiled", lambda b=None: False)
    with pytest.raises(ValueError, match="needs a TPU backend"):
        build_hierarchy(
            beam_hex(), 0, 2, assembly="paop_pallas", pallas_lane="compiled"
        )
    gmg = build_hierarchy(
        beam_hex(), 0, 2, assembly="paop_pallas", pallas_interpret=True
    )
    assert gmg.fine.operator.pallas_lane == "interpret"


# -- slow: solver differentials at p = 4 and p = 6 ---------------------------


@pytest.mark.slow
@pytest.mark.parametrize("p", [4, 6])
def test_solver_lane_differential(p):
    """The kernel vs the einsum paop reference at high order: identical
    iteration counts, matching solutions.  A compiled request raises off
    a TPU instead of interpreting."""
    si, ri = _solve(p, "paop_pallas", "interpret")
    _, ref = _solve(p, "paop")
    assert si.pallas_lane == "interpret"
    _assert_same_solve(ri, ref, f"p={p} paop_pallas vs paop")
    assert bool(np.all(np.asarray(ref.converged)))
    if not ops.backend_supports_compiled():
        with pytest.raises(ValueError, match="needs a TPU backend"):
            _solve(p, "paop_pallas", "compiled")


# -- slow: service differential ----------------------------------------------


@pytest.mark.slow
def test_service_lane_differential():
    """The generational service path reports the same outcomes for the
    kernel (interpreter lane) and the einsum assembly at p=4, and the
    service reports the lane its solvers ran."""
    reports = {}
    for assembly in ("paop_pallas", "paop"):
        svc = ElasticityService(
            assembly=assembly, pallas_lane="interpret", maxiter=MAXITER
        )
        reqs = [
            SolveRequest(p=4, refine=0, materials=m, traction=tuple(t),
                         rel_tol=1e-8, keep_solution=True)
            for m, t in zip(MATS, TRACTIONS)
        ]
        reports[assembly] = svc.solve(reqs)
        assert svc.pallas_lane == "interpret"
    for a, b in zip(reports["paop_pallas"], reports["paop"]):
        assert a.iterations == b.iterations
        assert a.converged and b.converged
        np.testing.assert_allclose(
            np.asarray(a.x), np.asarray(b.x),
            atol=1e-10 * (float(np.abs(np.asarray(a.x)).max()) or 1.0),
            rtol=0,
        )


# -- slow + multidevice: sharded lane differential ---------------------------


@pytest.mark.slow
@pytest.mark.multidevice
def test_sharded_lane_differential():
    """Scenario-sharding over 8 virtual devices composes with the fused
    kernel: the sharded solve reproduces the unsharded one at p=4."""
    if jax.device_count() < 8:
        pytest.skip(f"needs 8 devices, have {jax.device_count()}")
    mats, tr, tol = [], [], []
    for i in range(8):
        mats.append({1: (50.0 + 3.0 * (i % 3), 50.0), 2: (1.0 + 0.25 * (i % 2), 1.0)})
        tr.append((0.0, 1e-3 * (i % 2), -1e-2))
        tol.append(1e-8)
    tr, tol = np.asarray(tr), np.asarray(tol)
    _, ref = _solve(4, "paop_pallas", "interpret", mats=mats, tr=tr, tol=tol)
    ss, rs = _solve(4, "paop_pallas", "auto", mesh=scenario_mesh(8),
                    mats=mats, tr=tr, tol=tol)
    assert ss.n_shards == 8
    # sharded partitioning fuses differently: ~ulp, not bitwise
    _assert_same_solve(rs, ref, "sharded vs unsharded paop_pallas")
