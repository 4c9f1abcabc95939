"""End-to-end driver for the paper's benchmark: the two-material
cantilever beam under a constant downward traction, solved by
GMG-preconditioned PCG (paper Sec. 5.1.4).

Usage:
    PYTHONPATH=src python -m repro.launch.solve --p 2 --refine 2 \
        --assembly paop --coarse cholesky

Reports the paper's phase breakdown: Prec. (preconditioner setup),
Form-LS (RHS + constraint elimination), Solve (outer PCG), Total,
iteration count, and operator kernel time accumulated inside AddMult.
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.geometry import MATERIALS_BEAM
from repro.core.operators import ElasticityOperator
from repro.core.precision import check_policy, resolve_precision
from repro.fem.bc import eliminate_rhs
from repro.fem.mesh import beam_hex
from repro.kernels.pa_elasticity.ops import resolve_lane
from repro.launch.compile_cache import use_compile_cache
from repro.solvers.cg import pcg
from repro.solvers.gmg import build_hierarchy

TRACTION = (0.0, 0.0, -1e-2)


@dataclasses.dataclass
class SolveReport:
    p: int
    assembly: str
    ndof: int
    nelem: int
    iterations: int
    t_precond: float
    t_form_ls: float
    t_solve: float
    t_total: float
    final_rel_norm: float
    precision: str = "f64"
    x: Any = None


def solve_beam(
    p: int,
    n_h_refine: int = 1,
    assembly: str = "paop",
    coarse_mesh=None,
    rel_tol: float = 1e-6,
    maxiter: int = 5000,
    coarse_method: str = "cholesky",
    dtype=None,
    precision: str | None = None,
    keep_solution: bool = False,
    pallas_interpret: bool | None = None,
    pallas_lane: str | None = None,
    materials: dict | None = None,
    traction=TRACTION,
) -> SolveReport:
    """Solve the beam benchmark once.  ``precision`` names a
    :class:`~repro.core.precision.PrecisionPolicy`: the GMG hierarchy
    (smoothers, transfers, element kernels) is built at the policy's
    ``precond_dtype`` while the outer PCG — operator apply, residual
    norms, tolerance test — runs at ``solve_dtype``, with casts only at
    the preconditioner boundary.  The legacy uniform ``dtype`` argument
    still works.  With neither, the backend's default policy runs (f64;
    mixed on a TPU)."""
    policy = resolve_precision(precision, dtype)
    check_policy(
        policy, assembly, resolve_lane(pallas_lane, interpret=pallas_interpret)
    )
    coarse_mesh = coarse_mesh if coarse_mesh is not None else beam_hex()
    materials = materials if materials is not None else MATERIALS_BEAM
    t0 = time.perf_counter()

    # --- preconditioner setup (GMG hierarchy, smoothers, coarse factor)
    gmg = build_hierarchy(
        coarse_mesh,
        n_h_refine,
        p,
        assembly=assembly,
        materials=materials,
        dtype=policy.precond_dtype,
        coarse_method=coarse_method,
        pallas_interpret=pallas_interpret,
        pallas_lane=pallas_lane,
    )
    fine = gmg.fine
    sdt = policy.solve_dtype
    if jnp.dtype(sdt) != jnp.dtype(policy.precond_dtype):
        # Split-precision fine level: the outer Krylov streams its own
        # solve-dtype operator; the V-cycle is entered/left via casts.
        solve_op = ElasticityOperator(
            fine.space,
            assembly=assembly,
            materials=materials,
            dtype=sdt,
            ess_faces=("x0",),
            pallas_interpret=pallas_interpret,
            pallas_lane=pallas_lane,
        )
        A = solve_op.constrained()
        pdt = policy.precond_dtype
        M = lambda r: gmg(r.astype(pdt)).astype(sdt)  # noqa: E731
        rhs_op = solve_op.apply
        ess_mask = solve_op.ess_mask
    else:
        A = fine.constrained
        M = gmg
        rhs_op = fine.operator.apply
        ess_mask = fine.ess_mask
    t1 = time.perf_counter()

    # --- form linear system: traction RHS + essential elimination
    b = jnp.asarray(fine.space.traction_rhs("x1", traction), dtype=sdt)
    b = eliminate_rhs(rhs_op, ess_mask, b)
    t2 = time.perf_counter()

    # --- outer PCG with the GMG preconditioner
    @jax.jit
    def run(bv):
        return pcg(A, bv, M=M, rel_tol=rel_tol, maxiter=maxiter)

    res = run(b)
    x = res.x.block_until_ready()
    t3 = time.perf_counter()

    return SolveReport(
        p=p,
        assembly=assembly,
        ndof=fine.space.ndof,
        nelem=fine.space.nelem,
        iterations=int(res.iterations),
        t_precond=t1 - t0,
        t_form_ls=t2 - t1,
        t_solve=t3 - t2,
        t_total=t3 - t0,
        final_rel_norm=float(res.final_norm / res.initial_norm),
        precision=policy.name,
        x=x if keep_solution else None,
    )


def main() -> None:
    # The f64 tiers of every policy need x64 enabled; without it jax
    # silently truncates to f32 and the residual accounting lies.
    jax.config.update("jax_enable_x64", True)
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--p", type=int, default=2)
    ap.add_argument("--refine", type=int, default=1)
    ap.add_argument("--assembly", default="paop")
    ap.add_argument("--coarse", default="cholesky")
    ap.add_argument("--rel-tol", type=float, default=1e-6)
    ap.add_argument("--precision", default=None,
                    choices=["f64", "f32", "mixed", "mixed-bf16"],
                    help="precision policy: uniform f64/f32, or mixed / "
                         "mixed-bf16 (f64 outer PCG + residual test over "
                         "a reduced-precision V-cycle).  Default f64, or "
                         "mixed on a TPU, which refuses f64")
    args = ap.parse_args()

    rep = solve_beam(
        args.p,
        args.refine,
        assembly=args.assembly,
        rel_tol=args.rel_tol,
        coarse_method=args.coarse,
        precision=args.precision,
    )
    print(
        f"p={rep.p} assembly={rep.assembly} precision={rep.precision} "
        f"ndof={rep.ndof} "
        f"iters={rep.iterations} prec={rep.t_precond:.3f}s "
        f"form={rep.t_form_ls:.3f}s solve={rep.t_solve:.3f}s "
        f"total={rep.t_total:.3f}s rel={rep.final_rel_norm:.2e}"
    )


if __name__ == "__main__":
    main()
