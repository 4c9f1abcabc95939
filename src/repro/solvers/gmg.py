"""Geometric multigrid preconditioner (paper Sec. 3).

Hierarchy: starting from the coarse mesh, ``n_h_refine`` uniform
refinements give levels 0..r at degree p_min = 1; p-refinements then
double the degree until the finest level reaches the target p
(appending p_target itself when it is not a power of two, e.g. the
Fig. 5 sweep's p = 6).  Fine and intermediate levels use the selectable
matrix-free operator with Chebyshev(k=2)-Jacobi smoothing; the coarsest
level is assembled and solved per :mod:`repro.solvers.coarse`.

FA+GMG, PA+GMG and PAop+GMG differ only in the operator handle used on
fine/intermediate levels — exactly the paper's experimental contract.

Scenario batching: passing ``materials`` as a *sequence* of
attribute->(lambda, mu) dicts builds one hierarchy whose operators,
smoothers, transfers and coarse solve all carry a leading scenario axis
(S, nscalar, 3); the V-cycle below is shape-agnostic and preconditions
all scenarios in one pass (consumed by repro.solvers.batched.bpcg).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.operators import ElasticityOperator
from repro.fem.mesh import HexMesh
from repro.fem.space import H1Space
from repro.fem.transfer import Transfer, make_transfer
from repro.solvers.chebyshev import ChebyshevSmoother
from repro.solvers.coarse import make_coarse_solver

__all__ = [
    "p_chain",
    "hierarchy_spaces",
    "build_hierarchy",
    "GMGPreconditioner",
    "Level",
    "VCYCLE_COARSE",
    "VCYCLE_PARTS",
    "vcycle_scope",
]

# ``jax.named_scope`` names of the V-cycle's device work: per level l
# (finest = len(levels) - 1, coarsest = 0) its smoothing, residual and
# the transfers between l and l - 1; the coarse solve below level 1.
VCYCLE_COARSE = "vcycle.coarse"
VCYCLE_PARTS = ("smooth", "residual", "restrict", "prolong")


def vcycle_scope(level: int, part: str) -> str:
    """The scope name of one part of the V-cycle on ``level``."""
    return f"vcycle.L{level}.{part}"


def p_chain(p_target: int) -> list[int]:
    """Degree ladder 1 -> 2 -> 4 -> ... (-> p_target)."""
    chain = [1]
    while chain[-1] * 2 <= p_target:
        chain.append(chain[-1] * 2)
    if chain[-1] != p_target:
        chain.append(p_target)
    return chain


def hierarchy_spaces(
    coarse_mesh: HexMesh, n_h_refine: int, p_target: int
) -> list[H1Space]:
    """The GMG level ladder, coarse -> fine: ``n_h_refine`` uniform
    h-refinements at p = 1, then p-doubling on the finest mesh."""
    meshes = [coarse_mesh]
    for _ in range(n_h_refine):
        meshes.append(meshes[-1].refined())
    spaces = [H1Space(m, 1) for m in meshes]
    for p in p_chain(p_target)[1:]:
        spaces.append(H1Space(meshes[-1], p))
    return spaces


@dataclasses.dataclass
class Level:
    space: H1Space
    operator: ElasticityOperator
    constrained: Callable  # ConstrainedOperator
    smoother: ChebyshevSmoother | None
    ess_mask: Any


@dataclasses.dataclass
class GMGPreconditioner:
    levels: list[Level]  # coarse -> fine
    transfers: list[Transfer]  # transfers[i]: level i -> level i+1
    coarse_solve: Callable

    @property
    def fine(self) -> Level:
        return self.levels[-1]

    def __call__(self, r):
        return self._vcycle(len(self.levels) - 1, r)

    def _vcycle(self, l: int, b):
        if l == 0:
            with jax.named_scope(VCYCLE_COARSE):
                return self.coarse_solve(b)
        lev = self.levels[l]
        t = self.transfers[l - 1]
        with jax.named_scope(vcycle_scope(l, "smooth")):
            x = lev.smoother(b)  # pre-smooth from zero initial guess
        with jax.named_scope(vcycle_scope(l, "residual")):
            r = b - lev.constrained(x)
        with jax.named_scope(vcycle_scope(l, "restrict")):
            rc = t.restrict(r)
            rc = jnp.where(jnp.asarray(self.levels[l - 1].ess_mask), 0.0, rc)
        e = self._vcycle(l - 1, rc)
        with jax.named_scope(vcycle_scope(l, "prolong")):
            x = x + t.prolong(e)
        with jax.named_scope(vcycle_scope(l, "smooth")):
            x = lev.smoother(b, x)  # post-smooth
        return x


def build_hierarchy(
    coarse_mesh: HexMesh,
    n_h_refine: int,
    p_target: int,
    assembly: str = "paop",
    materials=None,
    dtype=jnp.float64,
    cheb_degree: int = 2,
    power_iters: int = 10,
    coarse_method: str = "cholesky",
    ess_faces=("x0",),
    pallas_interpret: bool | None = None,
    pallas_lane: str | None = None,
) -> GMGPreconditioner:
    """Build the paper's GMG preconditioner for the beam benchmark.

    ``pallas_lane`` ("auto"/"compiled"/"interpret", default auto: compiled
    on a TPU, interpret elsewhere) selects the Pallas lane for every
    ``paop_pallas`` level; the legacy ``pallas_interpret`` bool is
    honored when no lane is given."""
    spaces = hierarchy_spaces(coarse_mesh, n_h_refine, p_target)

    levels: list[Level] = []
    for i, sp in enumerate(spaces):
        is_coarsest = i == 0
        # Coarsest-level operator is only applied inside the inexact
        # pcg_jacobi coarse solve; use the cheap fused operator for it
        # unless the whole hierarchy is FA.
        lvl_assembly = assembly if (not is_coarsest or assembly == "fa") else "paop"
        op = ElasticityOperator(
            sp,
            assembly=lvl_assembly,
            materials=materials,
            dtype=dtype,
            ess_faces=ess_faces,
            pallas_interpret=pallas_interpret,
            pallas_lane=pallas_lane,
        )
        cop = op.constrained()
        smoother = None
        if not is_coarsest:
            diag = cop.diagonal()
            shape = (sp.nscalar, 3)
            if op.nbatch is not None:
                shape = (op.nbatch,) + shape
            smoother = ChebyshevSmoother.setup(
                cop,
                diag,
                shape=shape,
                dtype=dtype,
                degree=cheb_degree,
                power_iters=power_iters,
                batch_dims=1 if op.nbatch is not None else 0,
            )
        levels.append(
            Level(
                space=sp,
                operator=op,
                constrained=cop,
                smoother=smoother,
                ess_mask=op.ess_mask,
            )
        )

    transfers = [
        make_transfer(levels[i].space, levels[i + 1].space, dtype=dtype)
        for i in range(len(levels) - 1)
    ]
    coarse_solve = make_coarse_solver(levels[0].operator, method=coarse_method)
    return GMGPreconditioner(
        levels=levels, transfers=transfers, coarse_solve=coarse_solve
    )
