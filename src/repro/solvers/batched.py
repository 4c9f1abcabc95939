"""Batched multi-scenario GMG-PCG: many parameterized elasticity solves
in one device program, resumable in bounded chunks.

The paper's end-to-end solve (fused PAop operator + GMG-preconditioned
CG) runs one scenario at a time; this module amortizes compilation and
hardware occupancy across a *batch* of scenarios (different materials,
tractions, tolerances) the way the LM serving engine batches decode
requests:

* ``bpcg`` — PCG over a leading scenario axis.  Per-scenario convergence
  is tracked with an active mask: converged scenarios' ``x``/``r``/``d``
  are frozen (their step sizes are forced to zero and direction updates
  gated), the loop runs until every scenario converges or hits
  ``maxiter``, and per-scenario iteration counts are reported.

* the resumable step program — ``bpcg`` is split into
  :func:`bpcg_init` (build a pinned-shape :class:`BpcgState`) and
  :func:`bpcg_chunk` (advance all rows by a bounded number of
  iterations).  Because frozen rows never change, running chunks of
  ``k1`` then ``k2`` iterations produces exactly the state of one
  uninterrupted ``k1 + k2`` run, which is what lets a serving layer
  retire converged rows and refill their slots *between* chunks
  (continuous batching) instead of waiting for a whole generation.
  :func:`merge_states` resets just the refilled rows; untouched rows
  keep their state bitwise.

* ``BatchedGMGSolver`` — compiled solve *programs* for one
  discretization ``(coarse_mesh, n_h_refine, p)``.  Geometry (spaces,
  transfers, gather maps, basis tables, traction pattern) is built once
  at construction; materials, tractions and tolerances are **runtime
  arguments**.  Two jitted entry points drive the step program:
  ``prepare`` folds (new) per-scenario materials into the operators'
  per-row weighted fields in place and recomputes the derived
  per-scenario data (smoother diagonals + lambda_max, the coarse
  Cholesky factor) for exactly the reset rows; ``run_chunk`` rebuilds
  the hierarchy from that prep pytree (no power iterations, no
  refactorization), advances the state by ``k`` iterations and reports
  the per-row iterations consumed (the retire-cadence signal the
  adaptive chunk policies in :mod:`repro.serve.chunk_policy` use).  The
  monolithic ``solve`` is the same machinery run to completion in one
  call.  Re-solving with new scenario data hits the compiled programs —
  no retrace, no hierarchy rebuild.

The scenario axis is threaded through ``ChebyshevSmoother``,
``GMGPreconditioner`` and ``Transfer``; operators fold it into the
element axis so the fused PA kernels (including Pallas) run unchanged
on an S-times-larger grid.

Multi-device sharding: ``BatchedGMGSolver(..., mesh=...)`` (a 1-D
``jax.sharding`` mesh over the scenario axis, or an int meaning "the
first n devices") shards the scenario axis S across devices end to
end — the :class:`BpcgState` pytree, the prep pytree (weighted material
fields, smoother dinv/lambda_max, coarse Cholesky factors) and the
operators' folded (S*E, ...) element arrays all carry axis-0
``NamedSharding``.  Scenarios never couple, so each device runs the
exact single-device program on its own rows; the only cross-device
traffic is the (S,)-vector convergence logic of ``bpcg`` (cheap
all-gathers).  ``solve`` pads S up to a multiple of the device count
with born-converged rows (zero traction) and slices them back off, so
sharding is a pure implementation detail: results, iteration counts and
convergence flags are identical to the single-device path.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.operators import DEFER_MATERIALS, ElasticityOperator
from repro.core.precision import (
    PRECISION_POLICIES,
    PrecisionPolicy,
    check_policy,
    policy_refusal,
    resolve_precision,
)
from repro.kernels.pa_elasticity.ops import resolve_lane
from repro.distributed.sharding import (
    device_put_scenario,
    normalize_scenario_mesh,
    pin_scenario,
)
from repro.core.geometry import (
    check_material_dict,
    check_material_fields,
    material_fields,
)
from repro.fem.mesh import HexMesh, fine_descendants
from repro.fem.space import H1Space
from repro.fem.transfer import make_transfer
from repro.solvers.chebyshev import ChebyshevSmoother, _expand
from repro.solvers.coarse import cholesky_solver, probe_coarse_matrix
from repro.solvers.gmg import GMGPreconditioner, Level, hierarchy_spaces

__all__ = [
    "bpcg",
    "bpcg_init",
    "bpcg_chunk",
    "bpcg_result",
    "true_residual_audit",
    "merge_states",
    "BpcgState",
    "BPCGResult",
    "BatchedGMGSolver",
    "PCG_INIT",
    "PCG_APPLY",
    "PCG_PRECOND",
    "PCG_VECTOR",
    "PCG_AUDIT",
    "PREP",
    "PREP_COARSE",
]

# ``jax.named_scope`` names of the step and prep programs' device work;
# a profiler trace carries them as each operation's scope path.  The
# V-cycle nests its own (``repro.solvers.gmg``), and the operator apply
# nests ``pa.apply`` (``repro.core.operators``) under whichever called it.
PCG_INIT = "pcg.init"  # row (re)initialization: bpcg_init + merge_states
PCG_APPLY = "pcg.apply"  # the outer Krylov operator A(d)
PCG_PRECOND = "pcg.precond"  # the V-cycle M(r), casts included
PCG_VECTOR = "pcg.vector"  # dots, axpys, masks, stall bookkeeping
PCG_AUDIT = "pcg.audit"  # true_residual_audit
PREP = "prep"  # the prep program: material folds, smoother data
PREP_COARSE = "prep.coarse"  # coarse probe + Cholesky factor


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BPCGResult:
    x: Any  # (S, ...) solutions
    iterations: Any  # (S,) int32 per-scenario counts
    converged: Any  # (S,) bool
    final_norm: Any  # (S,) sqrt((B r, r)) at exit
    initial_norm: Any  # (S,)
    stalled: Any  # (S,) bool — stagnation detected (reduced precision)
    fallback: Any  # (S,) bool — row was re-solved on the f64 path


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BpcgState:
    """Pinned-shape resumable PCG state (one row per batch slot).

    Everything the iteration needs lives here, so a compiled
    ``run_chunk(state, k)`` can advance the batch, hand the state back to
    the host for retire/refill decisions, and resume bit-identically."""

    x: Any  # (S, ...) iterates
    r: Any  # (S, ...) residuals
    z: Any  # (S, ...) preconditioned residuals
    d: Any  # (S, ...) search directions
    nom: Any  # (S,) current (B r, r)
    nom0: Any  # (S,) (B r, r) at the row's (re)start
    threshold: Any  # (S,) per-row stopping value for nom
    iters: Any  # (S,) int32 iterations since the row's (re)start
    active: Any  # (S,) bool — still iterating
    best: Any  # (S,) lowest nom seen since the row's (re)start
    stall: Any  # (S,) int32 consecutive low-progress iterations
    stalled: Any  # (S,) bool — sticky stagnation flag (see bpcg_chunk)


def _dots(a, b):
    """Per-scenario inner products: contract everything but axis 0."""
    return jnp.sum(
        a.reshape(a.shape[0], -1) * b.reshape(b.shape[0], -1), axis=1
    )


# (S,) coefficients broadcast against (S, ...) vectors with the same
# right-pad rule the batched Chebyshev smoother uses.
_col = _expand


def bpcg_init(
    A: Callable,
    b,
    M: Callable | None = None,
    *,
    x0=None,
    rel_tol=1e-6,
    abs_tol=0.0,
) -> BpcgState:
    """Build the initial :class:`BpcgState` for ``A x = b``.

    MFEM-style thresholds, per scenario: a row stops when
    ``nom <= max(nom0 * rel_tol^2, abs_tol^2)``; ``rel_tol``/``abs_tol``
    may be scalars or (S,) arrays.  A row with a zero RHS is born
    converged (0 iterations) — this is also what makes padded batch
    slots free."""
    if M is None:
        M = lambda r: r
    s = b.shape[0]
    if x0 is None:
        x = jnp.zeros_like(b)
        r = b  # A is linear: A(0) == 0 exactly
    else:
        x = x0
        r = b - A(x)
    z = M(r)
    nom0 = _dots(z, r)
    rel = jnp.broadcast_to(jnp.asarray(rel_tol, dtype=nom0.dtype), (s,))
    ab = jnp.broadcast_to(jnp.asarray(abs_tol, dtype=nom0.dtype), (s,))
    threshold = jnp.maximum(nom0 * rel**2, ab**2)
    return BpcgState(
        x=x,
        r=r,
        z=z,
        d=z,
        nom=nom0,
        nom0=nom0,
        threshold=threshold,
        iters=jnp.zeros((s,), dtype=jnp.int32),
        active=nom0 > threshold,
        best=nom0,
        stall=jnp.zeros((s,), dtype=jnp.int32),
        stalled=jnp.zeros((s,), dtype=bool),
    )


def bpcg_chunk(
    A: Callable,
    state: BpcgState,
    M: Callable | None = None,
    *,
    k_iters=None,
    maxiter: int = 5000,
    stall_iters: int = 0,
    stall_rtol: float = 0.99,
) -> BpcgState:
    """Advance every active row by up to ``k_iters`` PCG iterations
    (unbounded — run to convergence/``maxiter`` — when ``k_iters`` is
    None).

    Chunked resumption is exact: inactive rows are frozen (alpha forced
    to 0, direction updates gated), so ``chunk(k1)`` followed by
    ``chunk(k2)`` yields the same state as one ``chunk(k1 + k2)`` call.
    ``k_iters`` may be a traced value, so one compiled program serves
    every chunk length.

    Stagnation detection (the reduced-precision safety net): with
    ``stall_iters > 0``, a row that goes ``stall_iters`` consecutive
    iterations without reducing its best-seen ``nom`` by at least a
    factor ``stall_rtol`` is flagged ``stalled`` (sticky) and
    deactivated — it has hit the precision floor of the arithmetic, and
    more iterations cannot help.  Tracked per scenario with the same
    masking as convergence, so one stuck row never holds the batch.
    The default ``stall_iters = 0`` disables detection entirely (no
    extra arithmetic in the loop body), keeping the f64 path
    bit-identical to the pre-stagnation program."""
    if M is None:
        M = lambda r: r

    def cond(carry):
        st, step = carry
        with jax.named_scope(PCG_VECTOR):
            go = jnp.any(st.active)
            if k_iters is not None:
                go = go & (step < k_iters)
        return go

    def body(carry):
        st, step = carry
        x, r, nom, active = st.x, st.r, st.nom, st.active
        with jax.named_scope(PCG_APPLY):
            ad = A(st.d)
        with jax.named_scope(PCG_VECTOR):
            den = _dots(st.d, ad)
            # Inactive rows get alpha = 0 (frozen); den == 0 cannot occur
            # for an active SPD row (d != 0 there) but is guarded so one
            # bad or retired scenario can never NaN the rest of the batch.
            ok = active & (den > 0)
            alpha = jnp.where(ok, nom / jnp.where(den == 0, 1.0, den), 0.0)
            x = x + _col(alpha, x.ndim) * st.d
            r = r - _col(alpha, r.ndim) * ad
        with jax.named_scope(PCG_PRECOND):
            z = M(r)
        with jax.named_scope(PCG_VECTOR):
            betanom = _dots(z, r)
            beta = jnp.where(ok, betanom / jnp.where(nom == 0, 1.0, nom), 0.0)
            d = jnp.where(
                _col(active, st.d.ndim), z + _col(beta, st.d.ndim) * st.d, st.d
            )
            nom = jnp.where(active, betanom, nom)
            # Count only real steps (ok), matching scalar pcg: an aborted
            # degenerate direction (den <= 0) takes no step and adds none.
            iters = st.iters + ok.astype(jnp.int32)
            active = ok & (nom > st.threshold) & (iters < maxiter)
            if stall_iters > 0:
                # Progress = the best-seen nom dropped by >= (1 - rtol);
                # best-so-far (not last-step) so an oscillating residual
                # doesn't reset the counter on every upswing.
                improved = betanom < st.best * stall_rtol
                stall = jnp.where(
                    ok, jnp.where(improved, 0, st.stall + 1), st.stall
                )
                best = jnp.where(ok, jnp.minimum(st.best, betanom), st.best)
                hit = active & (stall >= stall_iters)
                stalled = st.stalled | hit
                active = active & ~hit
                new = dataclasses.replace(
                    st, x=x, r=r, z=z, d=d, nom=nom, iters=iters,
                    active=active, best=best, stall=stall, stalled=stalled,
                )
            else:
                new = dataclasses.replace(
                    st, x=x, r=r, z=z, d=d, nom=nom, iters=iters, active=active
                )
            return (new, step + 1)

    state, _ = jax.lax.while_loop(
        cond, body, (state, jnp.zeros((), dtype=jnp.int32))
    )
    return state


def merge_states(reset_mask, fresh: BpcgState, old: BpcgState) -> BpcgState:
    """Per-row state merge: rows selected by ``reset_mask`` (S,) take
    ``fresh`` (a just-initialized state for their new RHS/tolerance),
    the rest keep ``old`` bitwise — refilling a slot must not perturb
    the rows still in flight."""
    mask = jnp.asarray(reset_mask)

    def pick(f, o):
        return jnp.where(_col(mask, jnp.ndim(f)), f, o)

    return BpcgState(
        **{
            fld.name: pick(getattr(fresh, fld.name), getattr(old, fld.name))
            for fld in dataclasses.fields(BpcgState)
        }
    )


def true_residual_audit(
    A: Callable, M: Callable, b, state: BpcgState, slack: float = 4.0
) -> BpcgState:
    """The reduced-precision honesty check: CG's recursively updated
    residual drifts from ``b - A x`` once rounding dominates, so its
    ``nom`` can sail below any threshold while the *true* residual sits
    at the arithmetic's floor.  Recompute the true preconditioned norm
    for rows claiming convergence; a row whose true ``nom`` exceeds its
    threshold by more than ``slack`` is marked ``stalled`` (sticky) and
    gets the true norm as its exit ``nom``, so ``bpcg_result`` reports
    it unconverged and the solve/serving layers route it to the f64
    fallback.  Rows passing the audit keep their state bitwise.  Never
    run on the f64 path (drift there is below any meaningful
    tolerance — and the extra A/M application isn't free)."""
    with jax.named_scope(PCG_AUDIT):
        claimed = (
            ~state.active & (state.nom <= state.threshold) & ~state.stalled
        )
        rt = b - A(state.x)
        nomt = _dots(M(rt), rt)
        lying = claimed & (nomt > state.threshold * slack)
        return dataclasses.replace(
            state,
            nom=jnp.where(lying, nomt, state.nom),
            stalled=state.stalled | lying,
        )


def _merge_fallback_rows(res: BPCGResult, sub: BPCGResult, rows) -> BPCGResult:
    """Merge an f64 re-solve of ``rows`` into a reduced-precision
    result.  The merged result is f64 (a fallback row's extra accuracy
    cannot ride an f32 vector); ``iterations`` accumulates so the
    reported count is the honest total cost, and ``fallback`` marks the
    re-solved rows while ``stalled`` keeps recording that the reduced
    pass flagged them."""
    rows = jnp.asarray(np.asarray(rows, dtype=np.int32))
    f64 = lambda a: jnp.asarray(a, jnp.float64)
    return BPCGResult(
        x=f64(res.x).at[rows].set(f64(sub.x)),
        iterations=res.iterations.at[rows].add(sub.iterations),
        converged=res.converged.at[rows].set(sub.converged),
        final_norm=f64(res.final_norm).at[rows].set(f64(sub.final_norm)),
        initial_norm=f64(res.initial_norm).at[rows].set(
            f64(sub.initial_norm)
        ),
        stalled=res.stalled,
        fallback=jnp.zeros_like(res.stalled).at[rows].set(True),
    )


def bpcg_result(state: BpcgState) -> BPCGResult:
    return BPCGResult(
        x=state.x,
        iterations=state.iters,
        converged=state.nom <= state.threshold,
        final_norm=jnp.sqrt(jnp.abs(state.nom)),
        initial_norm=jnp.sqrt(jnp.abs(state.nom0)),
        stalled=jnp.asarray(state.stalled),
        fallback=jnp.zeros_like(jnp.asarray(state.stalled)),
    )


def bpcg(
    A: Callable,
    b,
    M: Callable | None = None,
    *,
    x0=None,
    rel_tol=1e-6,
    abs_tol=0.0,
    maxiter: int = 5000,
    stall_iters: int = 0,
    stall_rtol: float = 0.99,
) -> BPCGResult:
    """MFEM-style PCG over a leading scenario axis with masked
    convergence.

    ``A`` and ``M`` map (S, ...) batches to (S, ...) batches with no
    cross-scenario coupling; ``rel_tol``/``abs_tol`` may be scalars or
    (S,) arrays (per-scenario tolerances).  Scenarios that converge stop
    updating while the rest keep iterating; the loop exits when no
    scenario is active.  Implemented as the resumable step program run
    in one uninterrupted chunk (see :func:`bpcg_init` /
    :func:`bpcg_chunk`; ``stall_iters`` enables the per-row stagnation
    detector for reduced-precision runs)."""
    state = bpcg_init(A, b, M, x0=x0, rel_tol=rel_tol, abs_tol=abs_tol)
    state = bpcg_chunk(
        A, state, M, k_iters=None, maxiter=maxiter,
        stall_iters=stall_iters, stall_rtol=stall_rtol,
    )
    return bpcg_result(state)


class BatchedGMGSolver:
    """Compiled multi-scenario solve programs for one discretization.

    Construction builds everything material-independent for the beam
    benchmark family: the mesh/degree hierarchy, transfer operators,
    per-level fine-descendant maps, and the boundary traction pattern.
    ``solve`` takes per-scenario materials (attribute dicts and/or
    per-element (lam_e, mu_e) coefficient arrays — see
    :meth:`pack_materials`), traction vectors and tolerances and runs to
    completion; ``prepare`` + ``run_chunk`` expose the same solve as a
    resumable step program for continuous batching.  Each jitted entry
    point is traced once per batch size (bucket) and reused for every
    subsequent call of the same shape.

    Precision: ``precision`` names a
    :class:`~repro.core.precision.PrecisionPolicy` (``"f64"``,
    ``"f32"``, ``"mixed"``, ``"mixed-bf16"`` or a policy object).  The
    outer Krylov loop — ``BpcgState`` vectors, operator apply in the CG
    recurrence, residual norms, thresholds — runs in
    ``policy.solve_dtype`` (exposed as ``self.dtype``); the GMG V-cycle
    (weighted material fields, Chebyshev smoother, transfers) runs in
    ``policy.precond_dtype``; the coarse probe/Cholesky in
    ``policy.coarse_dtype``.  For genuinely mixed policies the fine
    level keeps a second, ``solve_dtype`` copy of its weighted fields
    (``prep["lam_w_solve"]``/``prep["mu_w_solve"]``) so the outer
    residual is computed at full precision while the smoother streams
    reduced bytes.  Reduced policies run with the stagnation detector
    on, and ``solve`` re-solves any stalled rows on a lazily built f64
    twin solver (``fallback`` marks them in the result).  The legacy
    ``dtype`` argument still works and resolves to the matching uniform
    policy.
    """

    def __init__(
        self,
        coarse_mesh: HexMesh,
        n_h_refine: int,
        p_target: int,
        *,
        assembly: str = "paop",
        dtype=None,
        precision: str | PrecisionPolicy | None = None,
        cheb_degree: int = 2,
        power_iters: int = 10,
        ess_faces=("x0",),
        traction_face: str = "x1",
        maxiter: int = 200,
        stall_iters: int = 20,
        stall_rtol: float = 0.99,
        pallas_interpret: bool | None = None,
        pallas_lane: str | None = None,
        mesh=None,
    ):
        if assembly == "fa":
            raise ValueError("batched solves are matrix-free ('fa' unsupported)")
        self.coarse_mesh = coarse_mesh
        self.n_h_refine = n_h_refine
        self.p_target = p_target
        self.assembly = assembly
        self.precision = resolve_precision(precision, dtype)
        self.dtype = self.precision.solve_dtype
        self.precond_dtype = self.precision.precond_dtype
        self.coarse_dtype = self.precision.coarse_dtype
        self.cheb_degree = cheb_degree
        self.power_iters = power_iters
        self.maxiter = maxiter
        # Stagnation detection is armed only for reduced policies: the
        # f64 program stays bit-identical (stall_iters=0 compiles the
        # detector out of the loop body entirely).
        self.stall_iters = stall_iters if self.precision.reduced else 0
        self.stall_rtol = stall_rtol
        self._f64_twin: BatchedGMGSolver | None = None
        self._ess_faces = ess_faces
        self._traction_face = traction_face
        # Pallas lane, resolved ONCE here so every level operator runs
        # the same lane and ``self.pallas_lane`` reports what actually
        # runs ("compiled" or "interpret"; auto is compiled on a TPU and
        # interpret elsewhere).
        self.pallas_lane = resolve_lane(pallas_lane, interpret=pallas_interpret)
        check_policy(self.precision, assembly, self.pallas_lane)
        # Whether stalled rows can be re-solved under ``f64`` here (not
        # on a TPU, nor on the compiled Pallas lane); when not, they
        # stay unconverged and ``stalled`` in the result.
        self.can_fall_back = (
            policy_refusal(PRECISION_POLICIES["f64"], assembly,
                           self.pallas_lane) is None
        )
        # Scenario-axis device mesh (None = single-device).  An int is
        # shorthand for "shard over the first n devices".
        self.mesh, self.n_shards = normalize_scenario_mesh(mesh)

        spaces = hierarchy_spaces(coarse_mesh, n_h_refine, p_target)
        self.spaces = spaces

        # Attribute vocabulary (static): kept for validating attribute-
        # dict scenarios against the mesh (pack_materials).
        self.attr_values: tuple[int, ...] = tuple(
            int(a) for a in np.unique(coarse_mesh.attributes())
        )

        # Scenario materials travel as (S, nelem_fine) per-element
        # coefficient fields (attribute dicts are expanded on intake by
        # pack_materials).  Each coarser h-level sees the fine field
        # through its fine-descendant map — an exact power-of-two tree
        # average (see _restrict_field); p-embedding levels share the
        # fine mesh, so their map is the identity (stored as None).
        fine_mesh = spaces[-1].mesh
        # True when the outer Krylov and the V-cycle run different
        # dtypes — the fine level then carries a solve-dtype twin of its
        # base operator (outer A) next to the precond-dtype one.
        self._split_fine = jnp.dtype(self.dtype) != jnp.dtype(
            self.precond_dtype
        )
        self._base_ops = []
        self._desc_idx: list[Any] = []
        for i, sp in enumerate(spaces):
            lvl_assembly = assembly if i > 0 else "paop"
            # Base operators are geometry/tables carriers only: every
            # solve binds per-scenario fields via with_materials*.  The
            # V-cycle levels live at the policy's precond dtype.
            op = ElasticityOperator(
                sp,
                assembly=lvl_assembly,
                materials=DEFER_MATERIALS,
                dtype=self.precond_dtype,
                ess_faces=ess_faces,
                pallas_lane=self.pallas_lane,
                shard_mesh=self.mesh,
            )
            self._base_ops.append(op)
            self._desc_idx.append(
                None
                if sp.nelem == fine_mesh.nelem
                else jnp.asarray(fine_descendants(sp.mesh, fine_mesh))
            )
        self._fine_base_solve = (
            ElasticityOperator(
                spaces[-1],
                assembly=assembly if len(spaces) > 1 else "paop",
                materials=DEFER_MATERIALS,
                dtype=self.dtype,
                ess_faces=ess_faces,
                pallas_lane=self.pallas_lane,
                shard_mesh=self.mesh,
            )
            if self._split_fine
            else None
        )

        self.transfers = [
            make_transfer(
                spaces[i], spaces[i + 1], dtype=self.precond_dtype,
                shard_mesh=self.mesh,
            )
            for i in range(len(spaces) - 1)
        ]
        # traction_rhs is linear in the traction vector and separable:
        # F = pattern (x) t, so probing with t = e_x yields the pattern.
        fine = spaces[-1]
        self._traction_pattern = jnp.asarray(
            fine.traction_rhs(traction_face, (1.0, 0.0, 0.0))[:, 0],
            dtype=self.dtype,
        )
        self._fine_ess = jnp.asarray(self._base_ops[-1].ess_mask)
        self._jit_solve = jax.jit(self._solve_impl)
        self._jit_prepare = jax.jit(self._prepare_impl)
        # One step program per chunk kind, so a device trace names which
        # ran: the chunk that (re)starts rows and the one that resumes.
        self._jit_chunk = {
            True: jax.jit(self._chunk_start),
            False: jax.jit(self._chunk_resume),
        }

    @property
    def fine_space(self) -> H1Space:
        return self.spaces[-1]

    # -- sharding ------------------------------------------------------------
    def pad_batch(self, n: int) -> int:
        """Rows a batch of ``n`` scenarios must be padded to so the
        scenario axis divides the device mesh (n unchanged when
        single-device)."""
        m = self.n_shards
        return -(-n // m) * m

    def pad_scenarios(self, materials, tractions, rel_tol, n: int | None = None):
        """Pad a scenario batch to ``n`` rows (default: the device-aligned
        ``pad_batch`` size) with born-converged padding rows: the first
        scenario's materials (dict or per-element array pair alike —
        keeps the batched operators SPD) and a zero traction, so b == 0
        makes them free (0 iterations).  The ONE definition of the
        padding-row convention; the service and the differential tests
        both go through it.  Returns ``(materials, tractions, rel_tols,
        n_real)`` with rel_tols broadcast to a per-row array."""
        s = len(materials)
        if n is None:
            n = self.pad_batch(s)
        # Solver dtype, NOT a hard-coded float64: a non-f64 solver must
        # not have its runtime arguments silently promoted (the whole
        # solve would re-trace and run at the wrong precision).
        sdt = np.dtype(self.dtype)
        tractions = np.asarray(tractions, dtype=sdt)
        rel = np.broadcast_to(np.asarray(rel_tol, dtype=sdt), (s,)).copy()
        if n > s:
            materials = list(materials) + [materials[0]] * (n - s)
            tractions = np.concatenate(
                [tractions, np.zeros((n - s, 3), dtype=sdt)], axis=0
            )
            rel = np.concatenate([rel, np.full((n - s,), 1e-6, dtype=sdt)])
        return materials, tractions, rel, s

    def _check_batch(self, s: int, what: str) -> None:
        if s % self.n_shards:
            raise ValueError(
                f"{what}: batch size {s} does not divide the "
                f"{self.n_shards}-device scenario mesh; pad to "
                f"pad_batch({s}) = {self.pad_batch(s)} born-converged rows"
            )

    def _pin(self, tree):
        """with_sharding_constraint (traced): axis-0 scenario sharding."""
        return pin_scenario(tree, self.mesh)

    def _put(self, tree):
        """device_put (host-side): axis-0 scenario sharding."""
        return device_put_scenario(tree, self.mesh)

    # -- prep pytree ---------------------------------------------------------
    # prep carries every per-scenario derived quantity the step program
    # needs, as plain arrays: the operators' weighted material fields per
    # level, the smoother inverse diagonals + lambda_max per smoothed
    # level, and the coarse Cholesky factor.  It is produced by
    # ``prepare`` (jitted) and consumed by ``run_chunk`` (jitted), so
    # chunks pay neither power iterations nor refactorization.

    def empty_prep(self, s: int) -> dict:
        """Zero-filled prep of the right shapes for an S-row batch (laid
        out over the scenario mesh when sharded).  Only meaningful as the
        ``prep`` argument of a ``prepare`` call whose reset mask covers
        every row that will ever be read."""
        self._check_batch(s, "empty_prep")
        pdt = np.dtype(self.precond_dtype)
        lam_w, mu_w, dinv, lmax = [], [], [], []
        for i, (base, sp) in enumerate(zip(self._base_ops, self.spaces)):
            shape = (s * sp.nelem,) + base.w_detj.shape
            lam_w.append(np.zeros(shape, dtype=pdt))
            mu_w.append(np.zeros(shape, dtype=pdt))
            if i > 0:
                dinv.append(np.zeros((s, sp.nscalar, 3), dtype=pdt))
                lmax.append(np.zeros((s,), dtype=pdt))
        n0 = self.spaces[0].nscalar * 3
        prep = {
            "lam_w": tuple(lam_w),
            "mu_w": tuple(mu_w),
            "dinv": tuple(dinv),
            "lmax": tuple(lmax),
            "chol": np.zeros((s, n0, n0), dtype=np.dtype(self.coarse_dtype)),
        }
        if self._split_fine:
            fine = self.spaces[-1]
            shape = (s * fine.nelem,) + self._fine_base_solve.w_detj.shape
            sdt = np.dtype(self.dtype)
            prep["lam_w_solve"] = np.zeros(shape, dtype=sdt)
            prep["mu_w_solve"] = np.zeros(shape, dtype=sdt)
        return self._put(prep)

    def empty_state(self, s: int) -> BpcgState:
        """All-rows-retired state of the right shapes for an S-row batch
        (every row must be reset before its first chunk; laid out over
        the scenario mesh when sharded)."""
        self._check_batch(s, "empty_state")
        vec = np.zeros((s, self.fine_space.nscalar, 3), dtype=np.dtype(self.dtype))
        row = np.zeros((s,), dtype=np.dtype(self.dtype))
        return self._put(
            BpcgState(
                x=vec,
                r=vec,
                z=vec,
                d=vec,
                nom=row,
                nom0=row,
                threshold=row,
                iters=np.zeros((s,), dtype=np.int32),
                active=np.zeros((s,), dtype=bool),
                best=row,
                stall=np.zeros((s,), dtype=np.int32),
                stalled=np.zeros((s,), dtype=bool),
            )
        )

    def take_rows(self, state: BpcgState, prep: dict, rows):
        """Gather batch rows (host-side re-bucketing): returns (state,
        prep) whose row i is the old row ``rows[i]``.  ``rows`` may
        repeat indices (placeholder rows that the caller is about to
        reset) and may be shorter or longer than the old batch.  The
        result is re-laid-out over the scenario mesh (a re-bucketing
        changes which device owns which row)."""
        rows = np.asarray(rows, dtype=np.int32)
        self._check_batch(len(rows), "take_rows")
        new_state = BpcgState(
            **{
                fld.name: jnp.asarray(getattr(state, fld.name))[rows]
                for fld in dataclasses.fields(BpcgState)
            }
        )

        def fold_take(w, ne):
            s_old = w.shape[0] // ne
            folded = jnp.asarray(w).reshape((s_old, ne) + w.shape[1:])
            return folded[rows].reshape((-1,) + w.shape[1:])

        new_prep = {
            "lam_w": tuple(
                fold_take(w, sp.nelem)
                for w, sp in zip(prep["lam_w"], self.spaces)
            ),
            "mu_w": tuple(
                fold_take(w, sp.nelem)
                for w, sp in zip(prep["mu_w"], self.spaces)
            ),
            "dinv": tuple(jnp.asarray(d)[rows] for d in prep["dinv"]),
            "lmax": tuple(jnp.asarray(l)[rows] for l in prep["lmax"]),
            "chol": jnp.asarray(prep["chol"])[rows],
        }
        if self._split_fine:
            ne = self.fine_space.nelem
            new_prep["lam_w_solve"] = fold_take(prep["lam_w_solve"], ne)
            new_prep["mu_w_solve"] = fold_take(prep["mu_w_solve"], ne)
        return self._put(new_state), self._put(new_prep)

    def copy_prep_rows(self, prep: dict, src, dst) -> dict:
        """Duplicate prepared batch rows: row ``dst[i]`` takes row
        ``src[i]``'s derived data (weighted fields, smoother dinv/lmax,
        coarse factor) bitwise.  Since prep depends only on a row's
        materials (geometry is shared), a refilled slot whose materials
        match an already-prepared row can skip ``prepare`` — no power
        iterations, no refactorization — which is the common case for
        serving traffic with a bounded material vocabulary."""
        src = np.asarray(src, dtype=np.int32)
        dst = np.asarray(dst, dtype=np.int32)

        def fold_copy(w, ne):
            s = w.shape[0] // ne
            f = jnp.asarray(w).reshape((s, ne) + w.shape[1:])
            return f.at[dst].set(f[src]).reshape((-1,) + w.shape[1:])

        def row_copy(a):
            a = jnp.asarray(a)
            return a.at[dst].set(a[src])

        new_prep = {
            "lam_w": tuple(
                fold_copy(w, sp.nelem)
                for w, sp in zip(prep["lam_w"], self.spaces)
            ),
            "mu_w": tuple(
                fold_copy(w, sp.nelem)
                for w, sp in zip(prep["mu_w"], self.spaces)
            ),
            "dinv": tuple(row_copy(d) for d in prep["dinv"]),
            "lmax": tuple(row_copy(l) for l in prep["lmax"]),
            "chol": row_copy(prep["chol"]),
        }
        if self._split_fine:
            ne = self.fine_space.nelem
            new_prep["lam_w_solve"] = fold_copy(prep["lam_w_solve"], ne)
            new_prep["mu_w_solve"] = fold_copy(prep["mu_w_solve"], ne)
        return self._put(new_prep)

    # -- host (de)serialization ----------------------------------------------
    # The checkpoint contract for fault-tolerant serving
    # (repro.serve.recovery): a resumable (state, prep) pair round-trips
    # through flat {name: host numpy array} dicts BITWISE — chunked
    # resumption is exact (see bpcg_chunk), so a restored flight that
    # re-enters run_chunk with these arrays finishes with the same
    # solutions and iteration counts as the uninterrupted run.  The name
    # vocabulary is self-describing per solver: BpcgState field names
    # for the state; ``lam_w{i}``/``mu_w{i}`` per hierarchy level,
    # ``dinv{i}``/``lmax{i}`` per smoothed level, ``chol``, and (for
    # genuinely mixed precision policies) the ``lam_w_solve``/
    # ``mu_w_solve`` fine-level twins for the prep.

    def state_dtype(self, field: str):
        """The dtype contract of one BpcgState field under this solver's
        precision policy (checkpoint restore casts through this, so a
        manifest written by the same policy round-trips bitwise and a
        mismatched one fails loudly in the numerics, not silently)."""
        if field in ("iters", "stall"):
            return np.int32
        if field in ("active", "stalled"):
            return np.bool_
        return np.dtype(self.dtype)

    def state_to_host(self, state: BpcgState) -> dict[str, np.ndarray]:
        """Host-gathered flat snapshot of a resumable state: one numpy
        array per BpcgState field, bitwise."""
        return {
            fld.name: np.asarray(jax.device_get(getattr(state, fld.name)))
            for fld in dataclasses.fields(BpcgState)
        }

    def state_from_host(
        self, arrays: dict[str, np.ndarray], *, place: bool = True
    ) -> BpcgState:
        """Rebuild a :class:`BpcgState` from a :meth:`state_to_host`
        snapshot, re-laid-out over THIS solver's scenario mesh — the
        elastic-restore path: the snapshot may come from a process with
        a different device count.  With ``place=False`` the state stays
        host-resident and unvalidated (for a ``take_rows`` re-bucketing
        immediately after, when the old batch does not divide the new
        mesh)."""
        state = BpcgState(
            **{
                fld.name: np.asarray(
                    arrays[fld.name], dtype=self.state_dtype(fld.name)
                )
                for fld in dataclasses.fields(BpcgState)
            }
        )
        if not place:
            return state
        self._check_batch(state.x.shape[0], "state_from_host")
        return self._put(state)

    def prep_to_host(self, prep: dict) -> dict[str, np.ndarray]:
        """Host-gathered flat snapshot of a prep pytree (see the
        contract note above for the name vocabulary)."""
        out: dict[str, np.ndarray] = {}
        get = lambda a: np.asarray(jax.device_get(a))
        for i, (lw, mw) in enumerate(zip(prep["lam_w"], prep["mu_w"])):
            out[f"lam_w{i}"] = get(lw)
            out[f"mu_w{i}"] = get(mw)
        for i, (d, l) in enumerate(zip(prep["dinv"], prep["lmax"])):
            out[f"dinv{i}"] = get(d)
            out[f"lmax{i}"] = get(l)
        out["chol"] = get(prep["chol"])
        if self._split_fine:
            out["lam_w_solve"] = get(prep["lam_w_solve"])
            out["mu_w_solve"] = get(prep["mu_w_solve"])
        return out

    def prep_from_host(
        self, arrays: dict[str, np.ndarray], *, place: bool = True
    ) -> dict:
        """Rebuild a prep pytree from a :meth:`prep_to_host` snapshot
        (``place`` as in :meth:`state_from_host`).  Raises KeyError if
        the snapshot's level structure does not match this solver —
        e.g. a checkpoint from a different discretization or a mixed
        policy's twins fed to a uniform-policy solver."""
        n_lv = len(self.spaces)
        prep = {
            "lam_w": tuple(arrays[f"lam_w{i}"] for i in range(n_lv)),
            "mu_w": tuple(arrays[f"mu_w{i}"] for i in range(n_lv)),
            "dinv": tuple(arrays[f"dinv{i}"] for i in range(n_lv - 1)),
            "lmax": tuple(arrays[f"lmax{i}"] for i in range(n_lv - 1)),
            "chol": arrays["chol"],
        }
        if self._split_fine:
            prep["lam_w_solve"] = arrays["lam_w_solve"]
            prep["mu_w_solve"] = arrays["mu_w_solve"]
        if not place:
            return prep
        self._check_batch(prep["chol"].shape[0], "prep_from_host")
        return self._put(prep)

    # -- traced bodies -------------------------------------------------------
    def _restrict_field(self, field, level: int):
        """Restrict a (S, nelem_fine) per-element coefficient field to
        hierarchy level ``level`` by averaging each level element's fine
        descendants.  The reduction is a pairwise halving tree over the
        (power-of-two) descendant count, so it is *exact* whenever all
        descendants of an element carry the same value — which is what
        makes a piecewise-constant array field reproduce the equivalent
        attribute-dict scenario bit-for-bit on every level.  Identity
        (no gather) on levels that share the fine mesh."""
        desc = self._desc_idx[level]
        if desc is None:
            return field
        g = field[:, desc]  # (S, nelem_level, n_children)
        k = g.shape[-1]
        while g.shape[-1] > 1:
            g = g[..., 0::2] + g[..., 1::2]
        return g[..., 0] / k

    def _prepare_body(self, lam_vals, mu_vals, reset_mask, prep) -> dict:
        """Fold the (S, nelem_fine) material fields of the masked rows
        into the per-level weighted fields in place (coarser levels via
        :meth:`_restrict_field`), and recompute the derived per-scenario
        data (smoother dinv/lambda_max, coarse Cholesky) for exactly
        those rows; unmasked rows keep their prep bitwise."""
        s = lam_vals.shape[0]
        lam_vals, mu_vals, reset_mask, prep = self._pin(
            (lam_vals, mu_vals, reset_mask, prep)
        )
        lam_w, mu_w, dinv, lmax = [], [], [], []
        chol = None
        for i, base in enumerate(self._base_ops):
            sp = self.spaces[i]
            prev = base.with_material_weights(
                prep["lam_w"][i], prep["mu_w"][i], s
            )
            op = prev.with_materials_rows(
                self._restrict_field(lam_vals, i),
                self._restrict_field(mu_vals, i),
                reset_mask,
            )
            lam_w.append(self._pin(op.lam_w))
            mu_w.append(self._pin(op.mu_w))
            cop = op.constrained()
            if i == 0:
                # Probe at the V-cycle dtype (the operator's own), then
                # factor at the coarse dtype — mixed-bf16 probes through
                # a bf16 operator but holds the Cholesky at f32, where
                # the factorization is still numerically viable.
                with jax.named_scope(PREP_COARSE):
                    K = probe_coarse_matrix(
                        cop, sp.nscalar, s, self.precond_dtype,
                        shard_mesh=self.mesh,
                    )
                    L = jnp.linalg.cholesky(K.astype(self.coarse_dtype))
                    chol = self._pin(
                        jnp.where(reset_mask[:, None, None], L, prep["chol"])
                    )
            else:
                sm = ChebyshevSmoother.setup(
                    cop,
                    cop.diagonal(),
                    shape=(s, sp.nscalar, 3),
                    dtype=self.precond_dtype,
                    degree=self.cheb_degree,
                    power_iters=self.power_iters,
                    batch_dims=1,
                    shard_mesh=self.mesh,
                )
                dinv.append(
                    self._pin(
                        jnp.where(
                            reset_mask[:, None, None],
                            sm.dinv,
                            prep["dinv"][i - 1],
                        )
                    )
                )
                lmax.append(
                    self._pin(
                        jnp.where(reset_mask, sm.lmax, prep["lmax"][i - 1])
                    )
                )
        out = {
            "lam_w": tuple(lam_w),
            "mu_w": tuple(mu_w),
            "dinv": tuple(dinv),
            "lmax": tuple(lmax),
            "chol": chol,
        }
        if self._split_fine:
            # Solve-dtype twin of the fine-level weighted fields: the
            # outer Krylov's operator apply must run at full precision
            # even while the smoother streams the reduced copy.
            prev = self._fine_base_solve.with_material_weights(
                prep["lam_w_solve"], prep["mu_w_solve"], s
            )
            op = prev.with_materials_rows(
                lam_vals, mu_vals, reset_mask
            )
            out["lam_w_solve"] = self._pin(op.lam_w)
            out["mu_w_solve"] = self._pin(op.mu_w)
        return out

    def _build_from_prep(self, prep):
        """Hierarchy + preconditioner from a prep pytree: binds the
        stored weighted fields and smoother data — no power iterations,
        no probing, no factorization.

        Returns ``(levels, gmg, A, M)``: ``A`` is the outer Krylov
        operator at ``solve_dtype`` (the fine level's solve-dtype twin
        under a genuinely mixed policy, the fine V-cycle level
        otherwise) and ``M`` the preconditioner with the solve<->precond
        cast boundary folded in (identity casts under uniform
        policies)."""
        s = prep["chol"].shape[0]
        levels = []
        for i, base in enumerate(self._base_ops):
            sp = self.spaces[i]
            op = base.with_material_weights(
                prep["lam_w"][i], prep["mu_w"][i], s
            )
            cop = op.constrained()
            smoother = None
            if i > 0:
                smoother = ChebyshevSmoother(
                    A=cop,
                    dinv=prep["dinv"][i - 1],
                    lmax=prep["lmax"][i - 1],
                    degree=self.cheb_degree,
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
        coarse = cholesky_solver(prep["chol"], shard_mesh=self.mesh)
        if jnp.dtype(self.coarse_dtype) != jnp.dtype(self.precond_dtype):
            inner, cdt, pdt = coarse, self.coarse_dtype, self.precond_dtype
            coarse = lambda r: inner(r.astype(cdt)).astype(pdt)
        gmg = GMGPreconditioner(
            levels=levels,
            transfers=self.transfers,
            coarse_solve=coarse,
        )
        if self._split_fine:
            fine_solve = self._fine_base_solve.with_material_weights(
                prep["lam_w_solve"], prep["mu_w_solve"], s
            )
            A = fine_solve.constrained()
            sdt, pdt = self.dtype, self.precond_dtype
            M = lambda r: gmg(r.astype(pdt)).astype(sdt)
        else:
            A = levels[-1].constrained
            M = gmg
        return levels, gmg, A, M

    def _rhs(self, tractions):
        b = self._traction_pattern[None, :, None] * tractions[:, None, :]
        return self._pin(
            jnp.where(self._fine_ess, 0.0, b)  # homogeneous elimination
        )

    def _prepare_impl(self, lam_vals, mu_vals, reset_mask, prep) -> dict:
        with jax.named_scope(PREP):
            return self._prepare_body(lam_vals, mu_vals, reset_mask, prep)

    def _chunk_impl(
        self, tractions, rel_tol, reset_mask, state, prep, k_iters,
        *, do_reset: bool,
    ) -> tuple[BpcgState, Any]:
        state, prep = self._pin(state), self._pin(prep)
        levels, gmg, A, M = self._build_from_prep(prep)
        if do_reset:
            with jax.named_scope(PCG_INIT):
                fresh = bpcg_init(
                    A, self._rhs(tractions), M=M, rel_tol=rel_tol
                )
                state = merge_states(reset_mask, fresh, state)
        start_iters = state.iters
        out = bpcg_chunk(
            A, state, M=M, k_iters=k_iters, maxiter=self.maxiter,
            stall_iters=self.stall_iters, stall_rtol=self.stall_rtol,
        )
        if self.stall_iters > 0:
            out = true_residual_audit(A, M, self._rhs(tractions), out)
        # Per-row iterations consumed by THIS chunk: the scheduling
        # policies read retire cadence from this (S,) vector, so the
        # host never has to fetch the full state mid-flight.
        return self._pin(out), self._pin(out.iters - start_iters)

    def _chunk_start(self, tractions, rel_tol, reset_mask, state, prep,
                     k_iters):
        return self._chunk_impl(tractions, rel_tol, reset_mask, state, prep,
                                k_iters, do_reset=True)

    def _chunk_resume(self, tractions, rel_tol, reset_mask, state, prep,
                      k_iters):
        return self._chunk_impl(tractions, rel_tol, reset_mask, state, prep,
                                k_iters, do_reset=False)

    def _solve_impl(self, lam_vals, mu_vals, tractions, rel_tol):
        s = lam_vals.shape[0]
        with jax.named_scope(PREP):
            prep = self._prepare_body(
                lam_vals, mu_vals, jnp.ones((s,), dtype=bool),
                self.empty_prep(s),
            )
        levels, gmg, A, M = self._build_from_prep(prep)
        with jax.named_scope(PCG_INIT):
            state = bpcg_init(A, self._rhs(tractions), M=M, rel_tol=rel_tol)
        state = bpcg_chunk(
            A, state, M=M, k_iters=None, maxiter=self.maxiter,
            stall_iters=self.stall_iters, stall_rtol=self.stall_rtol,
        )
        if self.stall_iters > 0:
            state = true_residual_audit(A, M, self._rhs(tractions), state)
        return bpcg_result(self._pin(state))

    # -- public entry --------------------------------------------------------
    def pack_materials(self, materials: list) -> tuple[Any, Any]:
        """Normalize a length-S scenario list into (S, nelem_fine)
        per-element coefficient fields.

        Each entry is either an attribute -> (lambda, mu) dict
        (piecewise-constant by mesh attribute) or a ``(lam_e, mu_e)``
        array pair of shape (nelem_fine,) giving one coefficient per
        FINE-mesh element; the two forms mix freely within one batch.
        Coarser hierarchy levels see each field through an exact
        power-of-two descendant average (:meth:`_restrict_field`), so a
        piecewise-constant array reproduces the equivalent dict scenario
        bit-for-bit.  Raises ValueError naming the scenario plus the
        missing/offending attribute (dicts) or the mismatched shape /
        first non-positive element index (arrays)."""
        ne = self.fine_space.nelem
        fine_mesh = self.fine_space.mesh
        lam = np.empty((len(materials), ne))
        mu = np.empty_like(lam)
        for si, m in enumerate(materials):
            where = f"scenario {si} materials"
            if isinstance(m, dict):
                check_material_dict(m, self.attr_values, where=where)
                lam[si], mu[si] = material_fields(fine_mesh, m)
            else:
                if getattr(m, "ndim", None) is not None and np.ndim(m) != 1:
                    # A bare 2-D array entry means the caller passed the
                    # raw stacked (lam_2d, mu_2d) pair itself instead of
                    # a scenario list — unpacking its rows here would
                    # silently cross-pair lambda/mu across scenarios.
                    raise TypeError(
                        f"{where}: got a {np.ndim(m)}-D array as a "
                        f"scenario entry; pack_materials takes a LIST "
                        f"of per-scenario entries (dicts or (lam_e, "
                        f"mu_e) pairs) — for a pre-stacked (S, nelem) "
                        f"pair use list(zip(lam, mu))"
                    )
                try:
                    lam_e, mu_e = m
                except (TypeError, ValueError):
                    raise TypeError(
                        f"{where}: expected an attribute->(lambda, mu) "
                        f"dict or a (lam_e, mu_e) array pair, got "
                        f"{type(m).__name__!r}"
                    ) from None
                lam[si], mu[si] = check_material_fields(
                    lam_e, mu_e, ne, where=where
                )
        return jnp.asarray(lam, self.dtype), jnp.asarray(mu, self.dtype)

    def prepare(self, lam_vals, mu_vals, reset_mask, prep) -> dict:
        """Jitted: fold the masked rows' new materials into the per-row
        operator fields and refresh their derived data (see
        ``_prepare_body``).

        ``lam_vals``/``mu_vals`` are (S, nelem_fine) per-element fields
        (the output of :meth:`pack_materials`); S must divide the device
        mesh when sharded — the fields ride the same axis-0
        NamedSharding as the rest of the prep pytree.  Rows NOT selected
        by ``reset_mask`` keep their prep bitwise.  One trace per batch
        size."""
        s, ne = np.shape(lam_vals)
        self._check_batch(int(s), "prepare")
        if ne != self.fine_space.nelem:
            raise ValueError(
                f"prepare: material fields have {ne} elements per row, "
                f"expected nelem_fine = {self.fine_space.nelem}"
            )
        lam_vals, mu_vals, reset_mask, prep = self._put(
            (lam_vals, mu_vals, reset_mask, prep)
        )
        return self._jit_prepare(lam_vals, mu_vals, reset_mask, prep)

    def run_chunk(
        self, tractions, rel_tol, reset_mask, state, prep, k_iters,
        *, do_reset: bool = False,
    ) -> tuple[BpcgState, Any]:
        """Jitted: advance the batch by up to ``k_iters`` iterations.
        With ``do_reset`` the masked rows are first re-initialized for
        their (new) tractions/tolerances: x = 0, r = b, fresh thresholds,
        iteration count 0 (their materials must already be folded into
        ``prep`` via :meth:`prepare` or :meth:`copy_prep_rows`); rows
        outside the mask resume bit-identically.  The batch size must
        divide the device mesh when sharded — padding rows are the
        caller's job (see :meth:`pad_scenarios`).  ``k_iters`` is a
        runtime argument — any chunk length reuses the same compiled
        program.

        Returns ``(state, consumed)`` where ``consumed`` is the (S,)
        int32 count of iterations each row executed inside this chunk
        (0 for rows that entered inactive).  It is the cadence signal
        the adaptive chunk policies feed on: one small vector instead of
        an extra mid-flight fetch of the full state."""
        tractions = jnp.asarray(tractions, self.dtype)
        self._check_batch(int(tractions.shape[0]), "run_chunk")
        rel = jnp.broadcast_to(
            jnp.asarray(rel_tol, self.dtype), (tractions.shape[0],)
        )
        tractions, rel, reset_mask, state, prep = self._put(
            (tractions, rel, reset_mask, state, prep)
        )
        return self._jit_chunk[do_reset](
            tractions, rel, reset_mask, state, prep,
            jnp.asarray(k_iters, dtype=jnp.int32),
        )

    def _f64_fallback_solver(self) -> "BatchedGMGSolver":
        """The lazily built f64 twin that re-solves stalled rows: same
        discretization/geometry, the ``f64`` policy (which never
        recurses — its own detector is disarmed)."""
        if self._f64_twin is None:
            self._f64_twin = BatchedGMGSolver(
                self.coarse_mesh,
                self.n_h_refine,
                self.p_target,
                assembly=self.assembly,
                precision="f64",
                cheb_degree=self.cheb_degree,
                power_iters=self.power_iters,
                ess_faces=self._ess_faces,
                traction_face=self._traction_face,
                maxiter=self.maxiter,
                pallas_lane=self.pallas_lane,
                mesh=self.mesh,
            )
        return self._f64_twin

    def solve(
        self,
        materials: list[dict],
        tractions,
        rel_tol,
    ) -> BPCGResult:
        """Solve S scenarios in one compiled program.

        materials: length-S list; each entry an attribute->(lambda, mu)
                   dict or a (lam_e, mu_e) per-element array pair of
                   shape (nelem_fine,) — the forms mix freely (see
                   :meth:`pack_materials`)
        tractions: (S, 3) traction vectors on the traction face
        rel_tol:   scalar or (S,) per-scenario relative tolerances

        Sharded solvers pad S up to a multiple of the device count with
        born-converged rows (see :meth:`pad_scenarios`) and slice them
        off the result: callers see exactly the S rows they asked for.

        Reduced-precision policies carry the f64 safety net: rows the
        stagnation detector flagged (their requested tolerance sits
        below the reduced arithmetic's residual floor) are re-solved on
        the lazily built f64 twin and merged back — ``fallback`` marks
        them, ``iterations`` counts the total work (reduced + f64
        passes), and the merged result is promoted to f64 (only
        observable for the uniform ``f32`` policy; mixed policies
        already solve in f64).  Where ``f64`` cannot run
        (``can_fall_back`` is False) flagged rows stay unconverged, with
        ``stalled`` set."""
        materials, tractions, rel_tol, s = self.pad_scenarios(
            materials, tractions, rel_tol
        )
        lam_vals, mu_vals = self.pack_materials(materials)
        tr = jnp.asarray(tractions, self.dtype)
        rel = jnp.asarray(rel_tol, self.dtype)
        lam_vals, mu_vals, tr, rel = self._put(
            (lam_vals, mu_vals, tr, rel)
        )
        res = self._jit_solve(lam_vals, mu_vals, tr, rel)
        if len(materials) > s:
            res = BPCGResult(
                **{
                    fld.name: getattr(res, fld.name)[:s]
                    for fld in dataclasses.fields(BPCGResult)
                }
            )
        if self.precision.reduced and self.can_fall_back:
            need = np.asarray(res.stalled) & ~np.asarray(res.converged)
            if need.any():
                rows = np.nonzero(need)[0]
                twin = self._f64_fallback_solver()
                sub = twin.solve(
                    [materials[int(i)] for i in rows],
                    np.asarray(tractions, dtype=np.float64)[rows],
                    np.asarray(rel_tol, dtype=np.float64)[rows],
                )
                res = _merge_fallback_rows(res, sub, rows)
        return res
