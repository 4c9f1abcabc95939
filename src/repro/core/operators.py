"""ElasticityOperator: the paper's contribution as a composable module.

One operator object per (mesh, degree) pair exposes every assembly level
of the ablation (Table 7) behind a single interface consumed by the
solvers:

    assembly in {"fa", "pa_baseline", "pa_sumfact", "pa_sumfact_voigt",
                 "paop", "paop_pallas"}

``apply(x)`` acts on the unconstrained L-vector (nscalar, 3);
``constrained()`` wraps it with MFEM ConstrainedOperator semantics and
the matrix-free diagonal for the Chebyshev-Jacobi smoother.

Scenario batching: ``materials`` may also be a *sequence* of scenario
entries — attribute->(lambda, mu) dicts and/or per-element
``(lam_e, mu_e)`` pairs, mixed freely — or a raw coefficient-array pair
of shape (nelem,) or (S, nelem).  With a
leading scenario axis the operator acts on (S, nscalar, 3) L-vectors;
internally the scenario axis is folded into the element axis so every
PA kernel — including the Pallas one — runs unchanged on a grid S times
larger.  ``with_materials`` rebinds the (traceable) material fields
without redoing any geometry, which is what lets a jitted batched solve
take materials as runtime arguments.

Multi-device scenarios: with ``shard_mesh`` set (a 1-D jax.sharding
mesh over the scenario axis), the batched apply/diagonal paths pin both
the (S, nscalar, 3) L-vectors and the folded (S*nelem, ...) E-vectors
to axis-0 sharding via with_sharding_constraint.  Because S divides the
mesh, each shard holds whole scenarios and the element-local kernels
run unchanged per device with zero cross-device traffic (the L-vector
gather/scatter indices are per-scenario too).
"""

from __future__ import annotations

import copy
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import diagonal as _diag
from repro.core import fa as _fa
from repro.core import pa_baseline as _base
from repro.core import pa_sumfact as _sf
from repro.core import paop as _paop
from repro.core.basis import basis_tables
from repro.kernels.pa_elasticity.ops import check_compiled_dtype, resolve_lane
from repro.core.geometry import (
    MATERIALS_BEAM,
    make_quadrature_data,
    material_fields,
    quadrature_geometry,
)
from repro.distributed.sharding import pin_scenario
from repro.fem.bc import ConstrainedOperator
from repro.fem.space import H1Space

__all__ = [
    "ElasticityOperator", "ASSEMBLY_LEVELS", "DEFER_MATERIALS", "PA_APPLY"
]

# ``jax.named_scope`` name of every operator apply; it nests under the
# scope of the caller (the outer Krylov, a smoother, a residual, ...).
PA_APPLY = "pa.apply"

# Sentinel: build the operator as a geometry/tables carrier only; material
# fields are bound later via with_materials (e.g. inside a jitted batched
# solve).  Skips allocating placeholder (nelem, Q^3) quadrature buffers.
DEFER_MATERIALS = "defer"

ASSEMBLY_LEVELS = (
    "fa",
    "pa_baseline",
    "pa_sumfact",
    "pa_sumfact_voigt",
    "paop",
    "paop_pallas",
)


class ElasticityOperator:
    def __init__(
        self,
        space: H1Space,
        assembly: str = "paop",
        materials: dict[int, tuple[float, float]] | None = None,
        dtype=jnp.float64,
        ess_faces=("x0",),
        pallas_interpret: bool | None = None,
        pallas_lane: str | None = None,
        shard_mesh=None,
    ):
        if assembly not in ASSEMBLY_LEVELS:
            raise ValueError(f"unknown assembly level {assembly!r}")
        if shard_mesh is not None and assembly == "fa":
            raise ValueError("shard_mesh is matrix-free only (not 'fa')")
        self.space = space
        self.assembly = assembly
        self.dtype = dtype
        self.tables = space.tables
        # Resolved at construction, so this attribute is the report of
        # which Pallas lane runs ("compiled" or "interpret"): an explicit
        # pallas_lane wins, the legacy pallas_interpret bool is honored
        # (True pins the interpreter), and the default "auto" follows the
        # backend.  Only consulted by assembly="paop_pallas", which the
        # compiled lane runs in float32 only.
        self.pallas_lane = resolve_lane(pallas_lane, interpret=pallas_interpret)
        if assembly == "paop_pallas":
            check_compiled_dtype(dtype, self.pallas_lane)
        self.shard_mesh = shard_mesh

        geom = quadrature_geometry(space.mesh, self.tables)
        self.w_detj = jnp.asarray(geom.w_detj, dtype=dtype)  # (Q,Q,Q)
        self.jinv = jnp.asarray(geom.jinv, dtype=dtype)
        self.detj = geom.detj
        self.B = jnp.asarray(self.tables.B, dtype=dtype)
        self.G = jnp.asarray(self.tables.G, dtype=dtype)
        self.ess_mask = space.essential_mask(ess_faces)

        if isinstance(materials, str) and materials == DEFER_MATERIALS:
            if assembly == "fa":
                raise ValueError("assembly='fa' cannot defer materials")
            self.materials = None
            self.nbatch = None
            self.lam_w = self.mu_w = None
        else:
            self.materials = (
                materials if materials is not None else MATERIALS_BEAM
            )
            lam_e, mu_e = self._normalize_materials(self.materials)
            self._bind_materials(lam_e, mu_e)

        self._sparse: _fa.SparseMatrix | None = None
        if assembly == "fa":
            if self.nbatch is not None or not isinstance(self.materials, dict):
                raise ValueError(
                    "assembly='fa' supports only a single attribute->"
                    "(lambda, mu) dict; use a matrix-free level for "
                    "scenario-batched or per-element materials"
                )
            qd = make_quadrature_data(
                space.mesh, self.tables, self.materials
            )  # setup in float64 regardless of operator dtype
            self._sparse = _fa.assemble_sparse(
                space, qd, self.materials, ess_mask=None, dtype=dtype
            )

    # -- materials -----------------------------------------------------------
    @staticmethod
    def _is_field_pair(m) -> bool:
        """A (lam_e, mu_e) scenario entry: two 1-D array-likes."""
        return (
            isinstance(m, (tuple, list))
            and len(m) == 2
            and np.ndim(m[0]) == 1
            and np.ndim(m[1]) == 1
        )

    def _normalize_materials(self, materials):
        """Normalize to per-element coefficient fields (lam_e, mu_e) of
        shape (nelem,) or (S, nelem).

        Accepted forms: one attribute->(lambda, mu) dict; one
        (lam_e, mu_e) pair of (nelem,) arrays; a scenario *sequence*
        whose entries are dicts and/or such pairs, mixed freely (each
        entry one scenario row); or a raw pre-stacked (S, nelem) pair.
        A sequence of pairs is recognized per entry — it is never
        mis-read as one stacked pair."""
        mesh = self.space.mesh
        if isinstance(materials, dict):
            return material_fields(mesh, materials)
        if (
            isinstance(materials, (list, tuple))
            and len(materials) == 2
            and all(self._is_field_pair(m) for m in materials)
        ):
            # Genuinely ambiguous: ([a, b], [c, d]) with 1-D rows reads
            # both as a raw stacked (2, nelem) pair and as two
            # (lam_e, mu_e) scenario entries — and the two readings
            # cross lambda/mu differently.  Refuse loudly instead of
            # guessing wrong physics.
            raise ValueError(
                "ambiguous materials: a length-2 sequence of 1-D array "
                "pairs reads both as one stacked (2, nelem) (lam, mu) "
                "pair and as two per-scenario (lam_e, mu_e) pairs; pass "
                "numpy arrays of shape (2, nelem) for the stacked form, "
                "or include a dict entry / use another batch size for "
                "the scenario-sequence form"
            )
        if (
            isinstance(materials, (list, tuple))
            and materials
            and not self._is_field_pair(materials)
            and all(
                isinstance(m, dict) or self._is_field_pair(m)
                for m in materials
            )
        ):
            fields = [
                material_fields(mesh, m)
                if isinstance(m, dict)
                else (np.asarray(m[0]), np.asarray(m[1]))
                for m in materials
            ]
            return (
                np.stack([f[0] for f in fields]),
                np.stack([f[1] for f in fields]),
            )
        try:
            lam_e, mu_e = materials
        except (TypeError, ValueError):
            raise TypeError(
                "materials must be a dict, a (lam_e, mu_e) array pair, "
                "or a sequence of dicts / pairs (one per scenario); "
                f"got {type(materials)!r}"
            ) from None
        return lam_e, mu_e

    def _bind_materials(self, lam_e, mu_e):
        """Set lam_w/mu_w from coefficient fields (traceable: fields may be
        jax tracers inside a jitted batched solve)."""
        lam_e = jnp.asarray(lam_e, dtype=self.dtype)
        mu_e = jnp.asarray(mu_e, dtype=self.dtype)
        if lam_e.shape != mu_e.shape or lam_e.shape[-1] != self.space.nelem:
            raise ValueError(
                f"material fields {lam_e.shape}/{mu_e.shape} do not match "
                f"nelem={self.space.nelem}"
            )
        if lam_e.ndim == 2:  # (S, nelem): fold scenarios into elements
            self.nbatch = lam_e.shape[0]
            lam_e = lam_e.reshape(-1)
            mu_e = mu_e.reshape(-1)
        elif lam_e.ndim == 1:
            self.nbatch = None
        else:
            raise ValueError(f"material fields must be 1D or 2D: {lam_e.shape}")
        self.lam_w = lam_e[:, None, None, None] * self.w_detj
        self.mu_w = mu_e[:, None, None, None] * self.w_detj

    def with_materials(self, lam_e, mu_e) -> "ElasticityOperator":
        """A shallow copy with new material coefficient fields ((nelem,) or
        (S, nelem)); geometry, tables and masks are shared.  Safe to call
        under jit with traced fields (matrix-free levels only)."""
        if self.assembly == "fa":
            raise ValueError("with_materials is matrix-free only (not 'fa')")
        new = copy.copy(self)
        new.materials = None
        new._bind_materials(lam_e, mu_e)
        return new

    def with_material_weights(
        self, lam_w, mu_w, nbatch: int | None
    ) -> "ElasticityOperator":
        """A shallow copy binding precomputed weighted fields
        (``lam_e * w_detj``) directly, skipping the quadrature-weight
        multiply.  The resumable batched solve keeps these per-scenario
        fields alive across chunk boundaries (in its prep pytree) and
        rebinds them on every chunk; for a scenario batch ``lam_w`` is
        the folded ``(S * nelem, Q, Q, Q)`` array and ``nbatch`` is S."""
        if self.assembly == "fa":
            raise ValueError("with_material_weights is matrix-free only")
        new = copy.copy(self)
        new.materials = None
        new.nbatch = nbatch
        new.lam_w = lam_w
        new.mu_w = mu_w
        return new

    def with_materials_rows(self, lam_e, mu_e, row_mask) -> "ElasticityOperator":
        """In-place per-scenario-row field update (functional): rows of the
        batched material fields selected by ``row_mask`` (S,) take freshly
        weighted fields from the ``(S, nelem)`` candidates; unselected rows
        keep this operator's current fields *bitwise* — refilling a batch
        slot must not perturb the scenarios still in flight.  Traceable."""
        if self.assembly == "fa":
            raise ValueError("with_materials_rows is matrix-free only")
        if self.nbatch is None:
            raise ValueError(
                "with_materials_rows requires a scenario-batched operator"
            )
        s, ne = self.nbatch, self.space.nelem
        lam_e = jnp.asarray(lam_e, dtype=self.dtype)
        mu_e = jnp.asarray(mu_e, dtype=self.dtype)
        if lam_e.shape != (s, ne) or mu_e.shape != (s, ne):
            raise ValueError(
                f"candidate fields {lam_e.shape}/{mu_e.shape} must be "
                f"({s}, {ne})"
            )
        mask = jnp.asarray(row_mask).reshape((s,) + (1,) * 4)

        def merge(old_w, cand_e):
            cand_w = cand_e.reshape(-1)[:, None, None, None] * self.w_detj
            tail = old_w.shape[1:]
            return jnp.where(
                mask,
                cand_w.reshape((s, ne) + tail),
                old_w.reshape((s, ne) + tail),
            ).reshape((s * ne,) + tail)

        new = copy.copy(self)
        new.materials = None
        new.lam_w = merge(self.lam_w, lam_e)
        new.mu_w = merge(self.mu_w, mu_e)
        return new

    # -- raw action ---------------------------------------------------------
    def _apply_evec(self, x_e):
        if self.lam_w is None:
            raise ValueError(
                "materials are deferred; bind them with with_materials first"
            )
        a = self.assembly
        if a == "pa_baseline":
            g3d = _base.dense_grad_table(self.space.p, dtype=self.dtype)
            return _base.pa_baseline_apply(x_e, self.lam_w, self.mu_w, self.jinv, g3d)
        if a == "pa_sumfact":
            return _sf.pa_sumfact_apply(
                x_e, self.lam_w, self.mu_w, self.jinv, self.B, self.G
            )
        if a == "pa_sumfact_voigt":
            return _sf.pa_sumfact_voigt_apply(
                x_e, self.lam_w, self.mu_w, self.jinv, self.B, self.G
            )
        if a == "paop":
            return _paop.paop_apply(
                x_e, self.lam_w, self.mu_w, self.jinv, self.B, self.G
            )
        if a == "paop_pallas":
            from repro.kernels.pa_elasticity import ops as _kops

            return _kops.pa_elasticity(
                x_e,
                self.lam_w,
                self.mu_w,
                self.jinv,
                self.B,
                self.G,
                lane=self.pallas_lane,
            )
        raise AssertionError(a)

    def apply(self, x):
        """Unconstrained y = A x on the L-vector (nscalar, 3), or the
        scenario batch (S, nscalar, 3) for a batched operator."""
        with jax.named_scope(PA_APPLY):
            if self.assembly == "fa":
                y = self._sparse.matvec(x.reshape(-1))
                return y.reshape(x.shape)
            if self.nbatch is not None:
                s, ne = self.nbatch, self.space.nelem
                x = pin_scenario(x, self.shard_mesh)
                # (S, ne, 3, D, D, D)
                x_e = jax.vmap(self.space.to_evec)(x)
                # Pin the folded (S*ne, ...) E-vector: each shard holds
                # whole scenarios, so the fused PA/Pallas kernel below is
                # purely shard-local.
                x_e = pin_scenario(
                    x_e.reshape((s * ne,) + x_e.shape[2:]), self.shard_mesh
                )
                y_e = self._apply_evec(x_e)
                y_e = pin_scenario(y_e, self.shard_mesh)
                y_e = y_e.reshape((s, ne) + y_e.shape[1:])
                return pin_scenario(
                    jax.vmap(self.space.scatter_add)(y_e), self.shard_mesh
                )
            x_e = self.space.to_evec(x)
            y_e = self._apply_evec(x_e)
            return self.space.scatter_add(y_e)

    def __call__(self, x):
        return self.apply(x)

    # -- diagonal -------------------------------------------------------------
    def diagonal(self):
        """Assembled operator diagonal as an L-vector (nscalar, 3), with a
        leading scenario axis for a batched operator."""
        if self.assembly == "fa":
            d = jnp.asarray(self._sparse.csr.diagonal(), dtype=self.dtype)
            return d.reshape(-1, 3)
        if self.lam_w is None:
            raise ValueError(
                "materials are deferred; bind them with with_materials first"
            )
        d_e = _diag.element_diagonal(self.lam_w, self.mu_w, self.jinv, self.B, self.G)
        if self.nbatch is not None:
            s, ne = self.nbatch, self.space.nelem
            d_e = pin_scenario(d_e, self.shard_mesh)
            d_e = d_e.reshape((s, ne) + d_e.shape[1:])
            return pin_scenario(
                jax.vmap(self.space.scatter_add)(d_e), self.shard_mesh
            )
        return self.space.scatter_add(d_e)

    # -- constrained view -------------------------------------------------------
    def constrained(self) -> ConstrainedOperator:
        return ConstrainedOperator(self.apply, self.ess_mask, self.diagonal)

    # -- introspection ------------------------------------------------------------
    def memory_bytes(self) -> int:
        """Stored-operator footprint: quadrature data D for PA levels, CSR
        for FA (paper Fig. 4 peak-memory comparison)."""
        if self.assembly == "fa":
            return self._sparse.memory_bytes()
        itemsize = jnp.dtype(self.dtype).itemsize
        return int(self.lam_w.size + self.mu_w.size + self.jinv.size) * itemsize
