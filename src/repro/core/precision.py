"""Precision policies for the GMG-PCG stack (mixed-precision axis).

The PAop operator is bandwidth-bound across the whole p = 1..8 sweep
(every committed ``BENCH_operator_sweep.json`` row lands on the memory
side of the roofline), so halving bytes-per-apply is the biggest
remaining kernel-time lever — the direction "Towards a Higher Roofline
for Matrix-Vector Multiplication in Matrix-Free HOSFEM" takes.  A
:class:`PrecisionPolicy` names which dtype each tier of the solve runs
in:

* ``solve_dtype`` — the outer Krylov iteration: the ``BpcgState``
  vectors (x, r, z, d), the operator apply inside the CG recurrence,
  and — critically — the residual norms and tolerance thresholds.
  Keeping this at f64 is what makes the ``mixed`` policy safe: the
  stopping test is always evaluated in f64 arithmetic against the
  caller's tolerance, regardless of how sloppy the preconditioner is.
* ``precond_dtype`` — everything inside the GMG V-cycle: the per-level
  weighted material fields (the bytes the element kernel actually
  streams), the Chebyshev smoother (dinv, lambda_max, recurrence),
  and the inter-grid transfers.  A preconditioner is only required to
  be a fixed SPD operator — reduced precision here perturbs the
  convergence *rate*, never the answer the outer loop accepts.
* ``coarse_dtype`` — the coarsest-level probe + dense Cholesky factor
  and the per-chunk triangular solves.  Kept separate because bf16
  has too few mantissa bits to factor even well-conditioned coarse
  blocks (``mixed-bf16`` holds the coarse solve at f32).

Built-in policies (see :data:`PRECISION_POLICIES`):

==============  ===========  =============  ============
name            solve_dtype  precond_dtype  coarse_dtype
==============  ===========  =============  ============
``f64``         float64      float64        float64
``f32``         float32      float32        float32
``mixed``       float64      float32        float32
``mixed-bf16``  float64      bfloat16       float32
==============  ===========  =============  ============

The policy rides the prep pytree implicitly: a
:class:`~repro.solvers.batched.BatchedGMGSolver` resolves its policy at
construction and every prep leaf it produces carries the corresponding
dtype (the reduced policies additionally carry a ``solve_dtype`` copy
of the *fine-level* weighted fields, because the outer Krylov streams
the fine operator at full precision while the smoother streams it
reduced).  ``policy.name`` participates in the service compile-cache
key and the prep-reuse content digest, is recorded in every BENCH row
(``precision_policy``) and labels the service metrics.

Safety story: reduced-precision cycles can stagnate when the requested
tolerance sits below the reduced dtype's attainable residual floor.
The batched solver detects this per scenario (masked, exactly like
per-scenario convergence) and the solve/serving layers re-solve only
the affected rows under the ``f64`` policy — see
:func:`repro.solvers.batched.bpcg_chunk` (stall counters) and
``docs/PRECISION.md`` for the contract.  Where the ``f64`` policy cannot
run (see :func:`policy_refusal`) there is no re-solve: a stalled row is
reported unconverged and ``stalled``.

On a TPU (:func:`emulates_f64`) the default policy is ``mixed`` and the
``f64`` policy is refused: the chip has no float64 units, XLA emulates
every f64 op, and the f64 V-cycle's step program for the paper's p=8
beam did not finish compiling for a v5e in 23 minutes.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

__all__ = [
    "PrecisionPolicy",
    "PRECISION_POLICIES",
    "resolve_precision",
    "default_policy_name",
    "emulates_f64",
    "policy_refusal",
    "check_policy",
]


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """Dtype assignment for the tiers of one GMG-PCG solve (static
    metadata — hashable, usable in compile-cache keys)."""

    name: str
    solve_dtype: Any  # outer Krylov vectors + residual/tolerance accounting
    precond_dtype: Any  # smoother, transfers, element kernel in the V-cycle
    coarse_dtype: Any  # coarse probe + Cholesky factor/solve

    @property
    def uniform(self) -> bool:
        """True when every tier runs one dtype (no cast boundaries)."""
        return (
            self.solve_dtype == self.precond_dtype
            and self.solve_dtype == self.coarse_dtype
        )

    @property
    def reduced(self) -> bool:
        """True when any tier runs below float64 — exactly the policies
        covered by the stagnation-detection + f64-fallback contract."""
        return not (
            self.solve_dtype == jnp.float64
            and self.precond_dtype == jnp.float64
            and self.coarse_dtype == jnp.float64
        )


PRECISION_POLICIES: dict[str, PrecisionPolicy] = {
    "f64": PrecisionPolicy("f64", jnp.float64, jnp.float64, jnp.float64),
    "f32": PrecisionPolicy("f32", jnp.float32, jnp.float32, jnp.float32),
    "mixed": PrecisionPolicy("mixed", jnp.float64, jnp.float32, jnp.float32),
    "mixed-bf16": PrecisionPolicy(
        "mixed-bf16", jnp.float64, jnp.bfloat16, jnp.float32
    ),
}


def resolve_precision(
    precision: str | PrecisionPolicy | None, dtype=None
) -> PrecisionPolicy:
    """Resolve a precision request to a :class:`PrecisionPolicy`.

    ``precision`` is a policy name (``"f64"``, ``"f32"``, ``"mixed"``,
    ``"mixed-bf16"``), an explicit policy object, or None — meaning
    "derive from the legacy ``dtype`` argument": no dtype resolves to
    the backend's default (:func:`default_policy_name`), f64 to the
    ``f64`` policy, f32 to ``f32``, and any other uniform dtype to an
    ad-hoc uniform policy named after it.  Passing
    both a policy and a conflicting ``dtype`` is an error — the policy
    is the single source of dtype truth."""
    if isinstance(precision, PrecisionPolicy):
        pol = precision
    elif precision is None:
        if dtype is None:
            return PRECISION_POLICIES[default_policy_name()]
        if jnp.dtype(dtype) == jnp.dtype(jnp.float64):
            return PRECISION_POLICIES["f64"]
        for pol in PRECISION_POLICIES.values():
            if pol.uniform and jnp.dtype(pol.solve_dtype) == jnp.dtype(dtype):
                return pol
        return PrecisionPolicy(str(jnp.dtype(dtype)), dtype, dtype, dtype)
    else:
        try:
            pol = PRECISION_POLICIES[precision]
        except KeyError:
            raise ValueError(
                f"unknown precision policy {precision!r}; expected one "
                f"of {tuple(PRECISION_POLICIES)} or a PrecisionPolicy"
            ) from None
    if dtype is not None and jnp.dtype(dtype) != jnp.dtype(pol.solve_dtype):
        raise ValueError(
            f"precision policy {pol.name!r} solves in "
            f"{jnp.dtype(pol.solve_dtype)} but dtype="
            f"{jnp.dtype(dtype)} was also requested; pass one or the "
            f"other"
        )
    return pol


def emulates_f64(backend: str | None = None) -> bool:
    """True when ``backend`` (default: JAX's default backend) has no
    float64 arithmetic and XLA emulates it — a TPU."""
    b = backend if backend is not None else jax.default_backend()
    return b == "tpu"


def default_policy_name() -> str:
    """The policy a solve runs when none is named: ``f64`` where the
    backend computes in float64, ``mixed`` (f64 Krylov and stopping
    test over an f32 V-cycle) where it emulates it."""
    return "mixed" if emulates_f64() else "f64"


def policy_refusal(
    policy: PrecisionPolicy, assembly: str = "paop", lane: str = "interpret"
) -> str | None:
    """Why ``policy`` cannot run with ``assembly`` on Pallas ``lane``
    and JAX's default backend, or None when it can.  Solvers and the
    service refuse such a policy at construction and intake (see
    :func:`check_policy`), and never fall back onto one."""
    if emulates_f64() and jnp.dtype(policy.precond_dtype) == jnp.float64:
        return (
            f"precision policy {policy.name!r} runs its GMG V-cycle in "
            f"float64, which a TPU only emulates: the f64 step program "
            f"for the p=8 beam does not compile in a usable time.  Use "
            f"precision='mixed' (f64 Krylov and stopping test over an "
            f"f32 V-cycle; the default on a TPU) or 'f32'"
        )
    if assembly == "paop_pallas":
        from repro.kernels.pa_elasticity.ops import compiled_dtype_refusal

        for dt in (policy.solve_dtype, policy.precond_dtype):
            msg = compiled_dtype_refusal(dt, lane)
            if msg is not None:
                return msg
    return None


def check_policy(
    policy: PrecisionPolicy, assembly: str = "paop", lane: str = "interpret"
) -> None:
    """Raise ValueError with :func:`policy_refusal`'s reason, if any."""
    msg = policy_refusal(policy, assembly, lane)
    if msg is not None:
        raise ValueError(msg)
