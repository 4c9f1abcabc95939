"""Chebyshev-accelerated Jacobi smoother (MFEM OperatorChebyshevSmoother
analog; paper Sec. 3.1).

Requires only the operator action and its diagonal.  lambda_max of
D^{-1} A is estimated with a fixed number of power iterations (paper: 10)
at setup; the polynomial acts on the interval
[eig_lo_frac * hi, eig_hi_frac * lambda_max] (0.3 / 1.1 — the customary
matrix-free multigrid choice).  Degree k = 2 by default, one pre- and one
post-smoothing per V(1,1) cycle.

Scenario batching: with ``batch_dims=1`` the operator, diagonal and
vectors carry a leading scenario axis (S, ...) and lambda_max is
estimated per scenario; the Chebyshev recurrence coefficients become
(S,)-shaped and broadcast over each scenario's vector block, so one
smoother application advances every scenario in lockstep.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.distributed.sharding import pin_scenario

__all__ = ["ChebyshevSmoother", "power_iteration_lmax", "PREP_POWER"]

# ``jax.named_scope`` name of the lambda_max power iterations, which run
# in the prep program (``repro.solvers.batched.PREP``).
PREP_POWER = "prep.power"


def _expand(a, ndim: int):
    """Right-pad ``a`` with singleton axes so it broadcasts against an
    ndim-dimensional vector block ((S,) coefficients vs (S, n, 3))."""
    a = jnp.asarray(a)
    return a.reshape(a.shape + (1,) * (ndim - a.ndim))


def power_iteration_lmax(
    A: Callable, dinv, shape, dtype, iters: int = 10, batch_dims: int = 0,
    shard_mesh=None,
):
    """Estimate lambda_max(D^{-1} A) with deterministic power iterations.

    With ``batch_dims=1`` the leading axis of ``shape`` is a scenario
    batch: normalization and the Rayleigh quotient are taken per scenario
    and the estimate has shape ``shape[:batch_dims]``.  The start vector
    is drawn at the per-scenario shape and broadcast, so each batched row
    runs exactly the iteration its scalar counterpart would.

    ``shard_mesh`` (a scenario-axis device mesh) pins the broadcast start
    vector to axis-0 sharding, keeping the whole iteration shard-local:
    the per-row norms/Rayleigh quotients reduce within a shard, so the
    estimate is bitwise the single-device one.
    """
    key = jax.random.PRNGKey(1234)
    v = jax.random.normal(key, shape[batch_dims:], dtype=dtype)
    v = jnp.broadcast_to(v, shape)
    if batch_dims:  # axis 0 is the scenario batch
        v = pin_scenario(v, shard_mesh)
    axes = tuple(range(batch_dims, v.ndim))

    def body(_, carry):
        v, lam = carry
        nrm = jnp.sqrt(jnp.sum(v * v, axis=axes))
        v = v / _expand(nrm, v.ndim)
        w = dinv * A(v)
        lam = jnp.sum(v * w, axis=axes)
        return (w, lam)

    lam0 = jnp.zeros(shape[:batch_dims], dtype)
    with jax.named_scope(PREP_POWER):
        v, lam = jax.lax.fori_loop(0, iters, body, (v, lam0))
        return jnp.abs(lam)


@dataclasses.dataclass
class ChebyshevSmoother:
    """x <- x + p_k(D^{-1} A) D^{-1} (b - A x), Chebyshev on [lo, hi].

    ``lmax`` is a scalar for a single scenario or (S,) for a scenario
    batch (matching a (S, n, 3) vector block).
    """

    A: Callable
    dinv: Any
    lmax: Any
    degree: int = 2
    eig_lo_frac: float = 0.3
    eig_hi_frac: float = 1.1

    @classmethod
    def setup(cls, A, diagonal, shape, dtype, degree=2, power_iters=10,
              batch_dims=0, shard_mesh=None):
        # Essential-BC rows carry an identity diagonal by construction
        # (ConstrainedOperator.diagonal), but a zero slipping through —
        # e.g. a degenerate padded row — must not poison dinv with inf.
        diagonal = jnp.asarray(diagonal)
        safe = jnp.where(diagonal == 0, jnp.ones_like(diagonal), diagonal)
        dinv = 1.0 / safe
        lmax = power_iteration_lmax(
            A, dinv, shape, dtype, power_iters, batch_dims=batch_dims,
            shard_mesh=shard_mesh,
        )
        return cls(A=A, dinv=dinv, lmax=lmax, degree=degree)

    def __call__(self, b, x=None):
        """Apply ``degree`` Chebyshev-Jacobi steps to A x = b."""
        # Coefficients live in the vector-block dtype, not lmax's: an
        # f32 lmax estimated at setup against f64 blocks (or the mixed
        # policy's f64 lmax against f32 blocks) must neither demote the
        # recurrence nor silently promote every d/z update.
        hi = self.eig_hi_frac * jnp.asarray(self.lmax, dtype=b.dtype)
        lo = self.eig_lo_frac * hi
        theta = 0.5 * (hi + lo)
        delta = 0.5 * (hi - lo)
        sigma = theta / delta

        if x is None:
            x = jnp.zeros_like(b)
            r = b
        else:
            r = b - self.A(x)
        z = self.dinv * r
        d = z / _expand(theta, b.ndim)
        rho = 1.0 / sigma
        for _ in range(self.degree):
            x = x + d
            r = r - self.A(d)
            z = self.dinv * r
            rho_new = 1.0 / (2.0 * sigma - rho)
            d = _expand(rho_new * rho, b.ndim) * d + (
                2.0 * _expand(rho_new, b.ndim) / _expand(delta, b.ndim)
            ) * z
            rho = rho_new
        return x
