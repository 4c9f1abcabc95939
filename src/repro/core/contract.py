"""Sum-factorized 1D tensor contractions (paper Sec. 4.4 / 4.5).

The forward sweep evaluates reference-space gradients at quadrature points
through three sequential 1D contractions (X, then Y, then Z); the backward
sweep is its exact transpose.  All functions take arrays whose trailing
axes are the tensor-product axes ``(..., iz, iy, ix)`` so the same code
serves whole-mesh (C1/C2 ablation stages), per-element fused (vmap /
Pallas reference) and batched-element (Pallas kernel block) callers.

Index conventions match the paper: ``B[q, i] = phi_i(xi_q)``,
``G[q, i] = phi_i'(xi_q)``; D1D dof points, Q1D quadrature points.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "forward_grad", "backward_grad_t", "interp3d", "interp3d_t", "einsum"
]


def einsum(spec, *operands):
    """``jnp.einsum`` at full precision.  On a TPU an f32 contraction
    otherwise runs one bf16 MXU pass (~3 significant digits), which
    leaves an f32 operator too inexact for the Krylov iteration to reach
    its tolerances; elsewhere this is plain einsum."""
    return jnp.einsum(spec, *operands, precision=jax.lax.Precision.HIGHEST)


def forward_grad(x, B, G):
    """Reference gradient at quadrature points.

    x: (..., D1D, D1D, D1D) laid out (iz, iy, ix).
    Returns (..., 3, Q1D, Q1D, Q1D) with axis -4 the reference direction
    (d_xi, d_eta, d_zeta) and trailing axes (qz, qy, qx).
    """
    # X contraction: two channels (sm0[0/1] of the paper).
    u = einsum("...zyx,qx->...zyq", x, B)
    v = einsum("...zyx,qx->...zyq", x, G)
    # Y contraction: three channels (sm1[0/1/2]).
    d_xi = einsum("...zyq,ry->...zrq", v, B)
    d_eta = einsum("...zyq,ry->...zrq", u, G)
    u_xy = einsum("...zyq,ry->...zrq", u, B)
    # Z contraction.
    g_xi = einsum("...zrq,sz->...srq", d_xi, B)
    g_eta = einsum("...zrq,sz->...srq", d_eta, B)
    g_zeta = einsum("...zrq,sz->...srq", u_xy, G)
    return jnp.stack([g_xi, g_eta, g_zeta], axis=-4)


def backward_grad_t(q, B, G):
    """Transpose of :func:`forward_grad` (the test-function contraction).

    q: (..., 3, Q1D, Q1D, Q1D) — rows of the weighted stress pulled back to
    reference directions.  Returns (..., D1D, D1D, D1D): the divergence-type
    contraction sum_m d_m(.) applied slice-wise (G along direction m, B along
    the other two), summed over the three m-channels.
    """

    def sweep(t, tx, ty, tz):
        t = einsum("...srq,sz->...zrq", t, tz)  # Z: tmpZ
        t = einsum("...zrq,ry->...zyq", t, ty)  # Y: tmpY
        return einsum("...zyq,qx->...zyx", t, tx)  # X + accumulate

    return (
        sweep(q[..., 0, :, :, :], G, B, B)
        + sweep(q[..., 1, :, :, :], B, G, B)
        + sweep(q[..., 2, :, :, :], B, B, G)
    )


def interp3d(x, B):
    """Pure interpolation to quadrature points (used by mass-type terms)."""
    x = einsum("...zyx,qx->...zyq", x, B)
    x = einsum("...zyq,ry->...zrq", x, B)
    return einsum("...zrq,sz->...srq", x, B)


def interp3d_t(x, B):
    """Transpose of :func:`interp3d`."""
    x = einsum("...srq,sz->...zrq", x, B)
    x = einsum("...zrq,ry->...zyq", x, B)
    return einsum("...zyq,qx->...zyx", x, B)
