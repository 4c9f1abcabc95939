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

# ``jax.named_scope`` of a float64 contraction computed as multiply-adds.
CONTRACT_F64 = "pa.contract_f64"


def einsum(spec, *operands):
    """``jnp.einsum`` at full precision.  On a TPU an f32 contraction
    otherwise runs one bf16 MXU pass (~3 significant digits), which
    leaves an f32 operator too inexact for the Krylov iteration to reach
    its tolerances; elsewhere this is plain einsum.

    A two-operand float64 contraction is computed as an elementwise
    product summed over the contracted axis instead, in float64.  A TPU
    has no float64 units and XLA emulates an f64 ``dot_general`` by
    splitting and recombining its operands: on a v5e the f64 operator
    apply ran 21-33 times longer as dots than as emulated f64
    multiply-adds.  On a CPU the multiply-adds are faster as well."""
    if len(operands) == 2 and all(
        jnp.result_type(o) == jnp.float64 for o in operands
    ):
        with jax.named_scope(CONTRACT_F64):
            return _multiply_add(spec, *operands)
    return jnp.einsum(spec, *operands, precision=jax.lax.Precision.HIGHEST)


def _multiply_add(spec, a, b):
    """``jnp.einsum(spec, a, b)`` as a broadcast product summed over the
    one index ``spec`` contracts.  As in every caller, ``...`` (if
    present) leads the first operand and the output alone."""
    ins, out = spec.replace("...", "").split("->")
    ta, tb = ins.split(",")
    (k,) = (set(ta) & set(tb)) - set(out)
    axes = out + k  # the product's trailing axes, contracted one last

    def placed(o, t):
        lead = o.ndim - len(t)
        o = jnp.transpose(o, [*range(lead)] + [
            lead + t.index(c) for c in axes if c in t])
        return jnp.expand_dims(
            o, [lead + i for i, c in enumerate(axes) if c not in t])

    return jnp.sum(placed(a, ta) * placed(b, tb), axis=-1)


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
