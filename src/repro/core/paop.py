"""PAop: the fully fused, sum-factorized, Voigt-form element kernel
(paper Sec. 4.2-4.5), expressed element-locally.

``paop_element`` is the single-element fused dataflow — interpolate the
gradient, evaluate the six-component weighted Voigt stress pointwise,
pull the rows back to reference directions, and apply the transpose
contractions — with no whole-mesh intermediate anywhere.  ``paop_apply``
vmaps it over elements; under jit the per-element chain is what XLA sees
as one producer-consumer region (macro-kernel fusion).  The Pallas TPU
kernel (repro.kernels.pa_elasticity) implements the same dataflow with
explicit VMEM tiling; this function is its numerical oracle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.contract import backward_grad_t, einsum, forward_grad
from repro.core.voigt import VOIGT_INDEX, stress_voigt

__all__ = ["paop_element", "paop_apply", "paop_apply_scenarios"]


def paop_element(x_e, lam_w, mu_w, jinv, B, G):
    """Fused PAop action for one element.

    x_e:   (3, D1D, D1D, D1D)     element displacement (c, iz, iy, ix)
    lam_w: (Q1D, Q1D, Q1D)        w det(J) lambda at qpoints (mu_w likewise)
    jinv:  (3, 3)                 per-element-constant J^{-1}
    """
    # Forward: sum-factorized reference gradient (3c, 3m, qz, qy, qx).
    grad_ref = forward_grad(x_e, B, G)
    # Physical gradient d_j u_c = sum_m ghat[c, m] Jinv[m, j].
    grad = einsum("cmzyx,mj->zyxcj", grad_ref, jinv)

    # Pointwise structured Voigt stress (weighted): (qz, qy, qx, 6).
    sv = stress_voigt(grad, lam_w, mu_w)

    # Backward: reconstruct rows of sigma J^{-T} from the symmetric Voigt
    # buffer (sigma_10 reads the same cell as sigma_01) and contract back.
    rows = jnp.stack(
        [
            jnp.stack([sv[..., VOIGT_INDEX[c, j]] for j in range(3)], axis=-1)
            for c in range(3)
        ],
        axis=-2,
    )  # (qz, qy, qx, c, j)
    q = einsum("zyxcj,mj->cmzyx", rows, jinv)
    return backward_grad_t(q, B, G)


def paop_apply(x_e, lam_w, mu_w, jinv, B, G):
    """Fused PAop action over a batch of elements.

    x_e: (nelem, 3, D1D, D1D, D1D); jinv: (3,3) or (nelem, 3, 3).
    """
    if jinv.ndim == 2:
        fn = lambda x, lw, mw: paop_element(x, lw, mw, jinv, B, G)
        return jax.vmap(fn)(x_e, lam_w, mu_w)
    return jax.vmap(paop_element, in_axes=(0, 0, 0, 0, None, None))(
        x_e, lam_w, mu_w, jinv, B, G
    )


def paop_apply_scenarios(x_se, lam_w, mu_w, jinv, B, G):
    """Fused PAop action over a batch of scenarios sharing one mesh.

    x_se:          (S, nelem, 3, D1D, D1D, D1D)
    lam_w / mu_w:  (S, nelem, Q1D, Q1D, Q1D)   per-scenario material data
    jinv:          (3, 3)                       shared affine geometry

    The scenario axis is folded into the element axis, so the element
    kernel (and, one level up, the Pallas grid) runs unchanged — just
    S times larger.  This is how batched operators keep the paper's
    single-kernel dataflow while amortizing launch/compile overhead
    across scenarios.
    """
    s, ne = x_se.shape[:2]
    y = paop_apply(
        x_se.reshape((s * ne,) + x_se.shape[2:]),
        lam_w.reshape((s * ne,) + lam_w.shape[2:]),
        mu_w.reshape((s * ne,) + mu_w.shape[2:]),
        jinv,
        B,
        G,
    )
    return y.reshape((s, ne) + y.shape[1:])
