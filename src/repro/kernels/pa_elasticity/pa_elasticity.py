"""Fused sum-factorized PAop elasticity kernel (Pallas, TPU target).

TPU-native adaptation of the paper's PAop kernel (Sec. 4). The paper's
CPU design decisions map as follows:

* **slice-wise loops bounding the L1/L2 working set**  ->  two levels of
  tiling.  Across elements, an explicit `BlockSpec` tiles a *block of EB
  elements* into VMEM, with EB chosen by `ops.elements_per_block` to
  keep the block working set under a VMEM budget.  Within the kernel
  body, the dataflow is *component-sliced*: the forward pass walks one
  displacement component at a time and folds its physical gradients
  straight into the 6 Voigt accumulators, and the backward pass emits
  one output component at a time, writing each straight to its `y_ref`
  slice.  The 9-channel reference-gradient stack (`ghat`) and the
  concatenated output accumulator of the naive dataflow are never
  materialized — the VMEM live set at quadrature resolution is bounded
  by the Voigt channels plus one component's transient sweeps (~12
  Q^3-channels instead of ~18), the TPU analog of the paper's slice
  loops keeping one x/y-plane resident in L1.
* **SIMD vectorization across the contraction loops**  ->  an
  element-last data layout `(3, D1D, D1D, D1D, EB)`.  Each 1D
  contraction becomes a `(Q1D x D1D) @ (D1D x N)` matmul with
  N = (planes x EB) — the element axis fills the 128-wide MXU/VPU lanes
  that a single element's D1D in [2, 9] never could.  This is the TPU
  version of "vectorize across elements".
* **macro-kernel fusion**  ->  the kernel body runs forward
  interpolation, pointwise Voigt stress, and the transpose contraction
  back-to-back on VMEM-resident values; the operator-wide QVec round
  trip through HBM does not exist.  HBM traffic per element is exactly
  x_e, y_e, lambda_w, mu_w (+ the shared B/G tables once per block).
* **Voigt notation**  ->  the stress lives as 6 channels; backward
  reconstructs rows of sigma.J^{-T} through the symmetric index map.

Lanes: `interpret=True` runs the Pallas interpreter (any backend, used
for CPU CI); `interpret=False` is the *compiled* lane (TPU Mosaic).
Lane selection lives in `ops.resolve_lane`; this module takes the
already-resolved boolean.  Mosaic has no float64, so the compiled lane
runs f32 (and narrower) only; `ops.pa_elasticity` refuses f64 there.

The kernel assumes affine geometry with a mesh-constant J^{-1} (uniform
box; the general per-element-affine case is handled by the pure-JAX PAop
path).  Validated against `ref.paop_ref` across p in 1..8 and dtypes
(tests/test_pa_elasticity_kernel.py); tests/test_tpu_compile.py compiles
it for a v5e.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.contract import einsum

__all__ = ["pa_elasticity_pallas", "VMEM_LIMIT_BYTES"]

# The scoped VMEM Mosaic may use per grid step: half of a v5e
# TensorCore's 128 MiB, well above the 16 MiB default scoped limit that
# p=8 needs to exceed (about 21 MiB at the 128-element floor).
# ``ops.block_workingset_bytes`` picks the block; a limit set to that
# estimate exactly would leave no room for the compiler's own rounding
# (p=8 overran a 21.09 MiB limit by 0.1 MiB).
VMEM_LIMIT_BYTES = 64 * 2 ** 20


# --------------------------------------------------------------------------
# Element-last contraction helpers. Shapes: (..., axis_dim, EB); tables
# (Q1D, D1D). Each is one MXU matmul of shape (Q1D, D1D) x (D1D, N), at
# full f32 precision (``repro.core.contract.einsum``): the MXU's default
# single bf16 pass would leave the operator ~1e-3 off, which the outer
# Krylov iteration cannot hide.
# --------------------------------------------------------------------------
def _cx(t, table):
    # contract ix: (..., z, y, x, e) . (q, x) -> (..., z, y, q, e)
    return einsum("...zyxe,qx->...zyqe", t, table)


def _cy(t, table):
    return einsum("...zyqe,ry->...zrqe", t, table)


def _cz(t, table):
    return einsum("...zrqe,sz->...srqe", t, table)


def _cx_t(t, table):
    return einsum("...zyqe,qx->...zyxe", t, table)


def _cy_t(t, table):
    return einsum("...zrqe,ry->...zyqe", t, table)


def _cz_t(t, table):
    return einsum("...srqe,sz->...zrqe", t, table)


def _kernel(x_ref, lam_ref, mu_ref, jinv_ref, b_ref, g_ref, y_ref):
    """One grid step: the fused PAop dataflow for a block of EB elements.

    x_ref:   (3, D1D, D1D, D1D, EB)   VMEM
    lam_ref: (Q1D, Q1D, Q1D, EB)      VMEM  (mu_ref likewise)
    jinv_ref:(3, 3)                   SMEM, constant per mesh (affine)
    b_ref:   (Q1D, D1D), g_ref: (Q1D, D1D)
    y_ref:   (3, D1D, D1D, D1D, EB)   VMEM

    The body is component-sliced (the paper's slice-wise loop
    reorganization): neither the 9-channel reference gradient stack nor
    a concatenated output buffer ever exists.  Forward folds each
    component's gradients into the 6 Voigt accumulators as it goes;
    backward emits one output component per iteration directly into its
    y_ref slice.
    """
    B = b_ref[...]
    G = g_ref[...]
    # J^{-1} entries are scalars: read them from SMEM, not a VMEM vector.
    jinv = [[jinv_ref[m, j] for j in range(3)] for m in range(3)]
    lam_w = lam_ref[...]
    mu_w = mu_ref[...]

    # ---- forward, one displacement component c at a time (sm0/sm1 of
    # the paper, sliced).  Live at quadrature resolution: the running
    # Voigt accumulators (3 diagonal gradients + 3 symmetrized
    # off-diagonal sums) and one component's 3 transient reference
    # gradients — never the full (3, 3, Q, Q, Q, EB) grad tensor.
    diag = [None] * 3  # d_c u_c (physical)
    off = {}  # {(j, k): d_k u_j + d_j u_k}, j < k
    for c in range(3):
        xc = x_ref[c]
        u = _cx(xc, B)
        v = _cx(xc, G)
        # ghat[c, :] = (d_xi, d_eta, d_zeta) u_c, reference coords
        g0 = _cz(_cy(v, B), B)
        g1 = _cz(_cy(u, G), B)
        g2 = _cz(_cy(u, B), G)
        # physical row: d_j u_c = sum_m ghat[c, m] Jinv[m, j]
        for j in range(3):
            grad_cj = g0 * jinv[0][j] + g1 * jinv[1][j] + g2 * jinv[2][j]
            if j == c:
                diag[c] = grad_cj
            else:
                key = (min(c, j), max(c, j))
                off[key] = (
                    grad_cj if key not in off else off[key] + grad_cj
                )

    # ---- pointwise structured Voigt stress (weighted), 6 channels
    div = diag[0] + diag[1] + diag[2]
    ld = lam_w * div
    two_mu = 2.0 * mu_w
    s = {
        (0, 0): ld + two_mu * diag[0],
        (1, 1): ld + two_mu * diag[1],
        (2, 2): ld + two_mu * diag[2],
        (0, 1): mu_w * off[(0, 1)],
        (0, 2): mu_w * off[(0, 2)],
        (1, 2): mu_w * off[(1, 2)],
    }

    def sigma(a, b):
        return s[(a, b) if a <= b else (b, a)]

    # ---- backward, one output component c at a time: rows of
    # sigma.J^{-T} through the symmetric map, transpose sweeps, written
    # straight into the component's output slice (no concatenate).
    for c in range(3):
        # q_m = sum_j sigma[c, j] Jinv[m, j]   (3 pullback rows live)
        q = [
            sigma(c, 0) * jinv[m][0]
            + sigma(c, 1) * jinv[m][1]
            + sigma(c, 2) * jinv[m][2]
            for m in range(3)
        ]
        # transpose sweeps: G along the derivative direction m, B elsewhere
        y_c = _cx_t(_cy_t(_cz_t(q[0], B), B), G)
        y_c += _cx_t(_cy_t(_cz_t(q[1], B), G), B)
        y_c += _cx_t(_cy_t(_cz_t(q[2], G), B), B)
        y_ref[c] = y_c


@functools.partial(jax.jit, static_argnames=("d1d", "q1d", "eb", "interpret"))
def pa_elasticity_pallas(x_e, lam_w, mu_w, jinv, B, G, *, d1d, q1d, eb, interpret):
    """Apply the fused PAop kernel.

    x_e: (3, D1D, D1D, D1D, NE) element-last layout, NE a multiple of eb.
    lam_w/mu_w: (Q1D, Q1D, Q1D, NE); jinv: (3, 3); B/G: (Q1D, D1D).
    ``interpret=False`` is the compiled lane (Mosaic); callers go through
    ``ops.pa_elasticity``, which resolves the lane and picks ``eb`` first.
    """
    ne = x_e.shape[-1]
    if ne % eb:
        raise ValueError(f"element count {ne} is not a multiple of eb={eb}")
    grid = (ne // eb,)

    # Block indices are int32 for Mosaic; a bare Python 0 would trace
    # as int64 whenever jax_enable_x64 is on (as the solver entry points
    # set it), which Mosaic refuses to lower.
    def e_idx(i):
        z = jnp.int32(0)
        return (z, z, z, z, i)

    def q_idx(i):
        z = jnp.int32(0)
        return (z, z, z, i)

    def full(i):
        z = jnp.int32(0)
        return (z, z)

    kwargs = {}
    if not interpret:
        # Element blocks are independent, so the grid axis is "parallel":
        # Mosaic may run the steps in any order (and split them across
        # TensorCores where a chip has more than one).
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        )

    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct(x_e.shape, x_e.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((3, d1d, d1d, d1d, eb), e_idx),
            pl.BlockSpec((q1d, q1d, q1d, eb), q_idx),
            pl.BlockSpec((q1d, q1d, q1d, eb), q_idx),
            pl.BlockSpec((3, 3), full, memory_space=pltpu.SMEM),
            pl.BlockSpec((q1d, d1d), full),
            pl.BlockSpec((q1d, d1d), full),
        ],
        out_specs=pl.BlockSpec((3, d1d, d1d, d1d, eb), e_idx),
        interpret=interpret,
        **kwargs,
    )(x_e, lam_w, mu_w, jinv, B, G)
