"""jit'd public wrapper for the PAop Pallas kernel.

Handles lane selection (compiled vs interpret), layout (framework
element-first <-> kernel element-last), padding to a whole number of
element blocks, and the VMEM-budgeted choice of elements-per-block (the
TPU analog of the paper's slice-wise working-set bound).

Lanes
-----
The kernel runs in one of two *lanes*:

* ``"compiled"`` — native Pallas lowering through TPU Mosaic.  One
  fused kernel per element block, VMEM-resident intermediates.  Mosaic
  has no float64, so this lane runs float32 (or narrower) only.
* ``"interpret"`` — the Pallas interpreter.  Runs on any backend
  (including the CPU test containers), bit-faithful to the kernel
  dataflow, orders of magnitude slower.

``resolve_lane`` picks the lane from the backend: ``"auto"`` is
``compiled`` on a TPU and ``interpret`` elsewhere; an explicit
``"compiled"`` on a backend that cannot lower Pallas raises instead of
quietly interpreting.  The resolved lane is what operators, solvers and
the service record.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.flops import default_q1d
from repro.kernels.pa_elasticity.pa_elasticity import (
    VMEM_LIMIT_BYTES,
    pa_elasticity_pallas,
)

__all__ = [
    "pa_elasticity",
    "elements_per_block",
    "clamp_elements_per_block",
    "block_workingset_bytes",
    "backend_supports_compiled",
    "check_compiled_dtype",
    "compiled_dtype_refusal",
    "resolve_lane",
    "PALLAS_LANES",
    "VMEM_BUDGET_BYTES",
    "VMEM_LIMIT_BYTES",
]

# Working-set target when choosing elements per block.  Mosaic compile
# time grows with the block's vreg count (compiling for a v5e on an
# 8-core host: p=4 takes 7.5 s at eb=128 and 14 s at eb=256, p=8 takes
# 42 s at eb=128); 8 MiB keeps every kernel of the GMG ladder under a
# minute, and the 128-element floor already fills the lanes.
VMEM_BUDGET_BYTES = 8 * 2 ** 20
# Mosaic allocates the small B/G tables and its own scratch on top of
# the blocks and the body's live set.
_VMEM_SLACK_BYTES = 2 * 2 ** 20
_LANE = 128  # TPU lane width: every element block is a multiple.

PALLAS_LANES = ("auto", "compiled", "interpret")


def backend_supports_compiled(backend: str | None = None) -> bool:
    """True when ``backend`` (default: JAX's default backend) lowers
    ``pallas_call`` natively — a TPU.  Every other backend interprets."""
    b = backend if backend is not None else jax.default_backend()
    return b == "tpu"


def resolve_lane(lane: str | None = None, *, interpret: bool | None = None) -> str:
    """Resolve a lane request to the lane that will run: ``"compiled"``
    or ``"interpret"``.

    ``lane`` is ``"auto"`` / ``"compiled"`` / ``"interpret"`` (or None,
    meaning "derive from the legacy ``interpret`` flag": True pins the
    interpreter, False/None asks for auto).  ``"auto"`` follows the
    backend (:func:`backend_supports_compiled`); ``"compiled"`` on a
    backend that cannot lower Pallas raises ValueError."""
    if lane is None:
        lane = "interpret" if interpret else "auto"
    if lane not in PALLAS_LANES:
        raise ValueError(
            f"unknown pallas lane {lane!r}; expected one of {PALLAS_LANES}"
        )
    if lane == "interpret":
        return "interpret"
    if backend_supports_compiled():
        return "compiled"
    if lane == "compiled":
        raise ValueError(
            f"pallas lane 'compiled' needs a TPU backend, but JAX's "
            f"default backend is {jax.default_backend()!r}; use "
            f"lane='auto' or 'interpret' to run the Pallas interpreter"
        )
    return "interpret"


def compiled_dtype_refusal(dtype, lane: str) -> str | None:
    """Why a ``dtype`` kernel cannot run on ``lane``, or None: Mosaic
    cannot lower float64, and the kernel never changes dtype behind the
    caller."""
    if lane == "compiled" and jnp.dtype(dtype) == jnp.dtype(jnp.float64):
        return (
            "the compiled Pallas lane (assembly='paop_pallas' on a TPU) "
            "cannot run float64: Mosaic has no float64.  Use "
            "precision='f32' for the fused kernel, or assembly='paop' "
            "for the mixed and mixed-bf16 policies"
        )
    return None


def check_compiled_dtype(dtype, lane: str) -> None:
    """Raise ValueError for a float64 kernel on the compiled lane."""
    msg = compiled_dtype_refusal(dtype, lane)
    if msg is not None:
        raise ValueError(msg)


def _tiled(n: int, tile: int) -> int:
    return -(-n // tile) * tile


def block_workingset_bytes(
    p: int, eb: int, itemsize: int = 4, q1d: int | None = None
) -> int:
    """Scoped VMEM one grid step needs, as Mosaic allocates it.

    Every block lives in (sublane, 128-lane) tiles over its last two
    axes, so the second-minor axis (D1D or Q1D) rounds up to the
    sublane count (8 rows of 32 bits) and EB to the lane width.
    Mosaic double-buffers the x, y, lambda_w and mu_w blocks, and the
    component-sliced body keeps ~12 Q^3 channels live (6 Voigt, 3
    pullback rows, ~3 sweep transients).  For a v5e, before the fixed
    slack, this predicts 20.1 MiB at p=8, eb=128 where the compiler
    allocates 21.25 MiB, and 3.4 / 6.8 MiB at p=4, eb=128 / 256 where
    it allocates 3.5 / 7.0 MiB.

    ``q1d`` defaults to :func:`repro.core.flops.default_q1d` but MUST be
    passed when the kernel runs a different quadrature (``pa_elasticity``
    reads it off ``lam_w``)."""
    d1 = p + 1
    q1 = default_q1d(p) if q1d is None else q1d
    sub = 8 * max(1, 4 // itemsize)
    lanes = _tiled(eb, _LANE)
    x_blk = 3 * d1 * d1 * _tiled(d1, sub)
    q_chan = q1 * q1 * _tiled(q1, sub)
    words = 2 * (2 * x_blk + 2 * q_chan) + 12 * q_chan
    return words * lanes * itemsize + _VMEM_SLACK_BYTES


def clamp_elements_per_block(eb: int, ne: int) -> int:
    """Snap a requested elements-per-block to a block Mosaic accepts
    for ``ne`` elements: a multiple of 128 lanes.

    The block is at least 128 and no larger than the request rounded
    down to a multiple of 128, then shrunk to the least multiple of 128
    that covers ``ne`` in the same number of grid steps, so padding
    stays under 128 elements per step.  (A whole-axis block narrower
    than 128 is not an option: Mosaic refuses the kernel's reshapes
    that fold such a lane axis into the planes.)"""
    eb = max(_LANE, eb // _LANE * _LANE)
    nblocks = -(-ne // eb)
    return _tiled(-(-ne // nblocks), _LANE)


def elements_per_block(
    p: int, ne: int, itemsize: int = 4, q1d: int | None = None
) -> int:
    """The widest block (128 times a power of two) whose working set
    fits :data:`VMEM_BUDGET_BYTES`, never below 128, snapped to ``ne``
    by :func:`clamp_elements_per_block`.  ``q1d`` is the actual 1-D
    quadrature point count when it differs from the default p+2 rule."""
    eb = _LANE
    while block_workingset_bytes(p, 2 * eb, itemsize, q1d) <= VMEM_BUDGET_BYTES:
        eb *= 2
    return clamp_elements_per_block(eb, ne)


def pa_elasticity(
    x_e, lam_w, mu_w, jinv, B, G, *,
    eb=None, interpret: bool | None = None, lane: str | None = None,
):
    """Fused PAop operator action.

    x_e:    (nelem, 3, D1D, D1D, D1D)  framework layout
    lam_w:  (nelem, Q1D, Q1D, Q1D)     (mu_w likewise)
    jinv:   (3, 3) mesh-constant affine J^{-1}
    B, G:   (Q1D, D1D)
    lane:   "auto" | "compiled" | "interpret" (see :func:`resolve_lane`;
            the legacy boolean ``interpret`` is honored when ``lane`` is
            None — ``interpret=True`` pins the interpreter).
    Returns y_e in the same layout as x_e.
    """
    if jinv.ndim != 2:
        raise ValueError(
            "pa_elasticity kernel assumes a mesh-constant affine J^{-1}; "
            "use repro.core.paop.paop_apply for per-element geometry"
        )
    resolved = resolve_lane(lane, interpret=interpret)
    check_compiled_dtype(x_e.dtype, resolved)
    ne = x_e.shape[0]
    d1d = x_e.shape[-1]
    q1d = lam_w.shape[-1]
    p = d1d - 1
    itemsize = jnp.dtype(x_e.dtype).itemsize
    if eb is None:
        eb = elements_per_block(p, ne, itemsize, q1d)
    eb = clamp_elements_per_block(eb, ne)

    # Checked against the REAL q1d (read off lam_w), and against the
    # scoped-VMEM limit the kernel hands Mosaic.
    ws = block_workingset_bytes(p, eb, itemsize, q1d)
    if ws > VMEM_LIMIT_BYTES:
        raise ValueError(
            f"pa_elasticity block working set {ws} B (p={p}, q1d={q1d}, "
            f"eb={eb}, itemsize={itemsize}) exceeds the VMEM limit "
            f"{VMEM_LIMIT_BYTES} B; pass a smaller eb or let "
            f"elements_per_block choose it"
        )

    pad = (-ne) % eb
    xt = jnp.moveaxis(x_e, 0, -1)  # (3, D, D, D, NE)
    lt = jnp.moveaxis(lam_w, 0, -1)
    mt = jnp.moveaxis(mu_w, 0, -1)
    if pad:
        xt = jnp.pad(xt, [(0, 0)] * 4 + [(0, pad)])
        lt = jnp.pad(lt, [(0, 0)] * 3 + [(0, pad)])
        mt = jnp.pad(mt, [(0, 0)] * 3 + [(0, pad)])

    yt = pa_elasticity_pallas(
        xt, lt, mt, jinv, B, G,
        d1d=d1d, q1d=q1d, eb=eb, interpret=resolved == "interpret",
    )
    if pad:
        yt = yt[..., :ne]
    return jnp.moveaxis(yt, -1, 0)
