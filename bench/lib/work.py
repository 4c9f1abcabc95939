"""The least work one operator apply needs, whatever implements it.

FLOPs are the sum-factorized multiply-add count of the fused operator
per element (forward gradient, J^-T pullback, structured Voigt stress,
backward contractions), the closed form of the paper's Table 5, with
the (p + 2)-point Gauss rule.  Bytes are what the apply cannot avoid
moving: the input L-vector read, the output L-vector written, and two
material scalars (lambda, mu) per element.  Geometry is one constant
Jacobian for the whole box and the 1D tables fit in any cache, so
neither counts.
"""

from __future__ import annotations

import json
from pathlib import Path

__all__ = ["flops_per_elem", "apply_flops", "apply_bytes", "peaks", "roofline"]

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


def flops_per_elem(p: int) -> float:
    D, Q = p + 1, p + 2
    fwd = 3 * 2 * (2 * Q * D**3 + 3 * Q**2 * D**2 + 3 * Q**3 * D)
    geom = 2 * 9 * Q**3 * 2
    stress = 24 * Q**3
    bwd = 3 * 2 * (3 * Q**3 * D + 3 * Q**2 * D**2 + 3 * Q * D**3)
    return float(fwd + geom + stress + bwd)


def apply_flops(p: int, nelem: int) -> float:
    return flops_per_elem(p) * nelem


def apply_bytes(itemsize: int, ndof: int, nelem: int) -> float:
    return float(itemsize * (2 * ndof + 2 * nelem))


def peaks(device_kind: str) -> dict:
    """The device's published peaks; an unknown device is an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}"
            f" (known: {sorted(table)})"
        )
    return table[device_kind]


def roofline(flops: float, nbytes: float, seconds: float, peak: dict):
    """(share in %, binding term): the least time the chip could take,
    the larger of FLOPs over peak FLOP/s and bytes over bandwidth, over
    the measured time."""
    t_flops = flops / peak["flops_per_s"]
    t_bytes = nbytes / peak["bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
