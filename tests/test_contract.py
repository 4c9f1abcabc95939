"""Float64 contractions as multiply-adds.

``repro.core.contract.einsum`` computes a two-operand float64
contraction as a broadcast product summed over the contracted axis, and
any other as ``jnp.einsum``.  These tests compare the multiply-add path
with ``jnp.einsum`` for every contraction the solver runs, the operator
built on it with the operator built on the dot, and check that reduced
precision keeps the ``dot_general``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import contract
from repro.core.operators import ElasticityOperator
from repro.core.paop import paop_apply
from repro.fem.mesh import beam_hex
from repro.fem.space import H1Space

# Every contraction spec of the solver, with the size of each index for
# polynomial degree p (d = D1D = p + 1 points, q = Q1D = p + 2).
_SUMFACT = (  # core/contract.py
    "...zyx,qx->...zyq", "...zyq,ry->...zrq", "...zrq,sz->...srq",
    "...srq,sz->...zrq", "...zrq,ry->...zyq", "...zyq,qx->...zyx",
)
_JINV = ("cmzyx,mj->zyxcj", "zyxcj,mj->cmzyx")  # core/paop.py
_TRANSFER = (  # fem/transfer.py: coarse zyx, fine ZYX
    "...zyxc,Xx->...zyXc", "...zyXc,Yy->...zYXc", "...zYXc,Zz->...ZYXc",
    "...ZYXc,Zz->...zYXc", "...zYXc,Yy->...zyXc", "...zyXc,Xx->...zyxc",
)
_DIAG = ("emj,enj->emn",)  # core/diagonal.py, e elements
_KERNEL = (  # kernels/pa_elasticity: the same sweeps, element axis e last
    "...zyxe,qx->...zyqe", "...zyqe,ry->...zrqe", "...zrqe,sz->...srqe",
    "...srqe,sz->...zrqe", "...zrqe,ry->...zyqe", "...zyqe,qx->...zyxe",
)


def _sizes(spec, p):
    d, q = p + 1, p + 2
    if spec in _SUMFACT:
        return dict(z=d, y=d, x=d, q=q, r=q, s=q)
    if spec in _KERNEL:
        return dict(z=d, y=d, x=d, q=q, r=q, s=q, e=5)
    if spec in _JINV:
        return dict(c=3, m=3, j=3, z=q, y=q, x=q)
    if spec in _DIAG:
        return dict(e=d, m=3, n=3, j=3)
    return dict(z=d, y=d, x=d, Z=2 * p + 1, Y=2 * p + 1, X=2 * p + 1, c=3)


_CASES = [
    (spec, p, batch)
    for spec in _SUMFACT + _JINV + _TRANSFER + _DIAG + _KERNEL
    for p in (1, 2, 4, 8)
    for batch in (((), (1,), (3,)) if "..." in spec else ((),))
]


def _operands(spec, p, batch, dtype=jnp.float64, seed=0):
    sizes = _sizes(spec, p)
    rng = np.random.default_rng(seed)
    out = []
    for term in spec.split("->")[0].split(","):
        lead = batch if term.startswith("...") else ()
        shape = lead + tuple(sizes[c] for c in term.replace("...", ""))
        out.append(jnp.asarray(rng.standard_normal(shape), dtype))
    return out


def _lowered(fn, *args):
    """The program ``fn`` lowers to, with each op's scope path."""
    return jax.jit(lambda *a: fn(*a)).lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("spec,p,batch", _CASES)
def test_multiply_add_matches_einsum(spec, p, batch):
    a, b = _operands(spec, p, batch)
    y = contract.einsum(spec, a, b)
    ref = jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    assert y.dtype == jnp.float64 and y.shape == ref.shape
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(ref), rtol=1e-14,
        atol=1e-14 * float(jnp.abs(ref).max()),
    )
    text = _lowered(lambda a, b: contract.einsum(spec, a, b), a, b)
    assert "dot_general" not in text and contract.CONTRACT_F64 in text


def _rand_x(space, seed=1):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal((space.nscalar, 3))
    )


@pytest.mark.parametrize("p", [2, 4])
@pytest.mark.parametrize("which", ["paop_apply", "constrained"])
def test_operator_matches_the_dot(p, which, monkeypatch):
    """The f64 operator on multiply-adds against the same operator with
    every contraction a ``jnp.einsum`` dot."""
    space = H1Space(beam_hex(), p)
    op = ElasticityOperator(space, assembly="paop", dtype=jnp.float64)
    if which == "paop_apply":
        x_e = space.to_evec(_rand_x(space))
        args = (x_e, op.lam_w, op.mu_w, op.jinv, op.B, op.G)
        fn = paop_apply
    else:
        args = (_rand_x(space),)
        fn = op.constrained()
    text = _lowered(fn, *args)
    assert "dot_general" not in text and contract.CONTRACT_F64 in text
    y = jax.jit(fn)(*args)
    monkeypatch.setattr(contract, "_multiply_add", lambda spec, a, b: (
        jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)))
    assert "dot_general" in _lowered(fn, *args)
    ref = jax.jit(lambda *a: fn(*a))(*args)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(ref), rtol=1e-13,
        atol=1e-13 * float(jnp.abs(ref).max()),
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_reduced_precision_keeps_the_dot(dtype):
    """f32 and bf16 operands (the V-cycle, the transfers, the Pallas
    kernel's body) take ``jnp.einsum``: the MXU dot on a TPU."""
    spec = _SUMFACT[0]
    a, b = _operands(spec, 4, (3,), dtype)
    text = _lowered(lambda a, b: contract.einsum(spec, a, b), a, b)
    assert "dot_general" in text and contract.CONTRACT_F64 not in text
    space = H1Space(beam_hex(), 2)
    op = ElasticityOperator(space, assembly="paop", dtype=dtype)
    text = _lowered(op.apply, _rand_x(space).astype(dtype))
    assert "dot_general" in text and contract.CONTRACT_F64 not in text
