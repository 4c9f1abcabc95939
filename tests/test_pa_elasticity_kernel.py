"""Pallas PAop kernel: shape/dtype sweep against the pure-jnp oracle,
lane resolution (compiled on a TPU, interpret elsewhere, no fallback),
and the VMEM block-size estimator invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core.basis import basis_tables
from repro.kernels.pa_elasticity import ops
from repro.kernels.pa_elasticity.ref import paop_ref


def _setup(p, ne, dtype, seed=0):
    tb = basis_tables(p)
    rng = np.random.default_rng(seed)
    d1, q1 = tb.d1d, tb.q1d
    x = jnp.asarray(rng.standard_normal((ne, 3, d1, d1, d1)), dtype)
    lam = jnp.asarray(rng.random((ne, q1, q1, q1)) + 0.5, dtype)
    mu = jnp.asarray(rng.random((ne, q1, q1, q1)) + 0.5, dtype)
    jinv = jnp.asarray(np.diag([2.0, 3.0, 4.0]), dtype)
    B = jnp.asarray(tb.B, dtype)
    G = jnp.asarray(tb.G, dtype)
    return x, lam, mu, jinv, B, G


@pytest.mark.parametrize("p", [1, 2, 3, 4, 6, 8])
@pytest.mark.parametrize("ne", [1, 3, 8])
def test_kernel_matches_oracle_f32(p, ne):
    x, lam, mu, jinv, B, G = _setup(p, ne, jnp.float32)
    y = ops.pa_elasticity(x, lam, mu, jinv, B, G, eb=4, interpret=True)
    ref = paop_ref(x, lam, mu, jinv, B, G)
    scale = float(jnp.abs(ref).max())
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               atol=2e-5 * scale, rtol=2e-4)


@pytest.mark.parametrize("p", [1, 2, 4])
def test_kernel_matches_oracle_f64(p):
    x, lam, mu, jinv, B, G = _setup(p, 4, jnp.float64)
    y = ops.pa_elasticity(x, lam, mu, jinv, B, G, eb=2, interpret=True)
    ref = paop_ref(x, lam, mu, jinv, B, G)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-12)


@pytest.mark.parametrize("eb", [128, 256, 384])
def test_block_size_invariance(eb):
    """Result must not depend on the VMEM tiling choice: 300 elements in
    three, two or one lane-aligned blocks (the last padded)."""
    x, lam, mu, jinv, B, G = _setup(2, 300, jnp.float32)
    y1 = ops.pa_elasticity(x, lam, mu, jinv, B, G, eb=eb, interpret=True)
    y2 = ops.pa_elasticity(x, lam, mu, jinv, B, G, eb=512, interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5)


def test_padding_path():
    """ne not divisible by eb exercises the pad/trim wrapper."""
    x, lam, mu, jinv, B, G = _setup(2, 5, jnp.float32)
    y = ops.pa_elasticity(x, lam, mu, jinv, B, G, eb=4, interpret=True)
    ref = paop_ref(x, lam, mu, jinv, B, G)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=2e-4,
                               atol=1e-5 * float(jnp.abs(ref).max()))


def test_clamp_never_exceeds_element_count():
    """The clamped block is a multiple of 128 lanes (what Mosaic
    accepts), never wider than ne rounded up to the lane width nor than
    the request (floor 128), and pads by under 128 elements per grid
    step."""
    for ne in (1, 2, 3, 5, 7, 12, 100, 129, 300, 4096, 3 * 4096 + 64):
        for eb in (1, 2, 8, 128, 200, 1024):
            got = ops.clamp_elements_per_block(eb, ne)
            assert got % 128 == 0, (eb, ne, got)
            assert got <= -(-ne // 128) * 128, (eb, ne, got)
            assert got <= max(128, eb), (eb, ne, got)
            nblocks = -(-ne // got)
            assert nblocks * got - ne < 128 * nblocks, (eb, ne, got)


def test_clamp_prefers_exact_divisors():
    """Requests that tile ne exactly are kept (zero padding); otherwise
    the block shrinks to the least lane multiple covering ne in the
    same number of grid steps."""
    assert ops.clamp_elements_per_block(256, 4096) == 256
    assert ops.clamp_elements_per_block(1024, 3 * 4096) == 1024
    assert ops.clamp_elements_per_block(128, 64 * 6) == 128
    assert ops.clamp_elements_per_block(200, 1000) == 128  # 8 x 128
    assert ops.clamp_elements_per_block(512, 640) == 384  # 2 steps, not 512
    # below one lane width: a single padded 128-element block
    assert ops.clamp_elements_per_block(128, 12) == 128
    assert ops.clamp_elements_per_block(4, 7) == 128


@pytest.mark.parametrize("ne", [1, 3, 12, 64, 64 * 6, 512 * 5, 4096 * 3])
def test_elements_per_block_bounded_by_ne(ne):
    """For p = 1..8 at small and service-sized element counts (the
    beam_p8_6m ladder folds 64, 512 and 4096 elements per scenario):
    a lane-aligned block that pads by less than one lane width."""
    for p in range(1, 9):
        eb = ops.elements_per_block(p, ne)
        assert eb % 128 == 0 and eb < ne + 128, (p, ne, eb)


def test_small_mesh_padding_roundtrip():
    """The regression shape from the issue (small ne, auto eb): result
    must round-trip through pad/trim and match the oracle."""
    x, lam, mu, jinv, B, G = _setup(2, 12, jnp.float32)
    y = ops.pa_elasticity(x, lam, mu, jinv, B, G, interpret=True)
    ref = paop_ref(x, lam, mu, jinv, B, G)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=2e-4,
                               atol=1e-5 * float(jnp.abs(ref).max()))


def test_vmem_budget_respected():
    """The chosen block fits the working-set budget, except at the
    128-element floor (p >= 7), which still fits the hard VMEM limit."""
    for p in range(1, 9):
        eb = ops.elements_per_block(p, ne=1 << 20)
        ws = ops.block_workingset_bytes(p, eb)
        assert eb >= 128
        assert ws <= ops.VMEM_BUDGET_BYTES or eb == 128, (p, eb, ws)
        assert ws <= ops.VMEM_LIMIT_BYTES


# -- lane resolution ---------------------------------------------------------


def test_resolve_lane_basics():
    assert ops.resolve_lane("interpret") == "interpret"
    assert ops.resolve_lane(None, interpret=True) == "interpret"
    # auto (and the legacy interpret=False/None) resolves to a real lane
    for lane in (ops.resolve_lane("auto"), ops.resolve_lane(None),
                 ops.resolve_lane(None, interpret=False)):
        assert lane in ("compiled", "interpret")
    with pytest.raises(ValueError, match="pallas lane"):
        ops.resolve_lane("fast")


def test_resolve_lane_follows_backend_capability(monkeypatch):
    """auto resolves from the backend; an explicit interpret request
    always pins the interpreter; an explicit compiled request on a
    backend that cannot lower Pallas raises instead of interpreting."""
    monkeypatch.setattr(ops, "backend_supports_compiled", lambda b=None: True)
    assert ops.resolve_lane("auto") == "compiled"
    assert ops.resolve_lane("compiled") == "compiled"
    assert ops.resolve_lane(None, interpret=False) == "compiled"
    assert ops.resolve_lane("interpret") == "interpret"
    monkeypatch.setattr(ops, "backend_supports_compiled", lambda b=None: False)
    assert ops.resolve_lane("auto") == "interpret"
    with pytest.raises(ValueError, match="needs a TPU backend"):
        ops.resolve_lane("compiled")


def test_backend_supports_compiled_never_on_cpu():
    """Only a TPU lowers the kernel natively; the answer comes from the
    backend name alone, with no compile probe to swallow errors."""
    assert ops.backend_supports_compiled("cpu") is False
    assert ops.backend_supports_compiled("gpu") is False
    assert ops.backend_supports_compiled("tpu") is True


@pytest.mark.parametrize("p", [1, 2, 3, 4, 5, 6, 7, 8])
def test_compiled_lane_matches_interpret(p):
    """On a TPU the compiled lane agrees with the interpreter to f32
    rounding for every p in 1..8.  Elsewhere a compiled request raises
    rather than running the interpreter under the compiled lane's name
    (tests/test_tpu_compile.py compiles these kernels for a v5e)."""
    x, lam, mu, jinv, B, G = _setup(p, 4, jnp.float32)
    yi = ops.pa_elasticity(x, lam, mu, jinv, B, G, lane="interpret")
    if not ops.backend_supports_compiled():
        with pytest.raises(ValueError, match="needs a TPU backend"):
            ops.pa_elasticity(x, lam, mu, jinv, B, G, lane="compiled")
        return
    yc = ops.pa_elasticity(x, lam, mu, jinv, B, G, lane="compiled")
    scale = float(jnp.abs(yi).max())
    np.testing.assert_allclose(np.asarray(yc), np.asarray(yi),
                               atol=1e-6 * scale, rtol=1e-6)


# -- VMEM estimator: real q1d and call-time budget check ---------------------


def test_workingset_uses_real_q1d():
    """The estimator defaults to the p+2 Gauss rule but must budget
    against the actual quadrature when one is passed."""
    p = 2
    assert (ops.block_workingset_bytes(p, 8, q1d=p + 2)
            == ops.block_workingset_bytes(p, 8))
    assert (ops.block_workingset_bytes(p, 8, q1d=12)
            > ops.block_workingset_bytes(p, 8))
    eb_default = ops.elements_per_block(p, 1 << 20)
    eb_rich = ops.elements_per_block(p, 1 << 20, q1d=12)
    assert eb_rich < eb_default
    ws = ops.block_workingset_bytes(p, eb_rich, q1d=12)
    assert ws <= ops.VMEM_BUDGET_BYTES or eb_rich == 128
    assert ws <= ops.VMEM_LIMIT_BYTES


def test_call_time_vmem_budget_assertion():
    """An explicit eb whose working set (at the REAL q1d read off
    lam_w) exceeds the VMEM limit handed to Mosaic must fail loudly at
    call time, not ask for more VMEM than a v5e core has."""
    ne, p, q1 = 512, 8, 10
    d1 = p + 1
    x = jnp.zeros((ne, 3, d1, d1, d1), jnp.float32)
    lam = jnp.ones((ne, q1, q1, q1), jnp.float32)
    jinv = jnp.eye(3, dtype=jnp.float32)
    B = jnp.zeros((q1, d1), jnp.float32)
    assert ops.block_workingset_bytes(p, ne, 4, q1) > ops.VMEM_LIMIT_BYTES
    with pytest.raises(ValueError, match="VMEM limit"):
        ops.pa_elasticity(x, lam, lam, jinv, B, B, eb=ne, interpret=True)


# -- clamp invariants (property) ---------------------------------------------


@settings(max_examples=300, deadline=None)
@given(ne=st.integers(1, 4096), p=st.integers(1, 8),
       scale=st.integers(0, 12))
def test_clamp_invariants_property(ne, p, scale):
    """Over ne in [1, 4096] and the estimator's whole p range: the
    clamped block is a lane multiple, never wider than the request
    (floor 128) nor than ne rounded up to the lane width, and pads by
    under one lane width per grid step; a request that tiles ne exactly
    is kept."""
    eb_req = max(1, ops.elements_per_block(p, 1 << 20) >> scale)
    got = ops.clamp_elements_per_block(eb_req, ne)
    assert got % 128 == 0
    assert got <= max(128, eb_req // 128 * 128)
    assert got <= -(-ne // 128) * 128
    nblocks = -(-ne // got)
    assert nblocks * got - ne < 128 * nblocks
    if eb_req % 128 == 0 and ne % eb_req == 0:
        assert got == eb_req
