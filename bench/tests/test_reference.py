"""The plain reference against the program's assembled f64 matrix (fa)
at small sizes on the CPU."""

import numpy as np
import pytest

from bench.lib.reference import Beam

MATS = {1: (50.0, 50.0), 2: (1.0, 1.0)}


@pytest.fixture(scope="module", autouse=True)
def x64():
    import jax

    jax.config.update("jax_enable_x64", True)


@pytest.mark.parametrize("p, refine", [(1, 1), (2, 1), (3, 0), (4, 1)])
def test_apply_matches_assembled_matrix(p, refine):
    import jax.numpy as jnp

    from repro.core.operators import ElasticityOperator
    from repro.fem.mesh import beam_hex
    from repro.fem.space import H1Space

    space = H1Space(beam_hex().refined(refine), p)
    op = ElasticityOperator(space, assembly="fa", materials=MATS,
                            dtype=jnp.float64)
    beam = Beam(p, refine, block=7)
    assert (beam.nscalar, beam.nelem) == (space.nscalar, space.nelem)
    x = np.random.default_rng(p).standard_normal((space.nscalar, 3))
    want = np.asarray(op.apply(jnp.asarray(x)))
    got = beam.apply(x, *beam.fields(MATS))
    assert np.abs(got - want).max() <= 1e-12 * np.abs(want).max()


@pytest.mark.parametrize("p, refine", [(2, 1), (4, 1)])
def test_load_and_clamp_match_the_program(p, refine):
    from repro.fem.mesh import beam_hex
    from repro.fem.space import H1Space

    space = H1Space(beam_hex().refined(refine), p)
    beam = Beam(p, refine)
    t = (1e-3, -2e-3, -1.5e-2)
    want = space.traction_rhs("x1", t)
    want[space.essential_mask()] = 0.0
    np.testing.assert_allclose(beam.load(t), want, rtol=0, atol=1e-17)
    assert (beam.ess_mask() == space.essential_mask()).all()


def test_residual_of_exact_and_perturbed_solutions():
    """A direct f64 solve reads ~1e-12; a 1% scaled one reads ~1e-2."""

    beam = Beam(2, 0)
    lam, mu = beam.fields(MATS)
    n = beam.ndof
    m = beam.ess_mask().reshape(-1)
    cols = []
    for j in range(n):
        e = np.zeros(n)
        e[j] = 1.0
        y = beam.apply(np.where(m, 0.0, e).reshape(-1, 3), lam, mu).reshape(-1)
        cols.append(np.where(m, e, y))
    A = np.stack(cols, axis=1)
    t = (0.0, 1e-3, -1e-2)
    b = beam.load(t).reshape(-1)
    x = np.linalg.solve(A, b)
    assert beam.residual(x, t, lam, mu) < 1e-10
    assert beam.residual(1.01 * x, t, lam, mu) == pytest.approx(1e-2, rel=1e-6)
