"""Domain-decomposed AddMult (shard_map halo exchange) vs the global
operator.  Runs on however many devices exist (1 on CI = degenerate but
still exercises the block conversion + ppermute schedule)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.core.operators import ElasticityOperator
from repro.core.paop_dd import SlabDecomposition, choose_grid
from repro.fem.mesh import beam_hex
from repro.fem.space import H1Space


def _mesh_1d():
    n = len(jax.devices())
    return jax.make_mesh((n,), ("shard",), axis_types=(AxisType.Auto,))


def test_choose_grid():
    assert choose_grid(128, 16, 256) == (16, 16)
    assert choose_grid(16, 2, 8) == (4, 2)
    assert choose_grid(8, 1, 4) == (4, 1)
    with pytest.raises(ValueError):
        choose_grid(3, 3, 7)


@pytest.mark.parametrize("p", [1, 2, 3])
def test_dd_matches_global(p):
    mesh = _mesh_1d()
    m = beam_hex().refined()  # (16, 2, 2)
    space = H1Space(m, p)
    op = ElasticityOperator(space, assembly="paop", dtype=jnp.float64)
    dd = SlabDecomposition(space, mesh, ("shard",), dtype=jnp.float64)
    x = jnp.asarray(np.random.default_rng(p).standard_normal((space.nscalar, 3)))
    y_ref = np.asarray(op.apply(x))
    y_dd = np.asarray(dd.apply(x))
    np.testing.assert_allclose(y_dd, y_ref, rtol=1e-11,
                               atol=1e-12 * np.abs(y_ref).max())


def test_block_roundtrip():
    mesh = _mesh_1d()
    space = H1Space(beam_hex().refined(), 2)
    dd = SlabDecomposition(space, mesh, ("shard",), dtype=jnp.float64)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((space.nscalar, 3)))
    np.testing.assert_array_equal(
        np.asarray(dd.from_blocks(dd.to_blocks(x))), np.asarray(x)
    )


def test_two_material_split_respected():
    """The per-shard quadrature blocks carry the 50:1 material contrast."""
    mesh = _mesh_1d()
    space = H1Space(beam_hex().refined(), 2)
    dd = SlabDecomposition(space, mesh, ("shard",), dtype=jnp.float64)
    lam = np.asarray(dd.lam_blocks)  # (n_shards, lne, Q, Q, Q)
    # per-ELEMENT means divide out the shared quadrature factor; both
    # materials must be present across the union of shards (and the
    # contrast must be exactly 50:1).
    per_elem = lam.reshape(-1, lam.shape[-3] * lam.shape[-2] * lam.shape[-1]).mean(axis=1)
    assert per_elem.max() / per_elem.min() == pytest.approx(50.0, rel=1e-10)
