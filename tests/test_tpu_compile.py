"""Deviceless compiles for one described TPU v5e chip.

The TPU compiler is installed alongside JAX, so these tests lower and
compile the fused Pallas kernel and a service step program for a chip
that is described, not attached.  They catch what the interpreter never
checks: block shapes Mosaic refuses, VMEM over the scoped limit, int64
index maps, float64 in a kernel.  Nothing runs, so nothing here is a
result or a time.

The topology is described inside a module fixture (never at import):
only one process at a time may load the TPU library, and every test
worker imports this file.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.pa_elasticity import ops


@pytest.fixture(scope="module")
def topo():
    # Skip only where the TPU compiler is not installed at all; any
    # failure to describe the chip with it installed is a failure.
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("libtpu (the TPU compiler) is not installed")
    # Keep the TPU compiler's logs out of the temp directory.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_lane(monkeypatch):
    """Resolve Pallas lanes as on a TPU: JAX's default backend stays the
    CPU here, so the compiled lane is steered in the test."""
    monkeypatch.setattr(
        ops, "backend_supports_compiled", lambda backend=None: True
    )


def _kernel_args(p, ne, dtype, sharding):
    d1, q1 = p + 1, p + 2

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return (
        sds(ne, 3, d1, d1, d1),
        sds(ne, q1, q1, q1),
        sds(ne, q1, q1, q1),
        sds(3, 3),
        sds(q1, d1),
        sds(q1, d1),
    )


# Widths: 256 elements at p <= 4 (one whole-axis block each) and 256 at
# p = 6, 8 (two 128-element blocks).  Mosaic compile time follows the
# block width, so this stays at seconds per case while exercising the
# block rule and the VMEM limit the service's element counts use.
@pytest.mark.parametrize("p", [1, 2, 4, 6, 8])
def test_kernel_compiles_for_v5e(p, one_chip, tpu_lane):
    ne = 256
    eb = ops.elements_per_block(p, ne)
    assert eb == ne or eb % 128 == 0, eb
    args = _kernel_args(p, ne, jnp.float32, one_chip)
    compiled = (
        jax.jit(lambda *a: ops.pa_elasticity(*a, lane="compiled"))
        .lower(*args)
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("where", ["kernel", "operator", "solver"])
def test_f64_refused_on_compiled_lane(where, tpu_lane):
    """Mosaic has no float64: every entry to the compiled lane refuses
    it with a message naming the policy to use, before any lowering."""
    from repro.core.operators import ElasticityOperator
    from repro.fem.mesh import beam_hex
    from repro.fem.space import H1Space
    from repro.solvers.batched import BatchedGMGSolver

    with pytest.raises(ValueError, match="precision='f32'"):
        if where == "kernel":
            x = jnp.zeros((4, 3, 3, 3, 3), jnp.float64)
            q = jnp.ones((4, 4, 4, 4), jnp.float64)
            t = jnp.zeros((4, 3), jnp.float64)
            ops.pa_elasticity(
                x, q, q, jnp.eye(3, dtype=jnp.float64), t, t, lane="compiled"
            )
        elif where == "operator":
            ElasticityOperator(
                H1Space(beam_hex(), 2), assembly="paop_pallas",
                dtype=jnp.float64, pallas_lane="compiled",
            )
        else:
            # mixed keeps the outer Krylov (and its fine operator) in f64
            BatchedGMGSolver(
                beam_hex(), 0, 2, assembly="paop_pallas", precision="mixed"
            )


def test_chunk_program_compiles_for_v5e(one_chip, tpu_lane):
    """One continuous-serving step program (f32, fused kernel on the
    compiled lane) compiles for the chip: the kernel inside the GMG
    V-cycle and the while loop around it."""
    from repro.fem.mesh import beam_hex
    from repro.solvers.batched import BatchedGMGSolver

    s = 2
    solver = BatchedGMGSolver(
        beam_hex(), 0, 2, assembly="paop_pallas", precision="f32"
    )
    assert solver.pallas_lane == "compiled"

    def sds(a):
        return jax.ShapeDtypeStruct(
            np.shape(a), np.asarray(a).dtype, sharding=one_chip
        )

    state = jax.tree.map(sds, solver.empty_state(s))
    prep = jax.tree.map(sds, solver.empty_prep(s))
    row = jax.ShapeDtypeStruct((s,), np.float32, sharding=one_chip)
    compiled = solver._jit_chunk[True].lower(
        jax.ShapeDtypeStruct((s, 3), np.float32, sharding=one_chip),
        row,
        jax.ShapeDtypeStruct((s,), np.bool_, sharding=one_chip),
        state,
        prep,
        jax.ShapeDtypeStruct((), np.int32, sharding=one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
