"""Device time of one fine-level operator apply of the program, read
from the profiler's trace, and its share of the roofline."""

from __future__ import annotations

import shutil
import statistics
import tempfile

import numpy as np

from bench.lib import trace as tr
from bench.lib import work

REPEATS = 5


def time_apply(config: dict, dtype: str) -> dict:
    """Run ``ElasticityOperator.apply`` of the program on the
    configuration's finest level at ``dtype`` (``"float32"`` or
    ``"float64"``), with the configuration's assembly and materials, and
    time each run from the device trace."""
    import jax
    import jax.numpy as jnp

    from repro.core.geometry import material_fields, quadrature_geometry
    from repro.core.operators import DEFER_MATERIALS, ElasticityOperator
    from repro.fem.mesh import beam_hex
    from repro.fem.space import H1Space

    p, refine = int(config["p"]), int(config["refine"])
    dt = jnp.dtype(dtype)
    space = H1Space(beam_hex().refined(refine), p)
    op = ElasticityOperator(space, assembly=config["service"]["assembly"],
                            materials=DEFER_MATERIALS, dtype=dt)
    mats = {int(a): tuple(v) for a, v in config["materials"].items()}
    lam_e, mu_e = material_fields(space.mesh, mats)
    w = quadrature_geometry(space.mesh, space.tables).w_detj
    lam_w = jnp.asarray(lam_e[:, None, None, None] * w, dt)
    mu_w = jnp.asarray(mu_e[:, None, None, None] * w, dt)
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((space.nscalar, 3)), dt)

    def apply(x, lam_w, mu_w):
        return op.with_material_weights(lam_w, mu_w, None).apply(x)

    name = f"bench_apply_{dtype}"
    apply.__name__ = apply.__qualname__ = name
    f = jax.jit(apply)
    jax.block_until_ready(f(x, lam_w, mu_w))  # compile or load, untraced
    logdir = tempfile.mkdtemp(prefix="bench-apply-")
    try:
        jax.profiler.start_trace(logdir)
        try:
            for _ in range(REPEATS):
                jax.block_until_ready(f(x, lam_w, mu_w))
        finally:
            jax.profiler.stop_trace()
        times = tr.load(tr.find_xplane(logdir)).module_times(f"jit_{name}")
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    if not times:
        return {}
    return {
        "seconds": statistics.median(times),
        "runs": len(times),
        "flops": work.apply_flops(p, space.nelem),
        "bytes": work.apply_bytes(dt.itemsize, space.ndof, space.nelem),
    }


def share(run, measured: dict):
    """Roofline share (%) of a :func:`time_apply` result, or None."""
    if not measured:
        return None
    pct, bound = work.roofline(measured["flops"], measured["bytes"],
                               measured["seconds"],
                               work.peaks(run.device["kind"]))
    measured["bound"] = bound
    return pct
