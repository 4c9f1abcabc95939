"""Pipeline parallelism over the ``pod`` axis (GPipe schedule via
``jax.shard_map`` + ``jax.lax.ppermute``).

The layer stack is split into ``n_stages`` contiguous groups; stage ``s``
lives on slice ``s`` of the pipeline mesh axis.  The microbatch stream
enters stage 0; every tick each stage applies its layers to the
activation resident on it and forwards the result to the next stage with
``ppermute`` (collective_permute — the TPU-native nearest-neighbour ICI
primitive, which is exactly what an inter-pod hop should use).  After
``n_micro + n_stages - 1`` ticks every microbatch has traversed every
stage; the bubble fraction is the classic (n_stages-1)/(n_micro+n_stages-1).

The last stage accumulates its outputs masked to its own ticks; a final
``psum`` over the stage axis replicates the result (all other stages
contribute zeros), so the caller sees an ordinary replicated batch.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


__all__ = ["pipeline_apply", "split_stages", "bubble_fraction"]


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def split_stages(stacked_params, n_stages: int):
    """Reshape stacked per-layer params (L, ...) -> (n_stages, L/S, ...)."""

    def reshape(a):
        L = a.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return a.reshape((n_stages, L // n_stages) + a.shape[1:])

    return jax.tree.map(reshape, stacked_params)


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    x,
    *,
    mesh,
    n_micro: int,
    axis: str = "pod",
):
    """Run x (B, ...) through the staged stack.

    stage_fn(stage_param_slice, microbatch) -> microbatch.
    stage_params: pytree with leading (n_stages, ...) axis.
    Returns the transformed batch, replicated over ``axis``.
    """
    n_stages = mesh.shape[axis]
    B = x.shape[0]
    assert B % n_micro == 0, (B, n_micro)
    mb = B // n_micro

    def staged(params_local, x_full):
        my_params = jax.tree.map(lambda a: a[0], params_local)
        sid = jax.lax.axis_index(axis)
        xs = x_full.reshape((n_micro, mb) + x_full.shape[1:])
        n_ticks = n_micro + n_stages - 1
        fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            buf, out = carry
            m = t - sid  # microbatch index seen by this stage at tick t
            active = (m >= 0) & (m < n_micro)
            # stage 0 ingests microbatch t while the stream lasts
            inj = jnp.where(
                (sid == 0) & (t < n_micro),
                xs[jnp.clip(t, 0, n_micro - 1)],
                buf,
            )
            y = stage_fn(my_params, inj)
            y = jnp.where(active, y, buf)
            # last stage emits its finished microbatch into the output slot
            emit = active & (sid == n_stages - 1)
            sel = (jnp.arange(n_micro) == jnp.clip(m, 0, n_micro - 1)) & emit
            out = out + sel.reshape((n_micro,) + (1,) * y.ndim).astype(y.dtype) * y[None]
            y = jax.lax.ppermute(y, axis, fwd)
            return (y, out), None

        buf0 = jnp.zeros_like(xs[0])
        out0 = jnp.zeros_like(xs)
        (_, out), _ = jax.lax.scan(tick, (buf0, out0), jnp.arange(n_ticks))
        # replicate: only the last stage wrote non-zeros
        out = jax.lax.psum(out, axis)
        return out.reshape(x_full.shape)

    spec_p = jax.tree.map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(
        staged,
        mesh=mesh,
        in_specs=(spec_p, P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x)
