"""Production meshes.

``make_production_mesh`` is a FUNCTION (importing this module never
touches jax device state): the single-pod mesh is 16 x 16 = 256 chips
(one v5e pod in the 2D view used here), the multi-pod mesh prepends a
``pod`` axis of 2 (512 chips).  The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any
jax import so these shapes are buildable on the CPU container.

Axis roles (see repro.distributed.sharding):
  pod   — data parallelism across pods; only gradient all-reduce and
          pipeline collective-permute ride the inter-pod links.
  data  — data parallelism within a pod.
  model — tensor/expert parallelism within a pod (ICI-local).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = [
    "make_production_mesh",
    "make_local_mesh",
    "MESH_AXES",
]

MESH_AXES = {
    False: ("data", "model"),
    True: ("pod", "data", "model"),
}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(model_parallel: int | None = None):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    mp = model_parallel or 1
    while n % mp:
        mp //= 2
    return jax.make_mesh(
        (n // mp, mp), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
