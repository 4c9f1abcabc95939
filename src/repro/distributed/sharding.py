"""Sharding rules: parameter/optimizer/activation PartitionSpecs.

FSDP + Megatron-style tensor parallelism:

* ``model`` axis — TP/EP: attention qkv shard the head (output) dim, the
  output projection shards its input dim (one all-reduce per block); MLP
  up/gate shard d_ff out, down shards d_ff in; MoE expert tensors shard
  the expert dim (expert parallelism); embeddings/LM head shard vocab.
* ``data`` axis — FSDP/ZeRO-3: the *other* matrix dim of every large
  tensor is sharded over ``data``, so parameters, gradients and both
  Adam moments are fully sharded over the whole pod (a 32B-param config
  is 64 GB of bf16 weights + 256 GB of f32 moments — per-device this
  must divide by all 256 chips, not just the 16-wide model axis).
  GSPMD turns this into the usual FSDP schedule: per-layer all-gather of
  weights in the forward/backward, reduce-scatter of gradients.
* ``pod`` axis — pure DP: only the gradient all-reduce crosses pods.

Optimizer moments mirror parameter specs (they are pytrees of the same
structure, so ``param_pspecs`` applies directly).  Rules are name-based
over the pytree path; any block following the naming convention inherits
distribution for free.

Sequence parallelism: ``act_pspec`` returns the between-blocks activation
constraint P(dp, 'model', None) — with scan-over-layers + remat the
per-layer saved residual is (B, S, d) and at 4k x 64 layers it must not
be replicated over the model axis (43 GB -> 2.7 GB per device at 32B
scale).  The forward pass applies it via with_sharding_constraint.

Scenario data parallelism (the solver side): the batched elasticity
solve (:mod:`repro.solvers.batched`) carries a leading scenario axis S
with *no cross-scenario coupling* — per-row inner products, per-row
smoother coefficients, per-row coarse factors.  ``scenario_mesh`` /
``scenario_spec`` / ``pin_scenario`` / ``device_put_scenario`` give that
axis a 1-D ``jax.sharding`` mesh: every (S, ...) state/prep array and
every folded (S*E, ...) element array is sharded on axis 0, the fused PA
kernels run unchanged per shard, and the only cross-device traffic is
the (S,)-vector reductions of bpcg's convergence logic.
"""

from __future__ import annotations

import os
import re
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


__all__ = [
    "param_pspecs",
    "state_pspecs",
    "batch_pspec",
    "decode_state_pspecs",
    "act_pspec",
    "SCENARIO_AXIS",
    "scenario_mesh",
    "normalize_scenario_mesh",
    "scenario_spec",
    "scenario_sharding",
    "pin_scenario",
    "device_put_scenario",
    "scenario_row_devices",
    "scenario_layout_mismatches",
    "force_host_device_count",
]

# -- scenario-axis data parallelism (batched elasticity solves) -------------

SCENARIO_AXIS = "scenario"


def force_host_device_count(n: int | None) -> None:
    """Ask XLA for ``n`` virtual host (CPU) devices.

    Must run before the first jax backend touch (any ``jax.devices()`` /
    array op); appends ``--xla_force_host_platform_device_count`` to
    XLA_FLAGS unless one is already present, so an operator-set flag
    always wins.  Centralized here so the CLIs (``--devices N``) and the
    test suite (``REPRO_HOST_DEVICES``) cannot diverge in how they spell
    the flag."""
    if not n or n <= 1:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        return
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={int(n)}".strip()
    )


def scenario_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """A 1-D device mesh over :data:`SCENARIO_AXIS`.

    ``n_devices`` takes the first n of ``jax.devices()`` (all of them
    when None), so one process forced to 8 host devices can build 1-, 2-,
    4- and 8-wide meshes for differential testing."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            if n_devices < 1:
                raise ValueError(
                    f"scenario_mesh needs n_devices >= 1, got {n_devices}"
                )
            if n_devices > len(devices):
                raise ValueError(
                    f"scenario_mesh({n_devices}) but only "
                    f"{len(devices)} devices are available"
                )
            devices = devices[:n_devices]
    if len(devices) < 1:
        raise ValueError("scenario_mesh needs at least one device")
    return Mesh(np.asarray(devices), (SCENARIO_AXIS,))


def normalize_scenario_mesh(mesh) -> tuple[Mesh | None, int]:
    """(mesh, n_shards) from the ``mesh`` option every scenario-sharded
    constructor accepts: None (single-device), an int ("first n
    devices"), or a prebuilt 1-D Mesh.  Shared so `BatchedGMGSolver` and
    `ElasticityService` can never normalize inconsistently."""
    if isinstance(mesh, int):
        mesh = scenario_mesh(mesh)
    return mesh, (1 if mesh is None else int(mesh.devices.size))


def scenario_spec(ndim: int = 1) -> P:
    """PartitionSpec sharding axis 0 (the scenario axis — or the folded
    scenario*element axis) of an ndim-dimensional array."""
    return P(SCENARIO_AXIS, *(None,) * (max(ndim, 1) - 1))


def scenario_sharding(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    return NamedSharding(mesh, scenario_spec(ndim))


def pin_scenario(tree: Any, mesh: Mesh | None) -> Any:
    """with_sharding_constraint every array leaf of ``tree`` onto the
    scenario mesh along axis 0 (scalars untouched).  No-op when ``mesh``
    is None, so sharded and unsharded code paths stay one code path."""
    if mesh is None:
        return tree

    def pin(x):
        nd = jnp_ndim(x)
        if nd == 0:
            return x
        return jax.lax.with_sharding_constraint(
            x, scenario_sharding(mesh, nd)
        )

    return jax.tree.map(pin, tree)


def device_put_scenario(tree: Any, mesh: Mesh | None) -> Any:
    """device_put every array leaf with axis-0 scenario sharding (a no-op
    for arrays already laid out that way).  Host-side counterpart of
    :func:`pin_scenario` for feeding jitted entry points."""
    if mesh is None:
        return tree

    def put(x):
        nd = jnp_ndim(x)
        if nd == 0:
            return x
        return jax.device_put(x, scenario_sharding(mesh, nd))

    return jax.tree.map(put, tree)


def scenario_row_devices(s: int, n_shards: int) -> np.ndarray:
    """Device index owning each of ``s`` scenario rows under axis-0
    scenario sharding: a 1-D ``NamedSharding`` splits the axis into
    ``n_shards`` contiguous blocks of ``s // n_shards`` rows, so row
    ``r`` lives on device ``r // (s // n_shards)``.  Pure host math (the
    shard-aware chunk policy consumes it every step, so it must not
    touch the device); ``s`` must divide the mesh, exactly as the
    compiled programs require.  The multidevice suite checks this
    against the actual ``Array.sharding`` layout so the two can never
    silently diverge."""
    if n_shards < 1:
        raise ValueError(f"scenario_row_devices: n_shards must be >= 1, got {n_shards}")
    if s % n_shards:
        raise ValueError(
            f"scenario_row_devices: {s} rows do not divide {n_shards} shards"
        )
    return np.arange(s) // max(s // n_shards, 1)


def scenario_layout_mismatches(tree: Any, mesh: Mesh | None) -> list[str]:
    """Tree paths of array leaves NOT carrying axis-0 scenario
    ``NamedSharding`` on ``mesh`` (empty list == correctly laid out).

    The elastic-restore differential asserts on this: after a
    checkpoint restored onto a different device count, every leaf of
    the re-pinned state/prep pytrees must live on the NEW mesh with the
    scenario axis sharded — a silently replicated (or stale-mesh) leaf
    would still compute correctly but defeat the rescale.  With ``mesh``
    None (single-device) any placement is accepted."""
    if mesh is None:
        return []
    bad = []
    for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        nd = jnp_ndim(leaf)
        if nd == 0:
            continue
        sh = getattr(leaf, "sharding", None)
        want = scenario_sharding(mesh, nd)
        if sh is None or not sh.is_equivalent_to(want, nd):
            path = jax.tree_util.keystr(kp)
            bad.append(f"{path}: {sh}")
    return bad


def jnp_ndim(x) -> int:
    return getattr(x, "ndim", np.ndim(x))

# (regex over the tree path, trailing-dims sharding) — first match wins.
# The tuple addresses the *last* len(tuple) dims of the leaf; leading dims
# (stacked layer axis, MoE expert axis, codebook axis) are unsharded by
# left-padding with None — so one rule serves plain, stacked and
# expert-stacked variants of a matrix.
_RULES: list[tuple[str, tuple]] = [
    # --- embeddings / head: vocab over model, d over data (fsdp)
    (r"embed", ("model", "data")),
    (r"lm_head", ("data", "model")),
    # --- attention
    (r"attn.*\['w[qkv]'\]", ("data", "model")),
    (r"attn.*\['b[qkv]'\]", ("model",)),
    (r"attn.*\['wo'\]", ("model", "data")),
    # --- mlp (dense and MoE expert-stacked; E is left-padded to None)
    (r"\['router'\]", (None, None)),
    (r"\['w_gate'\]", ("data", "model")),
    (r"\['w_up'\]", ("data", "model")),
    (r"\['w_down'\]", ("model", "data")),
    # --- ssm / mamba2 / mlstm mixers
    (r"mixer.*\['in_proj'\]", ("data", "model")),
    (r"mixer.*\['out_proj'\]", ("model", "data")),
    (r"mixer.*\['w[qkv]'\]", ("data", "model")),
    # --- xlstm sLSTM
    (r"\['w_in'\]", ("data", "model")),
    (r"\['w_out'\]", ("model", "data")),
]


def act_pspec(mesh_axes: tuple[str, ...]) -> P:
    """Between-blocks residual constraint: batch over dp, sequence over
    'model' (Megatron-SP: the saved scan carries are what this bounds)."""
    dp = tuple(a for a in mesh_axes if a in ("pod", "data"))
    return P(dp, "model", None)


def _spec_for(path: str, leaf, mesh_shape: dict | None = None) -> P:
    nd = getattr(leaf, "ndim", 0)
    # MoE expert weights: true expert parallelism (E over 'model') when the
    # expert count divides the axis — every expert einsum is then local to
    # its shard and the backward has no model-axis partial sums.  Falls
    # through to the d_ff-sharding rules otherwise (e.g. 8 experts on a
    # 16-wide axis).
    if mesh_shape is not None and re.search(r"moe.*\['w_(gate|up|down)'\]", path):
        shape = getattr(leaf, "shape", ())
        e_ax = nd - 3
        if e_ax >= 0 and shape[e_ax] % mesh_shape.get("model", 1) == 0:
            parts = [None] * nd
            parts[e_ax] = "model"
            if shape[e_ax + 1] % mesh_shape.get("data", 1) == 0:
                parts[e_ax + 1] = "data"
            return P(*parts)
    for pat, trailing in _RULES:
        if re.search(pat, path):
            parts = [None] * max(nd - len(trailing), 0) + list(trailing)
            parts = parts[-nd:] if nd else []
            if mesh_shape is not None:
                shape = getattr(leaf, "shape", ())
                parts = [
                    a if (a is None or shape[i] % mesh_shape.get(a, 1) == 0) else None
                    for i, a in enumerate(parts)
                ]
            return P(*parts)
    return P()  # replicated


def param_pspecs(params, mesh=None, tp: bool = True) -> Any:
    """PartitionSpec pytree matching ``params``.

    When ``mesh`` is given, any axis that does not divide its dimension
    evenly is dropped (pjit argument shardings require exact division;
    e.g. an 8-expert tensor cannot ride a 16-wide axis).  ``tp=False``
    drops the 'model' axis from every rule — the pure-DP layout for
    models too small to amortize tensor parallelism (a 16-way TP of a
    125M-param stack pays one activation all-reduce per matmul for
    near-zero compute saved).
    """
    mesh_shape = dict(mesh.shape) if mesh is not None else None
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)

    def drop_tp(spec):
        if tp:
            return spec
        return P(*[
            None if part == "model"
            else (tuple(a for a in part if a != "model") or None)
            if isinstance(part, tuple) else part
            for part in spec
        ])

    specs = [
        drop_tp(_spec_for(jax.tree_util.keystr(kp), leaf, mesh_shape))
        for kp, leaf in flat
    ]
    return jax.tree.unflatten(jax.tree.structure(params), specs)


def state_pspecs(state, mesh=None, tp: bool = True) -> Any:
    """Specs for a TrainState: moments mirror params; counters replicated."""
    from repro.train.trainer import TrainState

    pspec = param_pspecs(state.params, mesh, tp)
    return TrainState(
        params=pspec,
        opt_state={
            "m": param_pspecs(state.opt_state["m"], mesh, tp),
            "v": param_pspecs(state.opt_state["v"], mesh, tp),
            "step": P(),
        },
        step=P(),
    )


def batch_pspec(mesh_axes: tuple[str, ...], batch: Any) -> Any:
    """Shard the global-batch dim over the data(+pod) axes."""
    dp = tuple(a for a in mesh_axes if a in ("pod", "data"))

    def spec(leaf):
        nd = getattr(leaf, "ndim", 0)
        return P(dp, *(None,) * (nd - 1))

    return jax.tree.map(spec, batch)


def decode_state_pspecs(state, mesh_axes: tuple[str, ...], cfg=None,
                        mesh=None) -> Any:
    """KV caches / recurrent states: batch over data(+pod), heads (or the
    head_dim fallback when the kv-head count doesn't divide the axis)
    over 'model'.

    A 32k decode cache is the dominant HBM resident at serving time
    (e.g. olmoe at B=128: 550 GB of kv) — it MUST shard over the model
    axis, exactly like the attention heads that consume it.  Stacked-
    family states (attn kv / mamba2) carry a leading layer axis, so
    batch is axis 1; xlstm states are per-layer python lists with batch
    at axis 0.
    """
    dp = tuple(a for a in mesh_axes if a in ("pod", "data"))
    mesh_shape = dict(mesh.shape) if mesh is not None else {}
    model_size = mesh_shape.get("model", 1)
    batch_axis = 0 if (cfg is not None and cfg.block_pattern == "xlstm") else 1

    def spec(leaf):
        nd = getattr(leaf, "ndim", 0)
        shape = getattr(leaf, "shape", ())
        if nd <= batch_axis:
            return P(*(None,) * nd)
        parts: list = [None] * nd
        if shape[batch_axis] % max(int(np.prod([mesh_shape.get(a, 1) for a in dp])), 1) == 0:
            parts[batch_axis] = dp
        # Shard axis 2 over 'model' first: for kv caches (L, B, S, K, hd)
        # that is the *sequence* axis — flash-decode layout: the score dot
        # keeps S as an output dim (no contraction resharding; softmax and
        # the o-reduction psum over the model axis), and S always divides
        # the mesh unlike the kv-head count.  For mamba2 states
        # (L, B, H, N, P) axis 2 is the head axis — also the right one.
        # Fall back to trailing axes when axis 2 doesn't divide.
        if nd >= 4 and model_size > 1:
            for ax in (2, nd - 2, nd - 1):
                if ax == batch_axis:
                    continue
                if shape[ax] % model_size == 0 and shape[ax] >= model_size:
                    parts[ax] = "model"
                    break
        return P(*parts)

    return jax.tree.map(spec, state)
