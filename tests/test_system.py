"""End-to-end system behaviour: training convergence, checkpoint/restart
determinism, gradient-compression training, the FEM solve driver, and
the dry-run cell machinery on the local device."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.configs.base import ShapeConfig, get_reduced
from repro.launch.train import train_loop
from repro.optim.adamw import AdamWConfig


def _cfg():
    return dataclasses.replace(
        get_reduced("qwen3_17b"), dtype="float32", n_layers=2, d_model=64,
        d_ff=128, vocab=128, chunk_size=16,
    )


SHAPE = ShapeConfig("sys", "train", 64, 4)


def test_training_reduces_loss(tmp_path):
    cfg = _cfg()
    opt = AdamWConfig(lr=1e-3, total_steps=60, warmup_steps=5)
    _, hist = train_loop(cfg, SHAPE, steps=60, opt=opt, log_every=5)
    first = hist[0]["loss"]
    last = np.mean([h["loss"] for h in hist[-3:]])
    assert last < first - 0.2, (first, last)


def test_checkpoint_restart_bit_identical(tmp_path):
    """Train 12 steps straight vs 6 + kill + resume 6: identical loss."""
    cfg = _cfg()
    opt = AdamWConfig(lr=1e-3, total_steps=12, warmup_steps=2)

    _, hist_ref = train_loop(cfg, SHAPE, steps=12, opt=opt, log_every=1)

    d = str(tmp_path / "ck")
    train_loop(cfg, SHAPE, steps=6, ckpt_dir=d, ckpt_every=6, opt=opt,
               log_every=1)
    _, hist_resumed = train_loop(cfg, SHAPE, steps=12, ckpt_dir=d,
                                 ckpt_every=6, opt=opt, log_every=1)
    ref_last = [h for h in hist_ref if h["step"] == 12][0]["loss"]
    res_last = [h for h in hist_resumed if h["step"] == 12][0]["loss"]
    assert res_last == pytest.approx(ref_last, rel=1e-5), (ref_last, res_last)


def test_training_with_gradient_compression():
    """int8 error-feedback compression still trains (loss decreases)."""
    from repro.distributed.compression import make_error_feedback_transform

    cfg = _cfg()
    init_fn, tfm = make_error_feedback_transform("int8")
    residual = {}

    def grad_transform(grads):
        # stateless within-step hook: apply plain int8 (no feedback) —
        # the feedback variant is exercised in test_distributed.py
        from repro.distributed.compression import int8_compress, int8_decompress

        return jax.tree.map(
            lambda g: int8_decompress(*int8_compress(g)).astype(g.dtype), grads
        )

    opt = AdamWConfig(lr=1e-3, total_steps=40, warmup_steps=5)
    from repro.train.trainer import make_train_step, train_state_init
    from repro.data.pipeline import TokenPipeline

    state = train_state_init(jax.random.PRNGKey(0), cfg)
    step = jax.jit(make_train_step(cfg, opt, grad_transform=grad_transform))
    pipe = TokenPipeline(cfg, SHAPE, seed=0)
    losses = []
    for _ in range(40):
        batch = {k: jnp.asarray(v) for k, v in next(pipe).items()}
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    pipe.close()
    assert np.mean(losses[-5:]) < losses[0] - 0.2


def test_solve_driver_all_assemblies_converge():
    from repro.launch.solve import solve_beam

    for a in ("paop", "paop_pallas"):
        rep = solve_beam(2, n_h_refine=0, assembly=a, rel_tol=1e-8)
        assert rep.final_rel_norm < 1e-8, a


def test_local_cell_lowering():
    """Cell machinery lowers + compiles on the single local device
    (1x1 mesh) — catches arg/sharding structure bugs without the 512-way
    dry run."""
    from repro.launch.cells import build_cell

    mesh = jax.make_mesh(
        (1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
    import repro.configs.base as base

    small_shape = ShapeConfig("train_4k", "train", 128, 2)
    with _patched_shapes({"train_4k": small_shape}):
        cell = build_cell("qwen3_17b", "train_4k", mesh)
        compiled = cell.lower(mesh).compile()
        assert compiled.cost_analysis() is not None


class _patched_shapes:
    def __init__(self, shapes):
        self.shapes = shapes

    def __enter__(self):
        import repro.configs.base as base

        self.saved = dict(base.SHAPES)
        base.SHAPES.update(self.shapes)

    def __exit__(self, *a):
        import repro.configs.base as base

        base.SHAPES.clear()
        base.SHAPES.update(self.saved)


def test_jaxpr_cost_scan_awareness():
    """The roofline's cost walker must multiply scan bodies by length."""
    from repro.launch.jaxpr_cost import cost_of_fn

    W = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((64,), jnp.float32)

    def once(w, x):
        return w @ x

    def scanned(w, x):
        def body(c, _):
            return w @ c, None

        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    c1 = cost_of_fn(once, W, x)
    c10 = cost_of_fn(scanned, W, x)
    assert c10.flops == pytest.approx(10 * c1.flops, rel=1e-6)


def test_roofline_collective_parser():
    from repro.launch.roofline import collective_bytes

    hlo = """
  %ag = f32[4,256]{1,0} all-gather(%x), replica_groups=[8,4]<=[32], dimensions={1}
  %ar = (f32[128]{0}) all-reduce(%y), replica_groups={{0,1,2,3}}, to_apply=%add
  %cp = bf16[64,64]{1,0} collective-permute(%z), source_target_pairs={{0,1}}
"""
    out = collective_bytes(hlo)
    ag_r = 4 * 256 * 4
    ar_r = 128 * 4
    cp_r = 64 * 64 * 2
    assert out["operand_bytes"] == pytest.approx(ag_r / 4 + ar_r + cp_r)
    assert out["link_bytes"] == pytest.approx(
        ag_r * 3 / 4 + 2 * ar_r * 3 / 4 + cp_r
    )
