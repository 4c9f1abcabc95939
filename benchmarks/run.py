"""Benchmark harness entry point: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--only table7,fig5]

Writes combined markdown to stdout (tee to bench_output.txt) and CSVs to
benchmarks/out/.
"""

from __future__ import annotations

import argparse
import csv
import os
import time

import jax

# FEM comparisons run in f64 (the paper's CPU precision); LM benches pass
# explicit f32 dtypes and are unaffected.
jax.config.update("jax_enable_x64", True)

SUITES = ["table3", "table4", "table5", "table7", "fig5", "fig6", "lm"]


def _write_csv(name: str, rows: list[dict]):
    if not rows:
        return
    os.makedirs("benchmarks/out", exist_ok=True)
    cols = list(rows[0].keys())
    with open(f"benchmarks/out/{name}.csv", "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=cols, extrasaction="ignore")
        w.writeheader()
        w.writerows(rows)


def _lm_microbench(fast: bool) -> list[dict]:
    """Token throughput of the reduced LM configs (train + decode) —
    the framework-side sanity benchmark."""
    import dataclasses

    import jax.numpy as jnp

    from benchmarks.common import fmt_table, time_fn
    from repro.configs.base import ShapeConfig, get_reduced
    from repro.data.pipeline import make_batch
    from repro.optim.adamw import AdamWConfig
    from repro.train.trainer import make_train_step, train_state_init

    archs = ["qwen3_17b", "mixtral_8x7b", "zamba2_27b"]
    if not fast:
        archs += ["xlstm_125m", "musicgen_medium"]
    shape = ShapeConfig("bench", "train", 128, 4)
    rows = []
    for arch in archs:
        cfg = dataclasses.replace(get_reduced(arch), dtype="float32",
                                  chunk_size=32)
        state = train_state_init(jax.random.PRNGKey(0), cfg)
        batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, shape, 0).items()}
        step = jax.jit(make_train_step(cfg, AdamWConfig()))
        t = time_fn(lambda s, b: step(s, b)[1]["loss"], state, batch,
                    warmup=1, repeats=2)
        toks = shape.seq_len * shape.global_batch
        rows.append({"arch": arch, "tokens_per_s": toks / t,
                     "step_time_s": t})
    print(fmt_table(rows, ["arch", "step_time_s", "tokens_per_s"],
                    title="LM reduced-config train-step microbench (CPU)"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller p-range / fewer cells")
    ap.add_argument("--only", default=None,
                    help=f"comma list from {SUITES}")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    only = args.only.split(",") if args.only else SUITES

    t0 = time.time()
    print(f"# Benchmark run (devices: {jax.devices()})\n")
    if "table5" in only:
        from benchmarks import table5_flops

        _write_csv("table5", table5_flops.main(args.fast))
        print()
    if "table7" in only:
        from benchmarks import table7_ablation

        _write_csv("table7", table7_ablation.main(args.fast))
        print()
    if "fig5" in only:
        from benchmarks import fig5_throughput

        _write_csv("fig5", fig5_throughput.main(args.fast))
        print()
    if "table3" in only:
        from benchmarks import table3_preconditioners

        _write_csv("table3", table3_preconditioners.main(args.fast))
        print()
    if "table4" in only:
        from benchmarks import table4_solver

        _write_csv("table4", table4_solver.main(args.fast))
        print()
    if "fig6" in only:
        from benchmarks import fig6_roofline

        _write_csv("fig6", fig6_roofline.main(args.fast))
        print()
    if "lm" in only:
        _write_csv("lm_micro", _lm_microbench(args.fast))
    print(f"\ntotal benchmark wall time: {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
