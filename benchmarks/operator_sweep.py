"""Operator-apply throughput sweep -> BENCH_operator_sweep.json.

The first artifact of the repo's perf trajectory: measured DoF/s of the
*batched* elasticity operator (S scenarios' materials folded into the
element axis — the apply the serving stack actually runs) swept over
p in {1, 2, 4, 6, 8} and over the Pallas lanes: per p, one ``paop``
einsum baseline row plus one ``paop_pallas`` row per requested lane
(``interpret`` and ``compiled`` by default).  Every row carries the
analytic models it is judged against — the paper-kernel FLOP count, the
PAop streaming-bytes model, the resulting operational intensity, and
the row's placement on the TPU v5e roofline
(``repro.launch.roofline.place_measured``) — plus the lane that
*actually ran*: ``pallas_lane`` is the operator's resolved lane, so a
``compiled`` request on a backend that cannot lower Pallas is recorded
as the interpret run it really was (``lane_requested`` keeps the ask).

``--precision`` sweeps the measurement over precision policies
(``f64`` by default; add ``f32`` / ``mixed`` / ``mixed-bf16`` for the
mixed-precision trajectory).  Each row is measured at its policy's
``precond_dtype`` — the dtype the V-cycle element kernel streams, which
is where the bandwidth-bound bytes live — and records
``precision_policy`` so the artifact carries the axis.

Absolute numbers on this container are CPU-sized — tiny, and that is
fine: the artifact is schema-versioned
(``repro.bench.operator_sweep/v3``, schema checked into
``benchmarks/schemas/``) so successive perf PRs append comparable
points, and ``fig6_roofline`` places the measured rows next to the
analytic OI trajectory.  The emitted document is validated against the
checked-in schema BEFORE being written — a drifting field name fails the
producer, not just the CI consumer.

    PYTHONPATH=src python -m benchmarks.operator_sweep --smoke
    PYTHONPATH=src python -m benchmarks.operator_sweep \
        --out BENCH_operator_sweep.json --batch 4 --precision f64 f32
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import time

import jax

jax.config.update("jax_enable_x64", True)

from benchmarks.common import fmt_table  # noqa: E402

SCHEMA = "repro.bench.operator_sweep/v3"
SCHEMA_PATH = os.path.join(
    os.path.dirname(__file__), "schemas", "bench_operator_sweep.schema.json"
)

# Refinement per p for the full sweep: roughly equalized element work at
# batch 4 (the fig5 FIXED_DOF idea, one level coarser since the scenario
# fold multiplies the element count).
SWEEP_REFINE = {1: 2, 2: 1, 4: 1, 6: 0, 8: 0}

# Lanes swept per p for the paop_pallas assembly (requested lanes; each
# row also records the lane that actually ran).
SWEEP_LANES = ("interpret", "auto")


def run(
    ps=(1, 2, 4, 6, 8),
    batch: int = 4,
    refine: int | None = None,
    repeats: int = 3,
    min_time_s: float = 0.05,
    smoke: bool = False,
    lanes=SWEEP_LANES,
    precisions=("f64",),
) -> list[dict]:
    """Artifact rows: per (p, precision policy), one ``paop`` baseline
    plus one ``paop_pallas`` row per requested lane (measured + models +
    roofline placement).  ``--smoke`` shrinks to refine 0 / batch 2 /
    single short repeat — same code path, same schema, CI-sized."""
    from repro.launch.roofline import place_measured
    from repro.obs.throughput import operator_throughput

    cells = []
    for p in ps:
        r = 0 if smoke else (refine if refine is not None else SWEEP_REFINE[p])
        for prec in precisions:
            cells.append((p, r, "paop", None, prec))
            for lane in lanes:
                cells.append((p, r, "paop_pallas", lane, prec))

    rows = []
    for p, r, assembly, lane, prec in cells:
        row = operator_throughput(
            p,
            r,
            2 if smoke else batch,
            assembly=assembly,
            pallas_lane=lane,
            precision=prec,
            repeats=1 if smoke else repeats,
            min_time_s=0.0 if smoke else min_time_s,
        )
        placed = place_measured(
            flops_per_apply=row["flops_per_apply"],
            bytes_per_apply=row["bytes_per_apply"],
            t_apply_s=row["t_apply_s"],
        )
        row["v5e_roof_fraction"] = placed.fraction
        row["v5e_bound"] = placed.bound
        rows.append(row)
    return rows


def make_document(rows: list[dict], smoke: bool) -> dict:
    from repro.kernels.pa_elasticity.ops import resolve_lane
    from repro.launch.roofline import V5E

    auto_lane = resolve_lane("auto")
    return {
        "schema": SCHEMA,
        "benchmark": "operator_sweep",
        "generated_unix": time.time(),
        "smoke": smoke,
        "host": {
            "platform": platform.platform(),
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
            "pallas_lane_auto": auto_lane,
            "pallas_interpret": auto_lane == "interpret",
            "x64": True,
        },
        "target_hw": {
            "name": V5E.name,
            "peak_flops": V5E.peak_flops,
            "hbm_bw": V5E.hbm_bw,
        },
        "rows": rows,
    }


def write_artifact(doc: dict, out: str) -> None:
    """Self-validate against the checked-in schema, then write."""
    from repro.obs.schema import validate_json

    with open(SCHEMA_PATH) as f:
        validate_json(doc, json.load(f))
    with open(out, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--p", type=int, nargs="+", default=[1, 2, 4, 6, 8])
    ap.add_argument("--batch", type=int, default=4,
                    help="scenarios folded into the batched operator")
    ap.add_argument("--lanes", nargs="+", default=list(SWEEP_LANES),
                    choices=["auto", "compiled", "interpret"],
                    help="requested paop_pallas lanes swept per p (rows "
                         "record the lane that actually ran)")
    ap.add_argument("--precision", nargs="+", default=["f64"],
                    choices=["f64", "f32", "mixed", "mixed-bf16"],
                    help="precision policies swept per p (each row is "
                         "measured at the policy's precond_dtype — the "
                         "bytes the V-cycle element kernel streams)")
    ap.add_argument("--refine", type=int, default=None,
                    help="override the per-p refinement map")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized: refine 0, batch 2, one short repeat")
    ap.add_argument("--out", default="BENCH_operator_sweep.json",
                    help="artifact path (schema-validated before writing)")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    rows = run(
        ps=tuple(args.p),
        batch=args.batch,
        refine=args.refine,
        repeats=args.repeats,
        smoke=args.smoke,
        lanes=tuple(args.lanes),
        precisions=tuple(args.precision),
    )
    print(fmt_table(
        rows,
        ["p", "assembly", "pallas_lane", "precision_policy", "refine",
         "batch", "dofs",
         "t_apply_s", "dofs_per_s", "gbytes_per_s", "oi_model",
         "v5e_roof_fraction", "v5e_bound"],
        title=(
            "Batched operator apply throughput "
            f"({'smoke, ' if args.smoke else ''}lane column is the lane "
            "that ran — trajectory artifact, not absolute perf)"
        ),
    ))
    doc = make_document(rows, smoke=args.smoke)
    write_artifact(doc, args.out)
    print(f"artifact -> {args.out} (schema {SCHEMA})")


if __name__ == "__main__":
    main()
