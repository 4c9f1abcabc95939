"""Batched-solve throughput: scenarios/sec of the ElasticityService vs
the sequential solve_beam driver (p=2, refine=1 beam benchmark).

For each batch size B the service solves one warm generation of B mixed
scenarios (the first call pays hierarchy build + compile; the timed
calls reuse the cached compiled program, which is the steady-state
serving regime).  The sequential baseline is solve_beam called once per
scenario — it re-builds the hierarchy and re-traces every call, exactly
what the service amortizes.

``--continuous`` instead compares the two scheduling policies on a
mixed-tolerance workload (alternating loose/tight rel_tol): generational
batching is gated by the slowest row of every generation, while
continuous batching retires loose rows early, refills their slots from
the queue, and lets the draining tail shrink to smaller padding buckets.
Reports throughput and per-request tail latency for both, plus the
scheduler-stats columns (chunks dispatched, mean chunk length, wasted
iterations) of the chosen ``--chunk-policy`` — fixed, adaptive
(cadence-driven chunk lengths) or shard-adaptive (per-device cadence +
placement); numerics are identical across policies, so the columns
isolate pure scheduling effects (see docs/SCHEDULING.md).

``--devices N`` shards the scenario axis over N devices (forcing N
virtual XLA host devices on CPU — set before backend init, which is why
the heavy imports live inside the functions).  Throughput always counts
REAL scenarios only: padding rows added for bucket or device alignment
ride along in ``SolveReport.padded_rows`` and are excluded from the
scenarios/sec math, so ``--devices 8`` numbers are honest.

``--heterogeneous`` swaps the attribute-dict materials for per-element
``(lam_e, mu_e)`` lognormal random fields (a 4-field vocabulary, so the
continuous engine's digest-keyed prep-row reuse still engages).  This is
the workload the per-element material path exists for; comparing a run
with and without the flag shows the cost of genuinely heterogeneous
coefficients is the same compiled program — materials are runtime
arguments either way.

    PYTHONPATH=src python -m benchmarks.batched_throughput [--quick]
    PYTHONPATH=src python -m benchmarks.batched_throughput --continuous
    PYTHONPATH=src python -m benchmarks.batched_throughput \
        --continuous --chunk-policy adaptive
    PYTHONPATH=src python -m benchmarks.batched_throughput --devices 8 --continuous
    PYTHONPATH=src python -m benchmarks.batched_throughput --heterogeneous --quick
"""

from __future__ import annotations

import argparse
import time

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402

from benchmarks.common import fmt_table  # noqa: E402

P, REFINE = 2, 1


def _materials_for(i: int, hetero: bool):
    """Request i's materials: attribute dicts by default, or per-element
    lognormal random fields (4-seed vocabulary) with --heterogeneous."""
    if not hetero:
        return {1: (50.0 + 5 * (i % 3), 50.0), 2: (1.0 + 0.5 * (i % 2), 1.0)}
    from repro.fem.mesh import beam_hex
    from repro.launch.serve_solve import make_material_field

    return make_material_field("lognormal:11", beam_hex(), REFINE, i)


def make_requests(n: int, rel_tol: float = 1e-6, hetero: bool = False):
    from repro.serve.elasticity_service import SolveRequest

    return [
        SolveRequest(
            p=P,
            refine=REFINE,
            materials=_materials_for(i, hetero),
            traction=(0.0, 0.0, -1e-2 * (1 + 0.1 * (i % 4))),
            rel_tol=rel_tol,
        )
        for i in range(n)
    ]


def _real_throughput(reports, dt: float) -> float:
    """Scenarios/sec over REAL requests.  ``reports`` has one entry per
    real request by construction (padding is never surfaced) — guard that
    invariant here so a padding-accounting regression can't silently
    inflate --devices numbers."""
    assert all(r.padded_rows >= r.batch_size > 0 for r in reports)
    return len(reports) / dt


def bench_batched(batch: int, repeats: int, mesh=None, hetero: bool = False) -> dict:
    from repro.serve.elasticity_service import ElasticityService

    service = ElasticityService(max_batch=batch, mesh=mesh)
    # Warm: builds the hierarchy and compiles the batched program.
    t0 = time.perf_counter()
    service.solve(make_requests(batch, hetero=hetero))
    t_warm = time.perf_counter() - t0
    # Steady state: same key -> cached program, setup must be ~0.
    times, setups, pad = [], [], 0
    for _ in range(repeats):
        reqs = make_requests(batch, hetero=hetero)
        t0 = time.perf_counter()
        reports = service.solve(reqs)
        times.append(time.perf_counter() - t0)
        setups.append(reports[0].t_setup)
        pad = max(pad, reports[0].padded_rows)
        assert all(r.converged for r in reports)
        assert len(reports) == batch  # padding rows never surfaced
    t = float(np.median(times))
    return {
        "batch": batch,
        "padded_rows": pad,
        "scenarios_per_s": batch / t,
        "t_generation_s": t,
        "t_warm_s": t_warm,
        "t_setup_cached_s": float(np.median(setups)),
    }


def bench_sequential(n: int) -> dict:
    from repro.launch.solve import solve_beam

    t0 = time.perf_counter()
    for req in make_requests(n):
        rep = solve_beam(
            req.p,
            req.refine,
            assembly="paop",
            rel_tol=req.rel_tol,
            materials=req.materials,
            traction=req.traction,
        )
        assert rep.final_rel_norm < req.rel_tol
    t = time.perf_counter() - t0
    return {
        "batch": "sequential",
        "padded_rows": n,
        "scenarios_per_s": n / t,
        "t_generation_s": t / n,
        "t_warm_s": 0.0,
        "t_setup_cached_s": float("nan"),
    }


def make_mixed_tol_requests(
    n: int, loose: float = 1e-4, tight: float = 1e-10, hetero: bool = False
):
    """Mixed-tolerance workload: one tight-tolerance request per four
    loose ones, with varied materials and tractions — the serving regime
    where a minority of slow scenarios gates every generation while the
    loose majority could have streamed through the freed slots."""
    from repro.serve.elasticity_service import SolveRequest

    return [
        SolveRequest(
            p=P,
            refine=REFINE,
            materials=_materials_for(i, hetero),
            traction=(0.0, 2e-3 * (i % 2), -1e-2 * (1 + 0.1 * (i % 4))),
            rel_tol=tight if i % 4 == 0 else loose,
        )
        for i in range(n)
    ]


def _latency_percentiles(latencies: list[float]) -> tuple[float, float]:
    """p50/p95 through the obs histogram quantile estimator — the SAME
    implementation the service's ``latency_summary()`` reports, so the
    benchmark's tail-latency columns and the serving summary can never
    drift apart (this replaced an ad-hoc np.percentile on raw lists)."""
    from repro.obs.metrics import Histogram, default_latency_edges

    h = Histogram(default_latency_edges())
    for v in latencies:
        h.observe(v)
    return h.quantile(0.5), h.quantile(0.95)


def _time_generational(service, n: int, hetero: bool = False):
    reqs = make_mixed_tol_requests(n, hetero=hetero)
    t0 = time.perf_counter()
    reports = service.solve(reqs)
    dt = time.perf_counter() - t0
    assert all(r.converged for r in reports)
    assert all(r.final_rel_norm <= r.request.rel_tol for r in reports)
    assert len(reports) == n  # padding rows never surfaced
    # A request is done when its generation retires; its latency is the
    # cumulative time of all generations up to and including its own
    # (generations of one key run back-to-back).
    gen_t = {r.generation: r.t_solve for r in reports}
    cum = np.cumsum([gen_t[g] for g in sorted(gen_t)])
    return dt, reports, [float(cum[r.generation]) for r in reports]


def _time_continuous(service, n: int, hetero: bool = False):
    reqs = make_mixed_tol_requests(n, hetero=hetero)
    before = {
        k: service.stats[k]
        for k in ("chunks", "chunk_iters_dispatched", "wasted_iters")
    }
    t0 = time.perf_counter()
    reports = service.solve_continuous(reqs)
    dt = time.perf_counter() - t0
    assert all(r.converged for r in reports)
    assert all(r.final_rel_norm <= r.request.rel_tol for r in reports)
    assert len(reports) == n  # padding rows never surfaced
    delta = {k: service.stats[k] - v for k, v in before.items()}
    sched = {
        "chunks": delta["chunks"],
        "mean_chunk": (
            delta["chunk_iters_dispatched"] / delta["chunks"]
            if delta["chunks"]
            else 0.0
        ),
        "wasted_iters": delta["wasted_iters"],
    }
    # admission -> retirement latency per request
    return dt, reports, [r.t_solve for r in reports], sched


def run_continuous(
    batch: int = 16,
    n_requests: int | None = None,
    repeats: int = 3,
    chunk_iters: int = 8,
    chunk_policy: str = "fixed",
    mesh=None,
    hetero: bool = False,
    precision: str = "f64",
) -> list[dict]:
    """Continuous vs generational on the mixed-tolerance workload.

    ``chunk_policy`` selects the continuous engine's chunk scheduler
    (fixed / adaptive / shard-adaptive — numerics are identical, so the
    comparison isolates pure scheduling effects), and the continuous row
    carries the scheduler counters: chunks dispatched, mean chosen chunk
    length, and wasted iterations (slot-iterations near-converged rows
    idled inside chunks).

    The repeats of the two policies are interleaved in time and each
    policy reports its best repeat: on a shared/throttled CPU a transient
    co-tenant spike would otherwise land on one policy's block and
    dominate the ratio."""
    from repro.serve.elasticity_service import ElasticityService

    n = 2 * batch if n_requests is None else n_requests
    svc_gen = ElasticityService(
        max_batch=batch, mesh=mesh, precision=precision
    )
    svc_cont = ElasticityService(
        max_batch=batch, chunk_iters=chunk_iters,
        chunk_policy=chunk_policy, mesh=mesh, precision=precision,
    )
    # Warm: hierarchy build + one compile per (bucket, reset-flag) the
    # workload visits (16, 8, ... as the continuous tail drains).
    svc_gen.solve(make_mixed_tol_requests(n, hetero=hetero))
    svc_cont.solve_continuous(make_mixed_tol_requests(n, hetero=hetero))
    runs_gen, runs_cont = [], []
    for _ in range(repeats):
        runs_gen.append(
            _time_generational(svc_gen, n, hetero=hetero) + (None,)
        )
        runs_cont.append(_time_continuous(svc_cont, n, hetero=hetero))
    rows = []
    for policy, runs in (
        ("generational", runs_gen),
        (f"continuous({chunk_policy}, k={chunk_iters})", runs_cont),
    ):
        # throughput AND latencies from the same (best) repeat
        t, reports, lat, sched = min(runs, key=lambda r: r[0])
        p50, p95 = _latency_percentiles(lat)
        row = {
            "policy": policy,
            "scenarios_per_s": _real_throughput(reports, t),
            "t_workload_s": t,
            "latency_p50_s": p50,
            "latency_p95_s": p95,
            "chunks": "-",
            "mean_chunk": "-",
            "wasted_iters": "-",
        }
        if sched is not None:
            row["chunks"] = sched["chunks"]
            row["mean_chunk"] = round(sched["mean_chunk"], 2)
            row["wasted_iters"] = sched["wasted_iters"]
        rows.append(row)
    rows[1]["speedup_vs_generational"] = (
        rows[1]["scenarios_per_s"] / rows[0]["scenarios_per_s"]
    )
    return rows


def run(
    fast: bool = False, quick: bool = False, mesh=None, hetero: bool = False
) -> list[dict]:
    batches = [1, 4] if quick else ([1, 4, 16] if fast else [1, 4, 16, 64])
    n_seq = 2 if quick else 4
    repeats = 1 if quick else 3
    # The sequential solve_beam baseline only speaks attribute dicts
    # (its hierarchy builder takes one dict for every level), so under
    # --heterogeneous it would be a DIFFERENT workload — comparing the
    # two would conflate material-form cost with conditioning.  Honest
    # math: no sequential row and no speedup column in that mode.
    rows = [] if hetero else [bench_sequential(n_seq)]
    seq_rate = rows[0]["scenarios_per_s"] if rows else None
    for b in batches:
        row = bench_batched(b, repeats, mesh=mesh, hetero=hetero)
        if seq_rate is not None:
            row["speedup_vs_sequential"] = row["scenarios_per_s"] / seq_rate
        rows.append(row)
    return rows


SERVING_SCHEMA = "repro.bench.serving/v1"


def write_serving_artifact(rows: list[dict], args, out: str) -> None:
    """BENCH_serving.json: the continuous-vs-generational comparison as
    a schema-versioned artifact (``repro.bench.serving/v1``), validated
    against the checked-in schema BEFORE writing.  Scheduler columns are
    null for the generational row (the table prints '-')."""
    import json
    import os

    from repro.obs.schema import validate_json

    def _num(v):
        return None if v == "-" else v

    doc = {
        "schema": SERVING_SCHEMA,
        "benchmark": "batched_throughput",
        "generated_unix": time.time(),
        "workload": {
            "p": P,
            "refine": REFINE,
            "batch": args.batch,
            "n_requests": args.n_requests or 2 * args.batch,
            "chunk_iters": args.chunk_iters,
            "chunk_policy": args.chunk_policy,
            "devices": args.devices or 1,
            "heterogeneous": bool(args.heterogeneous),
            "repeats": args.repeats,
            "precision_policy": args.precision,
        },
        "rows": [
            {
                **{k: v for k, v in r.items()},
                "chunks": _num(r["chunks"]),
                "mean_chunk": _num(r["mean_chunk"]),
                "wasted_iters": _num(r["wasted_iters"]),
            }
            for r in rows
        ],
    }
    schema_path = os.path.join(
        os.path.dirname(__file__), "schemas", "bench_serving.schema.json"
    )
    with open(schema_path) as f:
        validate_json(doc, json.load(f))
    with open(out, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: batches {1, 4}, single repeat")
    ap.add_argument("--fast", action="store_true", help="skip batch 64")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous vs generational batching on a "
                         "mixed-tolerance workload")
    ap.add_argument("--batch", type=int, default=16,
                    help="max_batch for --continuous (default 16)")
    ap.add_argument("--n-requests", type=int, default=None,
                    help="workload size for --continuous (default 2*batch)")
    ap.add_argument("--chunk-iters", type=int, default=8,
                    help="PCG iterations per continuous chunk (fixed "
                         "policy) / no-history fallback (adaptive)")
    ap.add_argument("--chunk-policy", default="fixed",
                    choices=["fixed", "adaptive", "shard-adaptive"],
                    help="chunk scheduler for --continuous (identical "
                         "numerics; scheduler-stats columns show the "
                         "chunks/waste difference)")
    ap.add_argument("--precision", default="f64",
                    choices=["f64", "f32", "mixed", "mixed-bf16"],
                    help="precision policy both services run the "
                         "workload under (recorded in the artifact's "
                         "workload block)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--devices", type=int, default=None,
                    help="shard the scenario axis over N devices (forces "
                         "N virtual host devices on CPU)")
    ap.add_argument("--heterogeneous", action="store_true",
                    help="per-element lognormal (lam_e, mu_e) random "
                         "fields instead of attribute dicts")
    ap.add_argument("--bench-out", default=None, metavar="PATH",
                    help="with --continuous: write the comparison as a "
                         "schema-versioned BENCH_serving.json artifact "
                         "(validated before writing)")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    # Env must be set before anything touches the jax backend.
    from repro.distributed.sharding import (
        force_host_device_count,
        scenario_mesh,
    )

    force_host_device_count(args.devices)
    mesh = None
    if args.devices is not None:
        mesh = scenario_mesh(args.devices)
        print(f"scenario mesh: {mesh.devices.size} devices "
              f"({jax.device_count()} visible)")

    mats = "lognormal fields" if args.heterogeneous else "attribute dicts"
    if args.continuous:
        rows = run_continuous(
            batch=args.batch,
            n_requests=args.n_requests,
            repeats=args.repeats,
            chunk_iters=args.chunk_iters,
            chunk_policy=args.chunk_policy,
            mesh=mesh,
            hetero=args.heterogeneous,
            precision=args.precision,
        )
        print(
            fmt_table(
                rows,
                [
                    "policy",
                    "scenarios_per_s",
                    "t_workload_s",
                    "latency_p50_s",
                    "latency_p95_s",
                    "chunks",
                    "mean_chunk",
                    "wasted_iters",
                    "speedup_vs_generational",
                ],
                title=(
                    f"Continuous vs generational batching "
                    f"(mixed tolerances, {mats}, batch={args.batch}, "
                    f"p={P}, refine={REFINE}, "
                    f"devices={args.devices or 1}, CPU)"
                ),
            )
        )
        if args.bench_out:
            write_serving_artifact(rows, args, args.bench_out)
            print(f"artifact -> {args.bench_out}")
        return
    rows = run(
        fast=args.fast, quick=args.quick, mesh=mesh,
        hetero=args.heterogeneous,
    )
    cols = [
        "batch",
        "padded_rows",
        "scenarios_per_s",
        "t_generation_s",
        "t_warm_s",
        "t_setup_cached_s",
    ]
    if not args.heterogeneous:
        # vs-sequential comparison only exists for the dict workload the
        # sequential baseline can actually run.
        cols.append("speedup_vs_sequential")
    print(
        fmt_table(
            rows,
            cols,
            title=(
                f"Batched GMG-PCG throughput ({mats}, p={P}, "
                f"refine={REFINE}, devices={args.devices or 1}, CPU)"
            ),
        )
    )


if __name__ == "__main__":
    main()
