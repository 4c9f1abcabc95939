"""CLI for the batched elasticity solve service.

Generates a mixed multi-scenario workload (varying materials, tractions
and tolerances, optionally across several discretizations), drives it
through :class:`repro.serve.elasticity_service.ElasticityService`, and
prints per-request reports plus aggregate throughput.

Usage:
    PYTHONPATH=src python -m repro.launch.serve_solve \
        --n-requests 16 --max-batch 8 --p 2 --refine 1
    PYTHONPATH=src python -m repro.launch.serve_solve --p 1 2  # mixed keys
    PYTHONPATH=src python -m repro.launch.serve_solve --continuous
    PYTHONPATH=src python -m repro.launch.serve_solve \
        --continuous --chunk-policy adaptive   # cadence-driven chunks
    PYTHONPATH=src python -m repro.launch.serve_solve \
        --continuous --devices 4 --chunk-policy shard-adaptive
    PYTHONPATH=src python -m repro.launch.serve_solve --devices 4  # sharded
    PYTHONPATH=src python -m repro.launch.serve_solve \
        --material-field lognormal:7   # heterogeneous per-element fields
    PYTHONPATH=src python -m repro.launch.serve_solve --continuous \
        --metrics-out metrics.prom --trace-out trace.json  # observability
    PYTHONPATH=src python -m repro.launch.serve_solve --continuous \
        --checkpoint-dir ckpt --checkpoint-every 2   # fault tolerance
    PYTHONPATH=src python -m repro.launch.serve_solve --continuous \
        --checkpoint-dir ckpt --resume               # restart after a kill

``--material-field {graded,checkerboard,lognormal[:seed]}`` replaces the
attribute-dict materials with per-element ``(lam_e, mu_e)`` coefficient
fields on the fine mesh — graded stiffness along the beam, a two-phase
checkerboard composite, or a lognormal random field (the classic
random-media setting).  Requests cycle through a small field vocabulary
so the continuous engine's digest-keyed prep-row reuse still engages.

``--devices N`` shards the scenario axis of every compiled solver over N
devices.  On a CPU-only host it forces N virtual XLA host devices
(``--xla_force_host_platform_device_count``), which MUST happen before
jax initializes its backend — hence the heavyweight imports live inside
``main``.

``--chunk-policy {fixed,adaptive,shard-adaptive}`` selects how the
continuous engine picks each chunk's PCG iteration count (and, for
shard-adaptive, which device refills land on).  Scheduling never changes
numerics — reports are identical across policies — and the run prints
the scheduler counters (chunks dispatched, mean chunk length, wasted
iterations); see docs/SCHEDULING.md.

``--metrics-out`` dumps the service's metrics registry (Prometheus text,
or a JSON snapshot for ``.json`` paths); ``--trace-out`` attaches a
device-fencing span recorder and writes a Chrome ``trace_event`` file
viewable at https://ui.perfetto.dev; ``--events-out`` writes the same
spans as JSON-lines.  A latency-quantile summary line (p50/p90/p99 from
the registry histogram) prints either way; see docs/OBSERVABILITY.md.

``--checkpoint-dir`` (continuous mode) snapshots the full serving state
— every in-flight resumable BpcgState, the queue, tickets — every
``--checkpoint-every`` steps through
:class:`repro.serve.recovery.ServiceRecovery`; ``--resume`` restores the
newest intact checkpoint instead of submitting a fresh workload, so a
SIGKILLed run restarted with the same flags finishes every accepted
request with bitwise-identical solutions and iteration counts.
``--devices`` may differ across the restart (elastic rescale).
``--watchdog-timeout`` arms the step hang detector; ``--report-out``
writes one JSON line per report (ticket, iterations, solution hash) for
differential comparison; ``--kill-after-steps`` SIGKILLs the process
mid-run (fault-injection hook for the test harness).  See
docs/FAULT_TOLERANCE.md.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import signal
import time

import jax

jax.config.update("jax_enable_x64", True)


def make_material_field(kind: str, coarse_mesh, refine: int, i: int):
    """Per-element ``(lam_e, mu_e)`` fields on the fine mesh for request
    ``i``.  ``kind`` is ``graded`` (stiffness ramps down along x from the
    clamped end), ``checkerboard`` (two-phase composite by element
    parity) or ``lognormal[:seed]`` (iid lognormal random medium).  A
    vocabulary of 4 variants per kind keeps digest-keyed prep reuse
    live under continuous refill."""
    import numpy as np

    fine = coarse_mesh.refined(refine)
    nx, ny, nz = fine.shape
    e = np.arange(fine.nelem)
    ex, ey, ez = e % nx, (e // nx) % ny, e // (nx * ny)
    v = i % 4  # field vocabulary index
    if kind == "graded":
        t = (ex + 0.5) / nx  # 0 at the clamped x=0 face
        lam = (50.0 + 5.0 * v) * (1.0 - t) + 1.0
        mu = 0.8 * lam
    elif kind == "checkerboard":
        hard = (ex + ey + ez) % 2 == 0
        lam = np.where(hard, 50.0 + 5.0 * v, 1.0 + 0.2 * v)
        mu = np.where(hard, 45.0 + 5.0 * v, 1.0)
    elif kind.startswith("lognormal"):
        seed = int(kind.split(":", 1)[1]) if ":" in kind else 0
        rng = np.random.default_rng(seed * 1000 + v)
        lam = np.exp(rng.normal(np.log(10.0), 0.6, fine.nelem))
        mu = np.exp(rng.normal(np.log(8.0), 0.6, fine.nelem))
    else:
        raise ValueError(
            f"unknown --material-field {kind!r} (expected graded, "
            f"checkerboard or lognormal[:seed])"
        )
    return np.asarray(lam, dtype=np.float64), np.asarray(mu, np.float64)


def make_workload(
    n_requests: int,
    ps: list[int],
    refine: int,
    base_tol: float,
    material_field: str | None = None,
):
    """A deterministic mixed workload: alternating material contrasts,
    traction directions/magnitudes and tolerances across ``ps``; with
    ``material_field`` set, attribute dicts are replaced by per-element
    coefficient fields from :func:`make_material_field`."""
    from repro.fem.mesh import beam_hex
    from repro.serve.elasticity_service import SolveRequest

    reqs = []
    for i in range(n_requests):
        p = ps[i % len(ps)]
        if material_field is None:
            stiff = 50.0 + 10.0 * (i % 3)
            soft = 1.0 + 0.5 * (i % 2)
            materials = {1: (stiff, stiff), 2: (soft, soft)}
        else:
            materials = make_material_field(
                material_field, beam_hex(), refine, i
            )
        tz = -1e-2 * (1.0 + 0.25 * (i % 4))
        ty = 2e-3 if i % 2 else 0.0
        reqs.append(
            SolveRequest(
                p=p,
                refine=refine,
                materials=materials,
                traction=(0.0, ty, tz),
                rel_tol=base_tol if i % 2 else base_tol * 1e-2,
            )
        )
    return reqs


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n-requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--p", type=int, nargs="+", default=[2])
    ap.add_argument("--refine", type=int, default=1)
    ap.add_argument("--rel-tol", type=float, default=1e-6)
    ap.add_argument("--assembly", default="paop")
    ap.add_argument("--pallas-lane", default="auto",
                    choices=["auto", "compiled", "interpret"],
                    help="Pallas kernel lane for paop_pallas assembly: "
                         "auto is compiled (Mosaic, f32 policies only) "
                         "on a TPU and interpret elsewhere; compiled "
                         "off a TPU is an error")
    ap.add_argument("--precision", default=None,
                    choices=["f64", "f32", "mixed", "mixed-bf16"],
                    help="service-default precision policy (requests may "
                         "still name their own): f64, f32 (uniform), or "
                         "mixed / mixed-bf16 (f64 outer Krylov over a "
                         "reduced-precision V-cycle).  Default f64, or "
                         "mixed on a TPU, which refuses f64.  Reduced "
                         "policies auto-fall-back stagnated rows to f64 "
                         "where it runs — the report's prec column shows "
                         "the policy that produced each answer, * marks "
                         "a fallback")
    ap.add_argument("--repeat", type=int, default=1,
                    help="re-run the workload to demonstrate cache hits")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching (slot refill + bucketed "
                         "padding) instead of generational")
    ap.add_argument("--chunk-iters", type=int, default=8,
                    help="PCG iterations per continuous chunk (fixed "
                         "policy) / no-history fallback (adaptive)")
    ap.add_argument("--chunk-policy", default="fixed",
                    choices=["fixed", "adaptive", "shard-adaptive"],
                    help="continuous chunk scheduling: fixed chunk "
                         "length, retire-cadence adaptive, or per-device "
                         "cadence + shard-balanced refill placement "
                         "(never changes numerics)")
    ap.add_argument("--min-chunk", type=int, default=None,
                    help="adaptive policies: chunk length lower clamp")
    ap.add_argument("--max-chunk", type=int, default=None,
                    help="adaptive policies: chunk length upper clamp "
                         "(default 4 * chunk-iters)")
    ap.add_argument("--devices", type=int, default=None,
                    help="shard the scenario axis over N devices (forces "
                         "N virtual host devices on CPU)")
    ap.add_argument("--material-field", default=None,
                    metavar="{graded,checkerboard,lognormal[:seed]}",
                    help="heterogeneous per-element (lam_e, mu_e) fields "
                         "instead of attribute dicts")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the service metrics registry as a "
                         "Prometheus text dump (.prom/.txt) or JSON "
                         "snapshot (.json)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record request and host-work spans (no "
                         "device sync; device time needs a jax.profiler "
                         "trace) and write a Chrome trace_event file — "
                         "open it at https://ui.perfetto.dev")
    ap.add_argument("--events-out", default=None, metavar="PATH",
                    help="also write the spans as a JSON-lines event log")
    ap.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                    help="continuous mode: checkpoint the full serving "
                         "state (in-flight BpcgState, queue, tickets) "
                         "into DIR at step boundaries")
    ap.add_argument("--checkpoint-every", type=int, default=1,
                    metavar="N", help="steps between checkpoints")
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest intact checkpoint from "
                         "--checkpoint-dir instead of submitting a "
                         "fresh workload (falls back to a fresh "
                         "workload when DIR has no usable checkpoint)")
    ap.add_argument("--watchdog-timeout", type=float, default=None,
                    metavar="SECONDS",
                    help="arm a step hang detector: steps exceeding "
                         "this raise the watchdog_fires counter and "
                         "emit a watchdog_fire span")
    ap.add_argument("--report-out", default=None, metavar="PATH",
                    help="write one JSON line per report (ticket, "
                         "iterations, converged, rel_norm, precision, "
                         "sha256 of the solution vector) — the "
                         "crash/restore differential suite compares "
                         "these files bitwise")
    ap.add_argument("--kill-after-steps", type=int, default=None,
                    metavar="N", help="SIGKILL this process after N "
                         "locally executed continuous steps (after the "
                         "checkpoint hook) — fault-injection test hook")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    if args.checkpoint_dir and not args.continuous:
        ap.error("--checkpoint-dir requires --continuous (the "
                 "generational path holds no resumable in-flight state)")

    # Env must be set before anything touches the jax backend.
    from repro.distributed.sharding import (
        force_host_device_count,
        scenario_mesh,
    )

    force_host_device_count(args.devices)
    from repro.serve.elasticity_service import ElasticityService

    mesh = None
    if args.devices is not None:
        mesh = scenario_mesh(args.devices)
        print(f"scenario mesh: {mesh.devices.size} devices "
              f"({jax.device_count()} visible)")

    spans = None
    if args.trace_out or args.events_out:
        from repro.obs import SpanRecorder

        spans = SpanRecorder()
    service = ElasticityService(
        max_batch=args.max_batch, assembly=args.assembly,
        pallas_lane=args.pallas_lane, precision=args.precision,
        chunk_iters=args.chunk_iters, chunk_policy=args.chunk_policy,
        min_chunk=args.min_chunk, max_chunk=args.max_chunk, mesh=mesh,
        spans=spans,
    )
    if args.assembly == "paop_pallas":
        print(f"pallas lane: {service.pallas_lane} "
              f"(requested {args.pallas_lane})")
    recovery = None
    if args.checkpoint_dir:
        from repro.serve.recovery import ServiceRecovery

        recovery = ServiceRecovery(
            service, args.checkpoint_dir, every=args.checkpoint_every
        )
    if args.watchdog_timeout is not None:
        service.attach_watchdog(args.watchdog_timeout)
    resumed = False
    if recovery is not None and args.resume:
        resumed = recovery.restore()
        if resumed:
            print(
                f"resumed from checkpoint step {service._step_index} "
                f"({len(service._flights)} flight(s), "
                f"{len(service._queue)} queued) in {args.checkpoint_dir}"
            )
        else:
            print(f"no usable checkpoint in {args.checkpoint_dir}; "
                  f"starting fresh")
    all_reports = []
    for round_i in range(args.repeat):
        t0 = time.perf_counter()
        if args.continuous:
            # Explicit step loop so checkpoints land at every step
            # boundary and a kill can strike between them.  A resumed
            # round 0 submits nothing: the checkpoint carries the whole
            # workload (flights + queue + any undrained reports).
            if not (resumed and round_i == 0):
                reqs = make_workload(
                    args.n_requests, args.p, args.refine, args.rel_tol,
                    material_field=args.material_field,
                )
                if args.report_out:
                    reqs = [
                        dataclasses.replace(r, keep_solution=True)
                        for r in reqs
                    ]
                for r in reqs:
                    service.submit(r)
            local_steps = 0
            while not service.idle():
                service.step()
                if recovery is not None:
                    recovery.maybe_checkpoint()
                local_steps += 1
                if (
                    args.kill_after_steps is not None
                    and local_steps >= args.kill_after_steps
                ):
                    print(
                        f"kill-after-steps: SIGKILL after local step "
                        f"{local_steps}",
                        flush=True,
                    )
                    os.kill(os.getpid(), signal.SIGKILL)
            reports = service.drain()
        else:
            reqs = make_workload(
                args.n_requests, args.p, args.refine, args.rel_tol,
                material_field=args.material_field,
            )
            reports = service.solve(reqs)
        all_reports.extend(reports)
        dt = time.perf_counter() - t0
        # Throughput counts REAL requests only — padding rows (bucket or
        # device alignment) ride in padded_rows and are excluded.
        print(
            f"-- round {round_i}: {len(reports)} scenarios in {dt:.2f}s "
            f"({len(reports) / dt:.2f} scenarios/s)"
        )
        print(
            f"{'i':>3} {'key':16s} {'prec':>7} {'ndof':>7} {'iters':>5} "
            f"{'conv':>5} {'rel_norm':>9} {'hit':>4} {'rows':>7} "
            f"{'setup(s)':>8} {'solve(s)':>8}"
        )
        for i, rep in enumerate(reports):
            p, refine, shape = rep.key[:3]
            short_key = f"p{p}/r{refine}/{'x'.join(map(str, shape))}"
            rows = f"{rep.batch_size}/{rep.padded_rows}"
            prec = rep.precision + ("*" if rep.fallback else "")
            print(
                f"{i:>3} {short_key:16s} {prec:>7} {rep.ndof:>7} "
                f"{rep.iterations:>5} {str(rep.converged):>5} "
                f"{rep.final_rel_norm:>9.2e} {str(rep.cache_hit):>4} "
                f"{rows:>7} {rep.t_setup:>8.3f} {rep.t_solve:>8.3f}"
            )
    print(f"service stats: {service.stats}")
    if recovery is not None:
        print(f"recovery: {recovery.summary()}")
    if args.report_out:
        import hashlib
        import json

        import numpy as np

        with open(args.report_out, "w") as f:
            for rep in all_reports:
                x_hash = (
                    None
                    if rep.x is None
                    else hashlib.sha256(
                        np.ascontiguousarray(rep.x).tobytes()
                    ).hexdigest()
                )
                f.write(json.dumps({
                    "ticket": rep.ticket,
                    "iterations": int(rep.iterations),
                    "converged": bool(rep.converged),
                    "final_rel_norm": float(rep.final_rel_norm),
                    "precision": rep.precision,
                    "fallback": bool(rep.fallback),
                    "born_converged": bool(rep.born_converged),
                    "x_sha256": x_hash,
                }) + "\n")
        print(f"reports -> {args.report_out}")
    if args.continuous:
        # Scheduler outcome of the chosen --chunk-policy: how many
        # chunks were dispatched, their mean chosen length, and the
        # slot-iterations near-converged rows idled inside chunks.
        s = service.trace.summary()
        print(
            f"scheduler[{service.chunk_policy.name}]: "
            f"chunks={s['chunks']} mean_chunk={s['mean_chunk']:.2f} "
            f"wasted_iters={s['wasted_iters']} refills={s['refills']}"
        )
    lat = service.latency_summary()
    if lat:
        print(
            f"latency: p50={lat['p50']:.3f}s p90={lat['p90']:.3f}s "
            f"p99={lat['p99']:.3f}s mean={lat['mean']:.3f}s "
            f"(n={int(lat['count'])})"
        )
    if args.metrics_out:
        if args.metrics_out.endswith(".json"):
            with open(args.metrics_out, "w") as f:
                f.write(service.registry.to_json(indent=2))
        else:
            with open(args.metrics_out, "w") as f:
                f.write(service.registry.to_prometheus_text())
        print(f"metrics -> {args.metrics_out}")
    if spans is not None:
        if args.trace_out:
            spans.to_chrome_trace(args.trace_out)
            print(
                f"trace -> {args.trace_out} "
                f"({spans.count()} spans; open at https://ui.perfetto.dev)"
            )
        if args.events_out:
            spans.to_jsonl(args.events_out)
            print(f"events -> {args.events_out}")


if __name__ == "__main__":
    main()
