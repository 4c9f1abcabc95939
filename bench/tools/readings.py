#!/usr/bin/env python3
"""Readings for a cell's correctness limit, in one process on the chip.

    python bench/tools/readings.py --workload <cell> --seeds 1 2 ... \
        [--early-seeds 4 5 6 --tol-scales 3 10] [--control-seeds 7 8 9] \
        [--seconds S]

For each seed it serves one window of the cell's mix through the
configured service and prints the numbers that ``correct`` compares,
with no limit applied: the lower reading of a limit is the largest over
these seeds.  With ``--early-seeds`` it plants an early stop on the same
service: every request is sent at its ``rel_tol`` times each of
``--tol-scales`` and judged at the mix's ``rel_tol``, as a solve cut
short would be.  With ``--control-seeds`` it then does the same with
the control, the program's own float32 policy (the precision below the
configuration's float64 Krylov), on a second service.  The smallest of
the early stops' and the control's readings bounds the limit from
above.  One JSON line per seed.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
# The precision below the configurations' float64 Krylov: the program's
# own float32 policy.
CONTROL = "f32"
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def stop_early(session, scale: float):
    """Send each request at ``scale`` times its tolerance; the run still
    records, and judges, the tolerance the mix asked for."""
    real = session.request
    session.request = lambda kw: real(dict(kw, rel_tol=kw["rel_tol"] * scale))
    return real


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--early-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--tol-scales", type=float, nargs="*", default=[10.0])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args()

    from bench.lib import harness
    from bench.lib.traffic import Traffic, load_mix
    from bench.run import cell_files

    cell, config, limits, _, _ = cell_files(args.workload)
    mix = load_mix(cell["traffic"])
    program = [("program", None, 1.0, args.seeds)] + [
        (f"early_x{s:g}", None, s, args.early_seeds) for s in args.tol_scales]
    control = [("control", CONTROL, 1.0, args.control_seeds)]
    for plan in (program, control):
        plan = [p for p in plan if p[3]]
        if not plan:
            continue
        t0 = time.perf_counter()
        precision = plan[0][1]
        session = harness.Session(config, chips=int(cell["chips"]),
                                  precision=precision)
        session.warm_up(Traffic(mix, config, plan[0][3][0]))
        harness.log(f"[{plan[0][0]}] set-up {time.perf_counter() - t0:.1f} s "
                    f"{session.clock.take()}")
        runs = []
        for label, _, scale, seeds in plan:
            real = stop_early(session, scale) if scale != 1.0 else None
            for seed in seeds:
                run = harness.Run(config=config, mix=mix,
                                  device=dict(session.device))
                session.serve(Traffic(mix, config, seed), args.seconds, run)
                runs.append((label, seed, run))
            if real is not None:
                session.request = real
        session.close()
        for label, seed, run in runs:
            correct, numbers, failed = harness.check(run, limits)
            reps = [r for _, r in run.requests.values() if r is not None]
            line = {
                "label": label, "workload": args.workload, "seed": seed,
                "precision": session.precision,
                "numbers": {k: v for k, (v, _) in numbers.items()},
                "correct_at_current_limit": correct, "failed": failed,
                "iterations": [r.iterations for r in reps],
                "stalled": [bool(r.stalled) for r in reps],
                "window_s": run.window_s, "credits": run.credits,
                "device": run.device,
            }
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
