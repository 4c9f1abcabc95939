#!/usr/bin/env python3
"""Benchmark of the elasticity service on the chip: one run of one cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found
by name from ``BENCHMARK.json`` and the files under ``bench/``.  The run
builds and warms up the service (set-up), runs the mix's closed loop
for ``--seconds`` up to the next step boundary (the window), finishes
untimed what the window cut short, checks every answer against the
plain reference, and prints one JSON line last on standard output.
``--trace 1`` runs the window under the profiler and reports the cell's
per-layer metrics instead of its end-to-end ones.  Without a TPU it
exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def cell_files(workload: str):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}: {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    limits = json.loads(
        (ROOT / "bench" / "limits" / f"{workload}.json").read_text())

    def wanted(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]

    return (cell, config, limits, wanted(spec["end_to_end"]),
            wanted(spec["per_layer"]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from bench.lib import harness

    cell, config, limits, e2e, layers = cell_files(args.workload)
    result = harness.run_cell(
        cell, config, limits, e2e, layers, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
