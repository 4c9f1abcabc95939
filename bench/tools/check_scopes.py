#!/usr/bin/env python3
"""Check, on the chip, the scope paths the per-layer readers use: run a
cell's configuration (at a small size unless told otherwise) with
``--trace 1`` semantics, keep the window's trace, and compare for every
device operation in it the path the readers took from the program's
HLO (``bench.lib.scopes``) with the ``tf_op`` the trace itself carries.
Prints the run's result line, the counts of operations whose paths
agree, differ, or are missing from either side, the disagreements, and
the operations with most self time that no layer claims; exits non-zero
unless every operation of the service's programs agrees.

    python bench/tools/check_scopes.py CELL [--p 2] [--refine 1] [--seconds 1]
"""

import argparse
import collections
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[2]
# the service's programs; the readers' own (a standalone apply) and the
# host's small helper programs are not built by the rebuilt service
PROGRAMS = ("jit__chunk_start", "jit__chunk_resume", "jit__prepare_impl")


def compare(t, paths: dict, from_trace: dict):
    """(counts, disagreements, top unscoped) over the operations of the
    service's programs in trace ``t``: ``paths`` are the readers'
    {module: {op: path}}, ``from_trace`` :func:`scopes.xspace_op_paths`."""
    from bench.lib import scopes

    counts, bad = collections.Counter(), collections.Counter()
    unscoped = collections.Counter()
    for plane in t.ops:
        for op, _, self_s, run in scopes.op_runs(t, plane):
            mod = scopes.module_name(run)
            if mod not in PROGRAMS:
                continue
            mine = paths.get(mod, {}).get(op)
            theirs = from_trace.get(plane, {}).get((scopes.program_id(run),
                                                    op))
            kind = ("missing" if mine is None or theirs is None
                    else "agree" if mine == theirs else "differ")
            counts[kind] += 1
            if kind != "agree":
                bad[(kind, mod, op, mine, theirs)] += 1
            if scopes.layer_of(mine or "") == scopes.UNSCOPED:
                unscoped[(mod, op, mine, theirs)] += self_s
    return counts, bad, unscoped


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("cell")
    ap.add_argument("--p", type=int, default=2)
    ap.add_argument("--refine", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=2**31 + 77)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.lib import harness, scopes
    from bench.lib import trace as tr
    from bench.run import cell_files

    keep = Path(tempfile.mkdtemp(prefix="check-scopes-"))
    seen = {}
    # The harness removes the window's trace once loaded: give it a
    # directory it cannot remove, and note the file it loads first.
    real_mkdtemp, real_rmtree, real_load = (
        harness.tempfile.mkdtemp, harness.shutil.rmtree, tr.load)
    harness.tempfile.mkdtemp = lambda prefix="", **kw: (
        str(keep) if prefix == "bench-trace-"
        else real_mkdtemp(prefix=prefix, **kw))
    harness.shutil.rmtree = lambda path, *a, **kw: (
        None if Path(path) == keep else real_rmtree(path, *a, **kw))

    def load(path):
        seen.setdefault("xplane", path)
        return real_load(path)

    tr.load = load
    program_paths = scopes.program_paths

    def remember(run):
        seen["paths"] = program_paths(run)
        return seen["paths"]

    scopes.program_paths = remember
    try:
        cell, config, limits, e2e, layers = cell_files(args.cell)
        config = dict(config, p=args.p, refine=args.refine)
        out = harness.run_cell(cell, config, limits, e2e, layers,
                               seed=args.seed, seconds=args.seconds,
                               trace=True, t_start=T_START)
        print(json.dumps(out), flush=True)
        from_trace = scopes.xspace_op_paths(seen["xplane"])
        t = real_load(seen["xplane"])
    finally:
        real_rmtree(keep, ignore_errors=True)
    counts, bad, unscoped = compare(t, seen.get("paths", {}), from_trace)
    print(f"operations: {dict(counts)}")
    for ex, n in bad.most_common(15):
        print(n, ex)
    print("most self time with no layer (s):")
    for ex, s in unscoped.most_common(10):
        print(f"{s:.6f}", ex)
    return 0 if counts and set(counts) == {"agree"} else 1


if __name__ == "__main__":
    sys.exit(main())
