"""One run of one cell: set-up, measured window, finish, check, report.

The system under test is ``repro.serve.elasticity_service``, driven in
its continuous mode (``submit`` / ``step`` / ``drain``) exactly as a
client would.  A :class:`Session` holds the service and its compiled
programs; :meth:`Session.serve` runs one measured window of a traffic
mix on it, and :func:`check` compares every answer with the plain
reference (``bench.lib.reference``) once the program's state is freed.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from bench.lib.compiles import CompileClock, use_cache
from bench.lib.traffic import Traffic

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
# Past the window's close, how long the run waits for the answers that
# were due in it; one that has not come by then never comes.
FINISH_S = 90.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_module(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    return load_module(path)


def annotate(name: str, on: bool):
    if not on:
        return nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


@dataclass
class Run:
    """What one run observed; the metric readers reduce it."""

    config: dict
    mix: dict
    device: dict
    setup_s: float = 0.0
    window_s: float = 0.0
    credits: float = 0.0
    attempted: int = 0
    # every request of the run: ticket -> (kwargs, report or None)
    requests: dict = field(default_factory=dict)
    # tickets whose reports came inside the window
    done_in_window: list = field(default_factory=list)
    # ticket -> seconds into the window at which it was submitted, and
    # at which its report came (in the window or after, untimed)
    submitted_at: dict = field(default_factory=dict)
    answered_at: dict = field(default_factory=dict)
    # service counter deltas over the run's requests
    counters: dict = field(default_factory=dict)
    compiles_in_window: dict = field(default_factory=dict)
    window_trace: object = None  # bench.lib.trace.Trace of the window
    window_span: tuple = ()
    extra: dict = field(default_factory=dict)  # per-metric measurements


class Session:
    """The configured service, built and warmed up on the device."""

    def __init__(self, config: dict, *, platforms=("tpu",), chips: int = 1,
                 precision: str | None = None):
        import jax

        jax.config.update("jax_enable_x64", True)
        self.cache_dir = use_cache()
        self.clock = CompileClock()
        devs = jax.devices()
        self.dev = devs[0]
        if self.dev.platform not in platforms:
            raise SystemExit(
                f"no accelerator: JAX platform {self.dev.platform!r}, "
                f"this benchmark runs on {platforms}")
        if len(devs) < chips:
            raise SystemExit(f"{chips} chips asked for, {len(devs)} visible")
        self.device = {"platform": self.dev.platform,
                       "kind": self.dev.device_kind, "count": len(devs)}
        self.config = config
        # The configuration's ``service`` group is ElasticityService's
        # keyword arguments as they are run (``mesh`` included, as a
        # device count); a control may replace the precision.
        kwargs = dict(config["service"])
        if precision:
            kwargs["precision"] = precision
        self.precision = kwargs["precision"]
        self.phases: dict[str, float] = {}
        t = time.perf_counter()
        from repro.serve.elasticity_service import ElasticityService

        self.service = ElasticityService(**kwargs)
        self.phases["service"] = time.perf_counter() - t

    def request(self, kwargs: dict):
        from repro.serve.elasticity_service import SolveRequest

        return SolveRequest(p=int(self.config["p"]),
                            refine=int(self.config["refine"]),
                            keep_solution=True, **kwargs)

    def warm_up(self, traffic: Traffic) -> None:
        """Serve one request at a loose tolerance in one-iteration chunks,
        so that the prep program and both step programs (the chunk that
        starts a row and the chunk that resumes one) compile or load now
        and not in the window."""
        from repro.serve.chunk_policy import make_chunk_policy

        svc = self.service
        t = time.perf_counter()
        policy = svc.chunk_policy
        svc.chunk_policy = make_chunk_policy("fixed", chunk_iters=1)
        try:
            svc.submit(self.request(traffic.warmup()))
            while not svc.idle():
                svc.step()
            (rep,) = svc.drain()
        finally:
            svc.chunk_policy = policy
        if rep.iterations < 2:
            raise RuntimeError(
                f"warm-up converged in {rep.iterations} iteration(s); the "
                f"resuming step program was not built: lower the mix's "
                f"warmup_rel_tol")
        self.phases["hierarchy"] = rep.t_setup
        self.phases["warm_up"] = time.perf_counter() - t - rep.t_setup
        self.warmup_iterations = rep.iterations

    def serve(self, traffic: Traffic, seconds: float, run: Run, *,
              trace: bool = False) -> None:
        """The measured window: the mix's traffic until the first step
        boundary at or after ``seconds``; then, untimed, the answers the
        window cut short."""
        import jax

        svc = self.service
        c0 = dict(svc.stats)
        n0 = len(svc.trace.decisions)
        logdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        if trace:
            jax.profiler.start_trace(logdir)
        self.clock.take()
        pending: set[int] = set()
        t_open = 0.0

        def submit_due():
            elapsed = time.perf_counter() - t_open
            for _ in range(traffic.due(elapsed, len(run.requests),
                                       len(run.done_in_window))):
                kw = traffic.request(len(run.requests))
                t = svc.submit(self.request(kw))
                run.requests[t] = [kw, None]
                run.submitted_at[t] = time.perf_counter() - t_open
                pending.add(t)

        def answer(rep, in_window):
            if rep.ticket not in pending:
                return
            run.requests[rep.ticket][1] = rep
            run.answered_at[rep.ticket] = time.perf_counter() - t_open
            pending.discard(rep.ticket)
            if in_window:
                run.done_in_window.append(rep.ticket)

        try:
            with annotate("bench.window", trace):
                t_open = time.perf_counter()
                submit_due()
                while True:
                    with annotate("bench.step", trace):
                        svc.step()
                    with annotate("bench.drain", trace):
                        for rep in svc.drain():
                            answer(rep, True)
                    if time.perf_counter() - t_open >= seconds:
                        break
                    submit_due()
                t_close = time.perf_counter()
        finally:
            if trace:
                jax.profiler.stop_trace()
        run.compiles_in_window = self.clock.take()
        run.window_s = t_close - t_open
        decisions = svc.trace.decisions[n0:]
        # A request the service had not admitted by the close (queued
        # behind a full batch) is withdrawn: nothing of it was due.
        # Those the window cut are finished, untimed.
        admitted = {rf.ticket for d in decisions for rf in d.refills}
        for t in [t for t in pending if t not in admitted]:
            pending.discard(t)
            del run.requests[t]
        run.attempted = len(run.requests)
        deadline = time.perf_counter() + FINISH_S
        while pending and time.perf_counter() < deadline:
            svc.step()
            for rep in svc.drain():
                answer(rep, False)
        c1 = dict(svc.stats)
        run.counters = {k: c1.get(k, 0) - c0.get(k, 0) for k in c1}
        run.credits = credits(run)
        if trace:
            from bench.lib import trace as tr

            try:
                run.window_trace = tr.load(tr.find_xplane(logdir))
            finally:
                shutil.rmtree(logdir, ignore_errors=True)
            run.window_span = run.window_trace.span("bench.window")

    def memory_peak(self) -> int:
        """Peak device bytes: arrays (``peak_bytes_in_use``) plus the
        region the runtime reserves for program temporaries
        (``peak_bytes_reserved``), which the arrays' count leaves out."""
        stats = self.dev.memory_stats() or {}
        log(f"memory_stats {json.dumps(stats)}")
        return int(stats.get("peak_bytes_in_use", 0)
                   + stats.get("peak_bytes_reserved", 0))

    def close(self) -> None:
        """Free the program's device state."""
        self.service = None
        gc.collect()


def credits(run: Run) -> float:
    """Solves credited to the window.  A request answered in it counts
    1; one it cut counts the share of its time, from submission to
    answer, that lay inside the window: it is finished after the close
    on the same programs, so its time outside is what it still cost."""
    total = float(len(run.done_in_window))
    for t, (_, rep) in run.requests.items():
        if t in run.done_in_window or rep is None:
            continue
        start, end = run.submitted_at[t], run.answered_at[t]
        total += (run.window_s - start) / (end - start)
    return total


def check(run: Run, limits: dict) -> tuple[bool, dict, int]:
    """Every answer of the run against the plain reference.  Returns
    (correct, {number: (value, limit)}, failed)."""
    from bench.lib.reference import Beam

    cfg = run.config
    beam = Beam(int(cfg["p"]), int(cfg["refine"]))
    lam, mu = beam.fields({int(a): tuple(v)
                           for a, v in cfg["materials"].items()})
    worst, failed, missing = 0.0, 0, 0
    for ticket, (kw, rep) in sorted(run.requests.items()):
        if rep is None or rep.x is None or rep.ticket != ticket:
            missing += 1
            continue
        if not rep.converged or rep.stalled:
            failed += 1
        rel = beam.residual(rep.x, kw["traction"], lam, mu)
        worst = max(worst, rel / kw["rel_tol"])
    numbers = {
        "residual_x_tol": (worst, float(limits["residual_x_tol"]["limit"])),
        "unanswered": (float(missing), 0.0),
    }
    correct = all(v <= lim for v, lim in numbers.values()) and np.isfinite(worst)
    return bool(correct), numbers, failed


def run_cell(cell: dict, config: dict, limits: dict, e2e: list,
             layers: list, *, seed: int, seconds: float, trace: bool,
             t_start: float, platforms=("tpu",)) -> dict:
    """One whole run; returns the result line's object."""
    from bench.lib.traffic import load_mix

    mix = load_mix(cell["traffic"])
    session = Session(config, platforms=platforms, chips=int(cell["chips"]))
    traffic = Traffic(mix, config, seed)
    session.warm_up(traffic)
    setup = session.clock.take()
    run = Run(config=config, mix=mix, device=dict(session.device))
    t_open_wall = time.perf_counter()
    run.setup_s = t_open_wall - t_start
    log(f"set-up {run.setup_s:.3f} s: {setup['programs']} programs, "
        f"{setup['loaded']} loaded from the compile cache in "
        f"{setup['load_s']:.3f} s, {setup['compiled']} compiled in "
        f"{setup['compile_s']:.3f} s; phases "
        + ", ".join(f"{k} {v:.3f} s" for k, v in session.phases.items())
        + f"; warm-up {session.warmup_iterations} iterations; "
        f"cache {session.cache_dir}")
    session.serve(traffic, seconds, run, trace=trace)
    run.device["memory_peak_bytes"] = session.memory_peak()
    session.close()
    w = run.compiles_in_window
    log(f"window {run.window_s:.3f} s, {len(run.done_in_window)} answered "
        f"in it, {run.credits:.4f} solves credited; "
        f"{w.get('programs', 0)} programs built in the window; "
        f"submitted at {run.submitted_at}, answered at {run.answered_at}")
    if w.get("programs", 0):
        raise RuntimeError(
            f"{w['programs']} programs were built inside the window: the "
            f"warm-up did not cover the window's shapes")
    wanted = layers if trace else e2e
    readers = {m["name"]: metric_module(m["name"]) for m in wanted}
    if trace:
        for name, mod in readers.items():
            if hasattr(mod, "measure"):
                run.extra[name] = mod.measure(run)
    correct, numbers, failed = check(run, limits)
    metrics = {}
    for m in wanted:
        v = readers[m["name"]].read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    out = {"correct": correct, "attempted": run.attempted, "failed": failed,
           "metrics": metrics, "device": run.device}
    if trace and run.window_trace is not None:
        lo, hi = run.window_span
        out["device"]["busy_s"] = run.window_trace.busy(lo, hi)
        out["device"]["window_s"] = hi - lo
        ops = sorted(run.window_trace.op_seconds(lo, hi).items(),
                     key=lambda kv: -kv[1])[:10]
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in run.window_trace.idle_gaps(lo, hi)],
        }
    for name, meas in run.extra.items():
        if meas:
            log(f"{name}: {json.dumps(meas)}")
    out["limits"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in numbers.items()}
    for k, (v, lim) in numbers.items():
        log(f"{k} {v!r} limit {lim!r}")
    return out
