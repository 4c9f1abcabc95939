"""Production-shaped batched elasticity solve service.

The solver-side sibling of :class:`repro.serve.engine.ServeEngine`:
requests describing parameterized elasticity scenarios (materials,
traction, tolerance) arrive in a queue, are grouped by *discretization
key* ``(p, n_h_refine, coarse_mesh.shape)``, and each group is solved by
compiled batched GMG-PCG programs
(:class:`repro.solvers.batched.BatchedGMGSolver`).  Two scheduling
policies share the cache and report plumbing:

* **continuous batching** (``submit`` / ``step`` / ``drain``) — the
  production path.  Each in-flight key holds a resumable
  :class:`~repro.solvers.batched.BpcgState`; every ``step`` advances it
  by a bounded chunk of PCG iterations, retires converged rows
  immediately (their :class:`SolveReport`\\ s become drainable), refills
  the freed slots from the queue by resetting *just those state rows*
  (new materials folded into the operators' per-scenario fields in
  place), and admits requests submitted mid-flight.  One slow scenario
  no longer idles a whole generation — exactly the prefill-boundary
  inefficiency continuous batching removes in LM serving engines.

* **generational batching** (``solve``) — drain everything in
  fixed batches; kept for one-shot workloads and as the baseline the
  ``--continuous`` benchmark compares against.

Shared machinery:

* the geometric hierarchy + compiled programs per key live in an LRU
  cache, so repeat traffic skips all setup (the paper's "Prec." phase)
  and retracing entirely;
* **bucketed padding**: batches are padded to the smallest sufficient
  bucket (1/2/4/.../max_batch), not always to ``max_batch``, so one
  compiled step program per ``(key, bucket)`` serves all nearby batch
  sizes and a draining tail of tight-tolerance scenarios shrinks to a
  cheaper program instead of dragging full-width padding along;
* padding rows (zero traction — born converged, 0 iterations) are
  internal: they are never surfaced to callers, and real zero-RHS
  requests are flagged ``born_converged`` so they can't be mistaken
  for a padded slot;
* every request gets a per-request :class:`SolveReport` with its own
  iteration count, convergence flag and residual norm;
* **heterogeneous materials**: ``SolveRequest.materials`` is either an
  attribute -> (lambda, mu) dict or a per-element ``(lam_e, mu_e)``
  array pair on the fine mesh; both are folded into (S, nelem)
  per-element fields on admission, so dict and array requests batch
  together, share compiled programs, and participate equally in
  prep-row reuse (keyed on a content digest of the folded fields);
* **scenario sharding**: with ``mesh`` set (a 1-D jax.sharding mesh over
  the scenario axis, or an int = "first n devices"), every compiled
  solver shards the batch rows across devices.  Buckets are rounded up
  to a multiple of the device count with born-converged padding rows, so
  the host-side retire/refill logic runs unchanged — ``step()`` fetches
  the (S,) convergence vectors of a sharded state exactly as before
  (jax gathers them transparently), and device-padding rows are never
  surfaced.  ``SolveReport.padded_rows`` records the compiled program's
  total row count so throughput accounting can exclude padding;
* **chunk scheduling**: how many PCG iterations each continuous chunk
  runs (and which free slot a refill lands in) is delegated to a
  :class:`~repro.serve.chunk_policy.ChunkPolicy` — ``fixed`` (the
  default, today's constant ``chunk_iters``), ``adaptive`` (chunk to
  the retire cadence observed in the flight's history ring buffer) or
  ``shard-adaptive`` (cadence per device + refills placed on the
  least-loaded shard).  Policies NEVER change numerics — any policy
  produces the same iteration counts, flags and (to machine precision)
  solutions as ``fixed``, bitwise so when its decisions coincide; only
  *when* rows retire/refill differs.  Every decision is recorded in
  ``ElasticityService.trace`` (a replayable
  :class:`~repro.serve.chunk_policy.SchedulerTrace`), and ``stats``
  carries the scheduler counters (``chunks``, ``chunk_iters_dispatched``,
  ``wasted_iters``, ``refills``);
* **observability**: every counter lives on a typed
  :class:`repro.obs.metrics.MetricsRegistry` (labeled by
  ``(p, refine, policy, devices)``; ``stats`` is a read-only legacy
  view), request latency and queue wait feed registry histograms
  (``latency_summary()`` reports the merged quantiles), and attaching a
  :class:`repro.obs.spans.SpanRecorder` (``attach_spans``) records the
  full request lifecycle — submit→admit→prep→dispatch→retire —
  exportable as a Chrome trace and a JSON-lines event log.  The host
  work of every step also opens ``service.<name>`` profiler annotations
  (:data:`SERVICE_SPANS`), so a ``jax.profiler`` trace names it beside
  the device's scoped work; recording adds no device sync.  The service
  clock (default: the profiler's) is injectable for deterministic
  tests.  Catalog: ``docs/OBSERVABILITY.md``.
"""

from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict, deque
from collections.abc import Mapping
from contextlib import contextmanager
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.geometry import (
    MATERIALS_BEAM,
    check_material_dict,
    check_material_fields,
)
from repro.core.precision import (
    PRECISION_POLICIES,
    PrecisionPolicy,
    check_policy,
    policy_refusal,
    resolve_precision,
)
from repro.distributed.sharding import scenario_row_devices
from repro.fem.mesh import HexMesh, beam_hex
from repro.serve.chunk_policy import (
    HISTORY_LEN,
    ChunkDecision,
    ChunkObservation,
    RefillPlacement,
    SchedulerTrace,
    make_chunk_policy,
    wasted_iterations,
)
from repro.obs.metrics import MetricsRegistry
from repro.obs.spans import profiler_clock
from repro.solvers.batched import BatchedGMGSolver, BpcgState

__all__ = ["SolveRequest", "SolveReport", "ElasticityService", "SERVICE_SPANS"]

# The service's host spans: each opens a ``service.<name>`` profiler
# annotation and, with a SpanRecorder attached, records ``<name>`` there.
SERVICE_SPANS = (
    "step",  # one step(): every flight's retire, admit, prep, dispatch
    "retire",  # consumed + state fetches (the host waits on the device)
    "admit",  # refill: material packing and digests
    "prep",  # digest match + the prepare / copy_prep_rows dispatch
    "dispatch",  # the run_chunk call
    "build",  # a solver cache miss: hierarchy + program construction
    "generation",  # one generational batch, solved and fetched
)

# Help text for the service counter families.  The keys double as the
# legacy ``ElasticityService.stats`` vocabulary: each maps to the
# ``service_<key>_total`` counter family on the registry, labeled by
# (p, refine, policy, devices).
_STAT_HELP = {
    "cache_hits": "Solver LRU cache hits.",
    "cache_misses": "Solver LRU cache misses (hierarchy + program builds).",
    "generations": "Generational batches solved.",
    "chunks": "Continuous chunks dispatched.",
    "chunk_iters_dispatched": "PCG iterations dispatched across chunks.",
    "wasted_iters": "Dispatched slot-iterations no live row consumed.",
    "refills": "Freed slots refilled from the queue.",
    "rebuckets": "In-flight state re-bucketings.",
    "prep_calls": "prepare() calls (power iterations + refactorization).",
    "prep_row_copies": "Prep rows reused via content-digest match.",
    "precision_fallbacks": (
        "Rows a reduced-precision flight re-queued onto the f64 path "
        "after stagnation detection."
    ),
    # Recovery subsystem (repro.serve.recovery + attach_watchdog).
    # These are labeled (policy, devices) only — a checkpoint/restore
    # spans every flight key and a watchdog fire has none.
    "checkpoints_written": (
        "Recovery checkpoints committed to disk (atomic renames)."
    ),
    "restores": "Service restores from a recovery checkpoint.",
    "watchdog_fires": "step() calls the watchdog flagged past timeout.",
}


class _StatsView(Mapping):
    """Read-only legacy view of the service counters.

    ``ElasticityService.stats`` used to be a plain dict of ints; it is
    now this Mapping over the metrics registry — same keys, same int
    values (each key summed across every (p, refine, policy, devices)
    label set), so ``svc.stats["chunks"]`` and ``dict(svc.stats)`` read
    exactly as before.  Writes go through the registry, never here."""

    _KEYS = tuple(_STAT_HELP)

    def __init__(self, registry: MetricsRegistry):
        self._registry = registry

    def __getitem__(self, key: str) -> int:
        if key not in self._KEYS:
            raise KeyError(key)
        return int(self._registry.total(f"service_{key}_total"))

    def __iter__(self):
        return iter(self._KEYS)

    def __len__(self) -> int:
        return len(self._KEYS)

    def __repr__(self) -> str:
        return repr(dict(self))


@dataclasses.dataclass
class SolveRequest:
    """One parameterized beam-benchmark scenario.

    ``materials`` accepts two forms (``None`` = the paper's beam
    materials):

    * an attribute -> (lambda, mu) dict — piecewise-constant by mesh
      attribute, e.g. ``{1: (50.0, 50.0), 2: (1.0, 1.0)}``;
    * a ``(lam_e, mu_e)`` pair of per-element coefficient arrays, each
      of shape (nelem_fine,) where ``nelem_fine =
      coarse_mesh.nelem * 8**refine`` — one (lambda, mu) per element of
      the *fine* (solve) mesh, enabling graded / composite /
      random-field scenarios.  Coarser GMG levels see the field through
      an exact descendant average, so a piecewise-constant array
      reproduces the equivalent dict request bit-for-bit.

    Both forms are validated at ``submit()`` (coverage/positivity for
    dicts; shape/positivity per element for arrays) so invalid requests
    fail before any batch state is touched.  ``rel_tol`` is the
    MFEM-style relative residual tolerance; ``keep_solution`` attaches
    the (nscalar, 3) solution vector to the report.

    ``precision`` selects the request's
    :class:`~repro.core.precision.PrecisionPolicy` by name (``"f64"``,
    ``"f32"``, ``"mixed"``, ``"mixed-bf16"``); ``None`` inherits the
    service default.  The resolved policy participates in the
    compile-cache/flight key — requests of different policies never
    share a compiled program — and is recorded on the report.  Rows a
    reduced-precision flight flags as stagnated are automatically
    re-queued (same ticket, original submit time) onto the ``f64``
    path; their reports carry ``fallback=True``.  Where ``f64`` cannot
    run (``ElasticityService.fallback_precision`` is None) they retire
    unconverged with ``stalled=True``."""

    p: int = 2
    refine: int = 1
    materials: dict[int, tuple[float, float]] | tuple[Any, Any] | None = None
    traction: tuple[float, float, float] = (0.0, 0.0, -1e-2)
    rel_tol: float = 1e-6
    coarse_mesh: HexMesh | None = None
    keep_solution: bool = False
    precision: str | None = None


def _req_materials(req: SolveRequest):
    """The request's materials with the beam default applied."""
    return req.materials if req.materials is not None else MATERIALS_BEAM


def _material_digest(
    lam_row: np.ndarray, mu_row: np.ndarray, precision: str = "f64"
) -> bytes:
    """Content digest of one folded (lam_e, mu_e) row pair.  The
    continuous engine keys prep-row reuse on this digest: two rows with
    equal digests carry bitwise-equal per-element fields (verified
    against the snapshot on match), so heterogeneous-field requests
    short-circuit power iterations exactly like repeated dicts.  The
    precision-policy name is folded in — prep computed at one policy's
    dtypes (f32 weighted fields, f32 Cholesky) is not the same derived
    data as another's, even for identical materials."""
    h = hashlib.blake2b(digest_size=16)
    h.update(precision.encode())
    h.update(np.ascontiguousarray(lam_row))
    h.update(np.ascontiguousarray(mu_row))
    return h.digest()


@dataclasses.dataclass
class SolveReport:
    """Per-request outcome (one row of a batched solve).

    ``generation`` is the generation index for the generational path and
    the retiring chunk index for the continuous path; ``batch_size`` is
    the number of live (non-padding) rows sharing the program when this
    request finished; ``t_solve`` is the generation's device time for
    the generational path and the request's admission-to-retirement
    latency for the continuous path."""

    request: SolveRequest
    key: tuple
    iterations: int
    converged: bool
    final_rel_norm: float
    ndof: int
    batch_size: int  # live scenarios in this batch (excl. padding)
    generation: int  # generation index / retiring chunk index
    cache_hit: bool  # hierarchy + compiled solve came from the LRU cache
    t_setup: float  # seconds building the solver program (0 on cache hit)
    t_solve: float  # see class docstring
    born_converged: bool = False  # zero RHS: converged before iteration 1
    # Total rows of the compiled program this request rode in, INCLUDING
    # bucket/device padding (batch_size counts only real requests).
    # Honest throughput math divides real requests — never padded_rows —
    # by wall-clock.
    padded_rows: int = 0
    # Precision policy the FINISHING solve ran under; ``fallback`` marks
    # a row the reduced-precision pass flagged as stagnated and the
    # service re-solved on the f64 path (precision then reads "f64").
    precision: str = "f64"
    fallback: bool = False
    # A reduced-precision row that stagnated (or failed the true-residual
    # audit) and had no f64 path to fall back on (a TPU, the compiled
    # Pallas lane): reported unconverged, never re-solved.
    stalled: bool = False
    # The continuous engine's submit() ticket this report answers (-1 on
    # the generational path, which returns reports positionally).  The
    # stable join key for crash/restore differentials: a resumed
    # service's reports carry the same tickets the dead process issued.
    ticket: int = -1
    x: Any = None


@dataclasses.dataclass
class _Slot:
    """A live batch row: which request occupies it and since when.

    ``t_submit`` carries the ticket's enqueue time so retirement can
    attribute queue wait."""

    ticket: int
    request: SolveRequest
    t_admit: float
    t_submit: float = 0.0


class _Flight:
    """In-flight continuous batch for one discretization key: the
    resumable solver state plus host-side slot bookkeeping."""

    def __init__(self, key, solver, cache_hit, t_setup, tid_base=0):
        self.key = key
        self.solver = solver
        self.cache_hit = cache_hit
        self.t_setup = t_setup
        # Chrome-trace track block: the flight's prep/chunk spans go on
        # ``tid_base``; slot i's queue_wait/solve spans on tid_base+1+i.
        self.tid_base = tid_base
        self.bucket = 0
        self.slots: list[_Slot | None] = []
        # Folded (bucket, nelem_fine) per-element material fields —
        # attribute dicts are expanded on admission, so dict and array
        # requests are indistinguishable from here down.
        ne = solver.fine_space.nelem
        self.lam = np.zeros((0, ne))
        self.mu = np.zeros((0, ne))
        self.mat_digest = np.zeros((0,), dtype=object)
        self.tr = np.zeros((0, 3))
        self.tol = np.zeros((0,))
        self.state: BpcgState | None = None
        self.prep: dict | None = None
        # Materials each prep row was computed for (prep_valid rows
        # only), as a content digest + field snapshot.  Kept separately
        # from lam/mu — a retiring row's prep stays valid for its OLD
        # materials until overwritten, so it can donate its derived data
        # to a refill with a matching config.
        self.prep_valid = np.zeros((0,), dtype=bool)
        self.prep_digest = np.zeros((0,), dtype=object)
        self.prep_lam = np.zeros((0, ne))
        self.prep_mu = np.zeros((0, ne))
        self.pending_reset: np.ndarray | None = None
        self.chunks = 0
        # Scheduling state the chunk policies feed on, all host-side:
        # a ring buffer of recent retire cadences (iterations at
        # retirement) and a per-row iteration mirror maintained from the
        # consumed vectors run_chunk returns (reset rows go back to 0),
        # so building a ChunkObservation costs no device fetch.  The
        # consumed vector of the last dispatched chunk stays on device
        # (pending_consumed) until the next retire pass — which fetches
        # state anyway — so the policy adds no extra mid-flight syncs;
        # last_decision is the trace record awaiting that outcome.
        self.retire_history: deque[int] = deque(maxlen=HISTORY_LEN)
        self.row_iters = np.zeros((0,), dtype=np.int64)
        self.pending_refills: tuple[RefillPlacement, ...] = ()
        self.pending_consumed: Any = None
        self.last_decision: ChunkDecision | None = None

    def live_rows(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if s is not None]


class ElasticityService:
    """Queue + LRU-cached compiled solvers + continuous/generational
    batching."""

    def __init__(
        self,
        *,
        max_batch: int = 8,
        cache_size: int = 4,
        assembly: str = "paop",
        dtype=None,
        precision: str | PrecisionPolicy | None = None,
        maxiter: int = 200,
        pallas_interpret: bool | None = None,
        pallas_lane: str | None = None,
        chunk_iters: int = 8,
        chunk_policy=None,
        min_chunk: int | None = None,
        max_chunk: int | None = None,
        mesh=None,
        registry: MetricsRegistry | None = None,
        spans=None,
        clock=profiler_clock,
    ):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if cache_size < 1:
            raise ValueError(f"cache_size must be >= 1, got {cache_size}")
        self.max_batch = max_batch
        self.cache_size = cache_size
        self.assembly = assembly
        # Service-default precision policy (requests override per row
        # via SolveRequest.precision).  ``dtype`` is the legacy uniform
        # spelling; ``self.dtype`` stays the resolved solve dtype.
        self.precision = resolve_precision(precision, dtype)
        self.dtype = self.precision.solve_dtype
        self.maxiter = maxiter
        # Pallas lane for every solver this service builds, resolved at
        # construction ("compiled" or "interpret"; "auto" — the default
        # — follows the backend).  ``pallas_interpret`` is the legacy
        # bool spelling: True pins the interpreter.  The resolved value
        # is the service's report of which lane runs.
        from repro.kernels.pa_elasticity.ops import resolve_lane

        self.pallas_lane = resolve_lane(pallas_lane, interpret=pallas_interpret)
        self.pallas_interpret = self.pallas_lane == "interpret"
        self._check_policy(self.precision)
        # Policy a stalled reduced-precision row is re-solved under, or
        # None where ``f64`` cannot run (a TPU, the compiled Pallas
        # lane): such a row retires unconverged with ``stalled=True``.
        self.fallback_precision = (
            None
            if policy_refusal(PRECISION_POLICIES["f64"], assembly,
                              self.pallas_lane)
            else "f64"
        )
        self.chunk_iters = chunk_iters
        # Chunk scheduling policy for the continuous path.  The old
        # ``chunk_iters < 1`` check generalizes to the policy-bound
        # validation inside make_chunk_policy (min_chunk <= max_chunk,
        # both >= 1), so a bad bound fails HERE with a message naming
        # the offending parameter, not mid-flight.
        self.chunk_policy = make_chunk_policy(
            chunk_policy,
            chunk_iters=chunk_iters,
            min_chunk=min_chunk,
            max_chunk=max_chunk,
        )
        # Replayable record of recent scheduling decisions, bounded to
        # the last 4096 (see repro.serve.chunk_policy.SchedulerTrace);
        # the cumulative stats counters don't depend on the trimming.
        self.trace = SchedulerTrace()
        self._step_index = 0
        # Scenario-axis device mesh shared by every solver this service
        # builds (int = "first n devices"); see repro.distributed.sharding.
        from repro.distributed.sharding import normalize_scenario_mesh

        self.mesh, self.n_shards = normalize_scenario_mesh(mesh)
        self._solvers: OrderedDict[tuple, BatchedGMGSolver] = OrderedDict()
        self._queue: list[tuple[int, SolveRequest]] = []
        self._flights: dict[tuple, _Flight] = {}
        self._completed: dict[int, SolveReport] = {}
        # Tickets the continuous engine re-queued onto the f64 path
        # after a reduced-precision flight flagged them as stagnated;
        # their eventual reports carry fallback=True.
        self._fallback_tickets: set[int] = set()
        self._next_ticket = 0
        # Observability: every counter the service used to keep in a
        # plain ``stats`` dict now lives on a typed metrics registry,
        # labeled by (p, refine, policy, devices); ``stats`` is a
        # read-only view so existing readers see the same keys/values.
        # ``clock`` is injectable for deterministic span/latency tests.
        self.clock = clock
        self.registry = (
            registry if registry is not None else MetricsRegistry()
        )
        self.stats = _StatsView(self.registry)
        self.spans = None
        self.watchdog = None
        self._t_submit: dict[int, float] = {}
        self._next_flight_idx = 0
        if spans is not None:
            self.attach_spans(spans)

    # -- observability -------------------------------------------------------
    def attach_watchdog(self, timeout_s: float, on_timeout=None):
        """Arm a :class:`repro.distributed.elastic.StepWatchdog` as a
        hang detector on ``step()``: a step exceeding ``timeout_s``
        increments the ``watchdog_fires`` counter (labeled policy/
        devices) and emits a ``watchdog_fire`` span on the engine track,
        then calls ``on_timeout(elapsed_s)`` if given (escalation hook —
        at pod scale, evicting the straggler).  Returns the watchdog so
        callers can read ``timeouts``/``slowest``."""
        from repro.distributed.elastic import StepWatchdog

        def fire(elapsed: float) -> None:
            self.registry.counter(
                "service_watchdog_fires_total",
                _STAT_HELP["watchdog_fires"],
                policy=self.chunk_policy.name,
                devices=self.n_shards,
            ).inc()
            if self.spans is not None:
                t = self.clock()
                self.spans.emit(
                    "watchdog_fire", cat="engine", tid=0, start=t, end=t,
                    elapsed_s=elapsed, step=self._step_index,
                )
            if on_timeout is not None:
                on_timeout(elapsed)

        self.watchdog = StepWatchdog(timeout_s, on_timeout=fire)
        return self.watchdog

    def attach_spans(self, recorder) -> None:
        """Install a :class:`repro.obs.spans.SpanRecorder`: the host
        spans of :data:`SERVICE_SPANS` and the per-request
        ``queue_wait`` / ``solve`` spans are recorded there, stamped by
        the service clock.  Recording adds no device sync and changes no
        scheduling decision; device time comes from a profiler trace."""
        self.spans = recorder
        recorder.thread_name(0, "engine")

    @contextmanager
    def _span(self, name: str, *, cat: str = "engine", tid: int = 0, **args):
        """One host span: a ``service.<name>`` profiler annotation (next
        to free while no profiler runs) and, with a recorder attached,
        the same interval recorded as ``name``.  The body may add args
        to the yielded dict."""
        rec = self.spans
        t0 = self.clock() if rec is not None else 0.0
        with jax.profiler.TraceAnnotation(f"service.{name}"):
            yield args
        if rec is not None:
            rec.emit(name, cat=cat, tid=tid, start=t0, end=self.clock(),
                     **args)

    def _labels(self, key: tuple) -> dict:
        """The uniform service label set for a flight key."""
        return {
            "p": key[0],
            "refine": key[1],
            "policy": self.chunk_policy.name,
            "devices": self.n_shards,
            "precision": key[-1],
        }

    def _inc(self, stat: str, key: tuple, n: int = 1) -> None:
        self.registry.counter(
            f"service_{stat}_total", _STAT_HELP[stat], **self._labels(key)
        ).inc(n)

    def _observe(self, name: str, help: str, key: tuple, v: float) -> None:
        self.registry.histogram(name, help, **self._labels(key)).observe(v)

    def latency_summary(
        self, qs: tuple[float, ...] = (0.5, 0.9, 0.99)
    ) -> dict[str, float]:
        """Request-latency quantiles merged across every label set —
        the one percentile implementation the benchmark and the CLI
        summary both report (empty dict before any request finished)."""
        h = self.registry.merged_histogram("request_latency_seconds")
        if h is None or h.count == 0:
            return {}
        out = {f"p{round(q * 100):02d}": h.quantile(q) for q in qs}
        out["mean"] = h.sum / h.count
        out["count"] = float(h.count)
        return out

    # -- queue ---------------------------------------------------------------
    def _policy_for(self, req: SolveRequest) -> PrecisionPolicy:
        """The request's resolved precision policy (service default when
        the request doesn't name one)."""
        if req.precision is None:
            return self.precision
        return resolve_precision(req.precision)

    def _check_policy(self, policy: PrecisionPolicy) -> None:
        """Refuse, before any batch state exists, a policy whose solvers
        could not be built here (see ``precision.policy_refusal``)."""
        check_policy(policy, self.assembly, self.pallas_lane)

    def group_key(self, req: SolveRequest) -> tuple:
        """Flight/compile-cache key.  Leads with (p, refine, shape) but
        also covers everything else a compiled program is specialized
        on — lengths, attribute layout, the affine map, and (last) the
        resolved precision-policy name: two meshes of equal shape but
        different geometry never share a solver, and neither do two
        policies (their programs differ in every dtype)."""
        mesh = req.coarse_mesh if req.coarse_mesh is not None else beam_hex()
        lm = mesh.linear_map
        return (
            req.p,
            req.refine,
            mesh.shape,
            mesh.lengths,
            tuple(int(a) for a in mesh.attributes()),
            None if lm is None else tuple(map(tuple, np.asarray(lm).tolist())),
            self._policy_for(req).name,
        )

    def submit(self, request: SolveRequest) -> int:
        """Non-blocking intake: enqueue a request and return its ticket.

        Safe to call while flights are mid-chunk — the next ``step``
        admits it into the first free slot of its discretization key.
        Invalid requests fail HERE, before any batch state is touched:
        attribute dicts must cover every mesh attribute with positive
        coefficients, and per-element ``(lam_e, mu_e)`` array pairs must
        have shape (nelem_fine,) = (coarse_mesh.nelem * 8**refine,) with
        every entry positive.  Error messages name the offending
        attribute / element index and the expected shape."""
        if request.materials is not None:
            mesh = (
                request.coarse_mesh
                if request.coarse_mesh is not None
                else beam_hex()
            )
            m = request.materials
            if isinstance(m, dict):
                check_material_dict(
                    m, mesh.attributes(), where="request materials"
                )
            else:
                try:
                    lam_e, mu_e = m
                except (TypeError, ValueError):
                    raise TypeError(
                        f"request materials: expected an attribute->"
                        f"(lambda, mu) dict or a (lam_e, mu_e) array "
                        f"pair, got {type(m).__name__!r}"
                    ) from None
                nelem_fine = mesh.nelem * 8**request.refine
                check_material_fields(
                    lam_e,
                    mu_e,
                    nelem_fine,
                    where=(
                        f"request materials (p={request.p}, "
                        f"refine={request.refine}, coarse mesh "
                        f"{mesh.shape})"
                    ),
                )
        # unknown precision names, and policies this service's kernel
        # lane cannot run, fail at intake
        self._check_policy(self._policy_for(request))
        ticket = self._next_ticket
        self._next_ticket += 1
        self._t_submit[ticket] = self.clock()
        self._queue.append((ticket, request))
        return ticket

    def bucket_for(self, n: int) -> int:
        """Smallest padding bucket (1/2/4/.../max_batch) holding n rows,
        rounded up to a multiple of the scenario-mesh device count (the
        sharded axis must divide the mesh; the extra rows are
        born-converged padding and are never surfaced)."""
        b = 1
        while b < n and b < self.max_batch:
            b *= 2
        b = min(b, self.max_batch)
        m = self.n_shards
        return -(-b // m) * m

    # -- cache ---------------------------------------------------------------
    def _solver_for(self, key: tuple, req: SolveRequest):
        """(solver, cache_hit, t_setup) for a discretization key."""
        if key in self._solvers:
            self._solvers.move_to_end(key)
            self._inc("cache_hits", key)
            return self._solvers[key], True, 0.0
        t0 = self.clock()
        cmesh = req.coarse_mesh if req.coarse_mesh is not None else beam_hex()
        with self._span("build", p=req.p, refine=req.refine):
            solver = BatchedGMGSolver(
                cmesh,
                req.refine,
                req.p,
                assembly=self.assembly,
                precision=self._policy_for(req),
                maxiter=self.maxiter,
                pallas_lane=self.pallas_lane,
                mesh=self.mesh,
            )
        self._solvers[key] = solver
        self._inc("cache_misses", key)
        while len(self._solvers) > self.cache_size:
            evicted, _ = self._solvers.popitem(last=False)  # LRU eviction
            if evicted in self._flights:
                # Never evict a solver with rows in flight: reinsert it as
                # most-recently-used and drop the next-oldest idle entry.
                self._solvers[evicted] = self._flights[evicted].solver
                self._solvers.move_to_end(evicted, last=False)
                for k in list(self._solvers):
                    if k not in self._flights:
                        del self._solvers[k]
                        break
        return solver, False, self.clock() - t0

    # -- continuous batching -------------------------------------------------
    def step(self) -> int:
        """Advance the continuous engine by one bounded chunk per
        in-flight discretization key: retire converged rows (their
        reports become drainable), refill freed slots from the queue,
        admit mid-flight submissions, and re-bucket each step program to
        the smallest sufficient batch size.  The chunk length (and, for
        the shard-adaptive policy, the refill placement) comes from
        ``self.chunk_policy``; every flight with live rows dispatches
        exactly one chunk per step — no flight is ever starved — and
        every decision lands in ``self.trace``.  Returns the number of
        requests completed by this step.

        With a watchdog attached (:meth:`attach_watchdog`) the whole
        step body runs under its monitor: a step that exceeds the
        timeout — a wedged device, a pathological compile — fires the
        ``watchdog_fires`` counter and a span without interrupting the
        step itself (detection, not preemption; escalation is the
        callback's job)."""
        with self._span("step", step=self._step_index + 1) as args:
            if self.watchdog is not None:
                with self.watchdog.step():
                    completed = self._step_body()
            else:
                completed = self._step_body()
            args["completed"] = completed
        return completed

    def _step_body(self) -> int:
        self._step_index += 1
        rec = self.spans
        done_before = len(self._completed)
        qgroups: OrderedDict[tuple, list[tuple[int, SolveRequest]]] = (
            OrderedDict()
        )
        for t, req in self._queue:
            qgroups.setdefault(self.group_key(req), []).append((t, req))
        keys = list(self._flights)
        keys += [k for k in qgroups if k not in self._flights]
        admitted: set[int] = set()
        for key in keys:
            flight = self._flights.get(key)
            queued = qgroups.get(key, [])
            if flight is None:
                solver, hit, t_setup = self._solver_for(key, queued[0][1])
                flight = _Flight(
                    key, solver, hit, t_setup, tid_base=self._flight_tid()
                )
                self._flights[key] = flight
                if rec is not None:
                    rec.thread_name(
                        flight.tid_base,
                        f"flight p={key[0]} refine={key[1]}",
                    )
            with self._span("retire", cat="flight", tid=flight.tid_base):
                self._retire(flight)
            if not flight.live_rows() and not queued:
                del self._flights[key]
                continue
            with self._span("admit", cat="flight", tid=flight.tid_base):
                admitted |= self._admit(flight, queued)
            if flight.live_rows():
                self._launch_chunk(flight)
            else:
                del self._flights[key]
        if admitted:
            self._queue = [
                (t, r) for t, r in self._queue if t not in admitted
            ]
        return len(self._completed) - done_before

    def _flight_tid(self) -> int:
        """Next flight's Chrome-trace track block: tid 0 is the engine;
        each flight takes a block of consecutive tids (the flight track
        plus one per possible slot, slots bounded by the device-aligned
        bucket, which may exceed max_batch by up to n_shards-1)."""
        idx = self._next_flight_idx
        self._next_flight_idx += 1
        return 1 + idx * (self.max_batch + self.n_shards + 1)

    def idle(self) -> bool:
        """True when no requests are queued or in flight."""
        return not self._queue and not self._flights

    def run_until_idle(self, max_steps: int = 1_000_000) -> None:
        """Drive ``step`` until every submitted request has completed."""
        steps = 0
        while not self.idle():
            self.step()
            steps += 1
            if steps >= max_steps:
                raise RuntimeError(
                    f"continuous engine did not drain in {max_steps} steps"
                )

    def drain(self) -> list[SolveReport]:
        """Non-blocking: pop every completed report (submission order).
        Pairs with ``submit`` — what's still in flight stays in flight;
        a report is never yielded twice, and padding/device-alignment
        rows never appear here at all."""
        out = [self._completed.pop(t) for t in sorted(self._completed)]
        return out

    def solve_continuous(
        self, requests: list[SolveRequest]
    ) -> list[SolveReport]:
        """Submit ``requests``, run the continuous engine until idle, and
        return their reports in submission order (other tickets, if any,
        stay drainable)."""
        tickets = [self.submit(r) for r in requests]
        self.run_until_idle()
        return [self._completed.pop(t) for t in tickets]

    def _finalize_chunk(self, flight: _Flight) -> None:
        """Fold the last chunk's consumed vector into the host-side
        scheduling state: advance the per-row iteration mirror and patch
        the awaiting trace record (consumed, wasted slot-iterations).
        Runs at the retire pass — the first point the host touches the
        device state anyway — so the policy costs no extra syncs."""
        if flight.pending_consumed is None:
            return
        consumed = np.asarray(flight.pending_consumed)
        flight.pending_consumed = None
        flight.row_iters += consumed.astype(np.int64)
        d = flight.last_decision
        flight.last_decision = None
        if d is not None:
            d.consumed = tuple(int(c) for c in consumed)
            d.wasted = wasted_iterations(consumed, d.live_slots)
            self._inc("wasted_iters", flight.key, d.wasted)

    def _retire(self, flight: _Flight) -> None:
        """Emit reports for rows that stopped iterating (converged or hit
        maxiter) during the previous chunk and free their slots,
        recording each real row's retire cadence in the flight's history
        ring buffer (the adaptive policies' signal)."""
        self._finalize_chunk(flight)
        if flight.chunks == 0 or flight.state is None:
            return
        active = np.asarray(flight.state.active)
        nom = np.asarray(flight.state.nom)
        nom0 = np.asarray(flight.state.nom0)
        thr = np.asarray(flight.state.threshold)
        iters = np.asarray(flight.state.iters)
        stalled = np.asarray(flight.state.stalled)
        reduced = resolve_precision(flight.key[-1]).reduced
        live = flight.live_rows()
        ndof = flight.solver.fine_space.ndof
        now = self.clock()
        rec = self.spans
        for i in live:
            if active[i]:
                continue
            slot = flight.slots[i]
            req = slot.request
            converged = bool(nom[i] <= thr[i])
            row_stalled = reduced and bool(stalled[i]) and not converged
            if row_stalled and self.fallback_precision is not None:
                # Stagnated under the reduced policy (or failed the true-
                # residual audit): re-queue the SAME ticket onto the f64
                # path with its original submit time, so the fallback is
                # a scheduling event, not a failed report.  The eventual
                # f64 report carries ``fallback=True``.  With no f64
                # path the row retires below, unconverged and stalled.
                self._queue.append(
                    (slot.ticket,
                     dataclasses.replace(
                         req, precision=self.fallback_precision))
                )
                self._t_submit[slot.ticket] = slot.t_submit
                self._fallback_tickets.add(slot.ticket)
                self._inc("precision_fallbacks", flight.key)
                flight.slots[i] = None
                continue
            rel = (
                float(np.sqrt(nom[i]) / np.sqrt(nom0[i]))
                if nom0[i] > 0
                else 0.0
            )
            wall = now - slot.t_admit
            self._observe(
                "request_latency_seconds",
                "Admission-to-retirement latency per request.",
                flight.key,
                wall,
            )
            if rec is not None:
                # Per ticket, queue_wait + this span == submit-to-retire
                # wall, exactly: both are stamped by the service clock.
                rec.emit(
                    "solve",
                    cat="request",
                    tid=flight.tid_base + 1 + i,
                    start=slot.t_admit,
                    end=now,
                    ticket=slot.ticket,
                    iterations=int(iters[i]),
                    converged=converged,
                    queue_wait=slot.t_admit - slot.t_submit,
                )
            fell_back = slot.ticket in self._fallback_tickets
            self._fallback_tickets.discard(slot.ticket)
            self._completed[slot.ticket] = SolveReport(
                request=req,
                key=flight.key,
                iterations=int(iters[i]),
                converged=converged,
                final_rel_norm=rel,
                ndof=ndof,
                batch_size=len(live),
                generation=flight.chunks - 1,
                cache_hit=flight.cache_hit,
                t_setup=flight.t_setup,
                t_solve=now - slot.t_admit,
                born_converged=bool(
                    iters[i] == 0 and converged and nom0[i] == 0
                ),
                padded_rows=flight.bucket,
                precision=flight.key[-1],
                fallback=fell_back,
                stalled=row_stalled,
                ticket=slot.ticket,
                x=np.asarray(flight.state.x[i])
                if req.keep_solution
                else None,
            )
            flight.slots[i] = None
            # Retire cadence for the policies: total iterations this row
            # ran before retiring.  Born-converged rows (0 iterations)
            # teach nothing about cadence and are skipped.
            if iters[i] > 0:
                flight.retire_history.append(int(iters[i]))

    def _admit(
        self, flight: _Flight, queued: list[tuple[int, SolveRequest]]
    ) -> set[int]:
        """Refill free slots from the queue, re-bucketing the pinned
        state to the smallest sufficient batch size first.  Returns the
        admitted tickets; leaves ``flight.pending_reset`` marking every
        row the next chunk must (re)initialize."""
        solver = flight.solver
        live = flight.live_rows()
        n_live = len(live)
        take = queued[: self.max_batch - n_live]
        bucket = self.bucket_for(max(n_live + len(take), 1))

        if flight.state is None:
            flight.state = solver.empty_state(bucket)
            flight.prep = solver.empty_prep(bucket)
            flight.slots = [None] * bucket
            ne = solver.fine_space.nelem
            flight.lam = np.zeros((bucket, ne))
            flight.mu = np.zeros((bucket, ne))
            flight.mat_digest = np.zeros((bucket,), dtype=object)
            flight.tr = np.zeros((bucket, 3))
            flight.tol = np.full((bucket,), 1e-6)
            flight.prep_valid = np.zeros((bucket,), dtype=bool)
            flight.prep_digest = np.zeros((bucket,), dtype=object)
            flight.prep_lam = np.zeros((bucket, ne))
            flight.prep_mu = np.zeros((bucket, ne))
            flight.row_iters = np.zeros((bucket,), dtype=np.int64)
            flight.bucket = bucket
            reset = np.ones((bucket,), dtype=bool)
        elif bucket != flight.bucket:
            # Re-bucket: keep live rows (bitwise), fill the rest with
            # placeholder copies of an existing row — every placeholder
            # is reset below before the next chunk reads it.
            filler = live[0] if live else 0
            rows = live + [filler] * (bucket - n_live)
            flight.state, flight.prep = solver.take_rows(
                flight.state, flight.prep, rows
            )
            flight.slots = [flight.slots[i] for i in live] + [None] * (
                bucket - n_live
            )
            idx = np.asarray(rows)
            flight.lam = flight.lam[idx]
            flight.mu = flight.mu[idx]
            flight.mat_digest = flight.mat_digest[idx]
            flight.tr = flight.tr[idx]
            flight.tol = flight.tol[idx]
            flight.prep_valid = flight.prep_valid[idx]
            flight.prep_digest = flight.prep_digest[idx]
            flight.prep_lam = flight.prep_lam[idx]
            flight.prep_mu = flight.prep_mu[idx]
            flight.row_iters = flight.row_iters[idx]
            flight.bucket = bucket
            reset = np.zeros((bucket,), dtype=bool)
            reset[n_live:] = True
            self._inc("rebuckets", flight.key)
        else:
            reset = np.zeros((bucket,), dtype=bool)
        if (
            flight.pending_reset is not None
            and len(flight.pending_reset) == bucket
        ):
            # A pre-marked reset from outside the admit cycle — e.g. an
            # elastic restore whose re-bucketed filler rows must be
            # re-initialized before the next chunk reads them.  OR it in
            # rather than overwrite; a re-bucketing above (length
            # mismatch) already resets every non-live row, subsuming it.
            reset |= flight.pending_reset

        admitted: set[int] = set()
        free = [i for i, s in enumerate(flight.slots) if s is None]
        # Refill placement is a policy decision: the default policies
        # fill ascending slot indices (the pre-policy behavior); the
        # shard-adaptive policy targets the least-loaded device so
        # retires drain whole shards as early as possible.  Placement
        # never changes numerics — rows are slot-independent.
        slot_devs = scenario_row_devices(flight.bucket, self.n_shards)
        order = self.chunk_policy.placement(
            free,
            [int(d) for d in slot_devs],
            [int(slot_devs[i]) for i in flight.live_rows()],
        )
        refills: list[RefillPlacement] = []
        now = self.clock()
        rec = self.spans
        for (ticket, req), row in zip(take, order):
            if flight.slots[row] is not None:  # pragma: no cover
                raise AssertionError(f"slot {row} double-assigned")
            t_submit = self._t_submit.pop(ticket, now)
            flight.slots[row] = _Slot(ticket, req, now, t_submit=t_submit)
            self._observe(
                "request_queue_wait_seconds",
                "Submit-to-admission wait per request.",
                flight.key,
                now - t_submit,
            )
            if rec is not None:
                tid = flight.tid_base + 1 + row
                rec.thread_name(tid, f"p={flight.key[0]} slot {row}")
                rec.emit(
                    "queue_wait",
                    cat="request",
                    tid=tid,
                    start=t_submit,
                    end=now,
                    ticket=ticket,
                )
            lam, mu = solver.pack_materials([_req_materials(req)])
            flight.lam[row] = np.asarray(lam[0])
            flight.mu[row] = np.asarray(mu[0])
            flight.mat_digest[row] = _material_digest(
                flight.lam[row], flight.mu[row], precision=flight.key[-1]
            )
            flight.tr[row] = req.traction
            flight.tol[row] = req.rel_tol
            reset[row] = True
            admitted.add(ticket)
            refills.append(
                RefillPlacement(
                    ticket=ticket, slot=row, device=int(slot_devs[row])
                )
            )
            self._inc("refills", flight.key)
        # Padding rows being reset borrow a real row's materials (keeps
        # the batched operators SPD) with a zero traction: b == 0 makes
        # them born-converged, so they cost 0 bpcg iterations and are
        # never surfaced to callers.
        occupied = flight.live_rows()
        if occupied:
            src = occupied[0]
            for row in range(flight.bucket):
                if flight.slots[row] is None and reset[row]:
                    flight.lam[row] = flight.lam[src]
                    flight.mu[row] = flight.mu[src]
                    flight.mat_digest[row] = flight.mat_digest[src]
                    flight.tr[row] = 0.0
                    flight.tol[row] = 1e-6
        flight.pending_reset = reset if reset.any() else None
        flight.pending_refills = tuple(refills)
        return admitted

    def _refresh_prep(self, flight: _Flight, reset: np.ndarray) -> dict:
        """Make every reset row's prep match its (new) materials.  Rows
        whose folded per-element fields content-match an already-valid
        row — digest equality first (O(1) per candidate, heterogeneous
        fields included), confirmed bitwise against the snapshot — reuse
        that row's derived data with a cheap device gather (prep depends
        only on materials); only genuinely new material configurations
        pay the ``prepare`` power iterations + refactorization.  Returns
        the row counts the ``prep`` span records."""
        solver = flight.solver
        src_rows, dst_rows, unresolved = [], [], []
        sources = [s for s in range(flight.bucket) if flight.prep_valid[s]]
        for r in np.flatnonzero(reset):
            dig = flight.mat_digest[r]
            match = next(
                (
                    s
                    for s in sources
                    if flight.prep_digest[s] == dig
                    and np.array_equal(flight.prep_lam[s], flight.lam[r])
                    and np.array_equal(flight.prep_mu[s], flight.mu[r])
                ),
                None,
            )
            if match is None:
                unresolved.append(int(r))
            else:
                src_rows.append(match)
                dst_rows.append(int(r))
        if dst_rows:
            # copy_prep_rows gathers every source before any destination
            # is written, so a retiring row can donate its old prep even
            # while being refilled itself.
            flight.prep = solver.copy_prep_rows(
                flight.prep, src_rows, dst_rows
            )
            self._inc("prep_row_copies", flight.key, len(dst_rows))
        if unresolved:
            mask = np.zeros((flight.bucket,), dtype=bool)
            mask[unresolved] = True
            flight.prep = solver.prepare(
                jnp.asarray(flight.lam, solver.dtype),
                jnp.asarray(flight.mu, solver.dtype),
                mask,
                flight.prep,
            )
            self._inc("prep_calls", flight.key)
        flight.prep_valid[reset] = True
        flight.prep_digest[reset] = flight.mat_digest[reset]
        flight.prep_lam[reset] = flight.lam[reset]
        flight.prep_mu[reset] = flight.mu[reset]
        return {
            "rows_reset": int(reset.sum()),
            "rows_copied": len(dst_rows),
            "rows_prepared": len(unresolved),
        }

    def _launch_chunk(self, flight: _Flight) -> None:
        """One bounded advance of the flight's compiled step program,
        re-initializing any rows flagged by the last admit.  The chunk
        length comes from the policy's view of the in-flight mix (the
        host-side iteration mirror, the per-device row map and the
        retire-history ring buffer); the decision is appended to
        ``self.trace`` and completed by the next retire pass."""
        solver = flight.solver
        reset = flight.pending_reset
        do_reset = reset is not None
        if do_reset:
            with self._span("prep", cat="flight", tid=flight.tid_base) as a:
                a.update(self._refresh_prep(flight, reset))
            flight.row_iters[reset] = 0
        mask = (
            reset if do_reset else np.zeros((flight.bucket,), dtype=bool)
        )
        live = flight.live_rows()
        slot_devs = scenario_row_devices(flight.bucket, self.n_shards)
        obs = ChunkObservation(
            live_iters=tuple(int(flight.row_iters[i]) for i in live),
            live_devices=tuple(int(slot_devs[i]) for i in live),
            history=tuple(flight.retire_history),
            bucket=flight.bucket,
            n_devices=self.n_shards,
        )
        k = self.chunk_policy.chunk_for(obs)
        with self._span("dispatch", cat="chunk", tid=flight.tid_base,
                        chunk=k, bucket=flight.bucket, live=len(live)):
            flight.state, flight.pending_consumed = solver.run_chunk(
                flight.tr,
                flight.tol,
                mask,
                flight.state,
                flight.prep,
                k,
                do_reset=do_reset,
            )
        decision = ChunkDecision(
            step=self._step_index,
            key=flight.key,
            policy=self.chunk_policy.name,
            bucket=flight.bucket,
            observation=obs,
            chunk=k,
            refills=flight.pending_refills,
            live_slots=tuple(live),
        )
        self.trace.append(decision)
        flight.last_decision = decision
        flight.pending_refills = ()
        flight.pending_reset = None
        flight.chunks += 1
        self._inc("chunks", flight.key)
        self._inc("chunk_iters_dispatched", flight.key, k)

    # -- generational batching -----------------------------------------------
    def solve(self, requests: list[SolveRequest] | None = None) -> list[SolveReport]:
        """Generational path: drain the queue (plus ``requests``) and
        return one report per request, in submission order.

        Each discretization key's requests are solved in fixed batches
        padded to the smallest sufficient (device-aligned) bucket;
        padding rows are internal and never surfaced.  Materials may be
        attribute dicts or per-element array pairs, mixed freely within
        a batch.  Do not mix with in-flight continuous work — use
        ``solve_continuous`` there."""
        if requests:
            for r in requests:
                self.submit(r)
        pending = [r for _, r in self._queue]
        for t, _ in self._queue:
            self._t_submit.pop(t, None)
        self._queue = []

        # Group by discretization key, preserving submission order.
        groups: OrderedDict[tuple, list[tuple[int, SolveRequest]]] = OrderedDict()
        for i, req in enumerate(pending):
            groups.setdefault(self.group_key(req), []).append((i, req))

        reports: list[SolveReport | None] = [None] * len(pending)
        for key, members in groups.items():
            solver, hit, t_setup = self._solver_for(key, members[0][1])
            for gen, start in enumerate(range(0, len(members), self.max_batch)):
                chunk = members[start : start + self.max_batch]
                gen_reports = self._run_generation(
                    solver, key, chunk, hit or gen > 0, t_setup if gen == 0 else 0.0, gen
                )
                for (i, _), rep in zip(chunk, gen_reports):
                    reports[i] = rep
        return reports  # type: ignore[return-value]

    def _run_generation(
        self,
        solver: BatchedGMGSolver,
        key: tuple,
        chunk: list[tuple[int, SolveRequest]],
        cache_hit: bool,
        t_setup: float,
        generation: int,
    ) -> list[SolveReport]:
        reqs = [r for _, r in chunk]
        n_real = len(reqs)
        # Bucketed padding: the smallest sufficient (device-aligned)
        # bucket, not max_batch, so short generations reuse a cheaper
        # compiled program.  The padding rows themselves (first row's
        # materials, zero traction -> born converged) come from the one
        # shared convention in BatchedGMGSolver.pad_scenarios.
        n_pad = self.bucket_for(n_real) - n_real
        materials, tractions, rel_tols, _ = solver.pad_scenarios(
            [_req_materials(r) for r in reqs],
            [r.traction for r in reqs],
            [r.rel_tol for r in reqs],
            n=n_real + n_pad,
        )

        t0 = self.clock()
        with self._span("generation", cat="generation", generation=generation,
                        batch=n_real, padded_rows=n_real + n_pad):
            res = solver.solve(materials, tractions, rel_tols)
            x = res.x.block_until_ready()
        t_solve = self.clock() - t0
        self._inc("generations", key)
        for _ in reqs:
            self._observe(
                "request_latency_seconds",
                "Admission-to-retirement latency per request.",
                key,
                t_solve,
            )
        iters = np.asarray(res.iterations)
        conv = np.asarray(res.converged)
        fin = np.asarray(res.final_norm)
        ini = np.asarray(res.initial_norm)
        fell_back = np.asarray(res.fallback)
        stalled = np.asarray(res.stalled) & ~conv
        ndof = solver.fine_space.ndof
        out = []
        # Padding rows (s >= n_real) are internal and never reported.
        for s, req in enumerate(reqs):
            rel = float(fin[s] / ini[s]) if ini[s] > 0 else 0.0
            if fell_back[s]:
                self._inc("precision_fallbacks", key)
            out.append(
                SolveReport(
                    request=req,
                    key=key,
                    iterations=int(iters[s]),
                    converged=bool(conv[s]),
                    final_rel_norm=rel,
                    ndof=ndof,
                    batch_size=n_real,
                    generation=generation,
                    cache_hit=cache_hit,
                    t_setup=t_setup,
                    t_solve=t_solve,
                    born_converged=bool(iters[s] == 0 and conv[s] and ini[s] == 0),
                    padded_rows=n_real + n_pad,
                    precision=solver.precision.name,
                    fallback=bool(fell_back[s]),
                    stalled=bool(stalled[s]),
                    x=np.asarray(x[s]) if req.keep_solution else None,
                )
            )
        return out
