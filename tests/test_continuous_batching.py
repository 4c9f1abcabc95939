"""Continuous-batching tests: resumable bpcg chunk semantics (property-
based: resumption is bit-identical, refilled slots match fresh solves),
the ElasticityService slot-refill engine (randomized-arrival stress
test), and the bucketed compile cache (smallest sufficient bucket, LRU
eviction, zero retraces on cache hits)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.fem.mesh import beam_hex
from repro.solvers.batched import (
    BatchedGMGSolver,
    bpcg,
    bpcg_chunk,
    bpcg_init,
    bpcg_result,
    merge_states,
)
from repro.serve.elasticity_service import ElasticityService, SolveRequest

from tests._hypothesis_compat import given, settings, st

MATS_A = {1: (50.0, 50.0), 2: (1.0, 1.0)}
MATS_B = {1: (80.0, 60.0), 2: (2.0, 1.0)}
MATS_C = {1: (9.0, 9.0), 2: (1.0, 3.0)}


def _spd_batch(seed: int, s: int, n: int):
    rng = np.random.default_rng(seed)
    mats, rhss = [], []
    for _ in range(s):
        m = rng.standard_normal((n, n))
        mats.append(m @ m.T + n * np.eye(n))
        rhss.append(rng.standard_normal(n))
    a = jnp.asarray(np.stack(mats))
    return a, jnp.asarray(np.stack(rhss))


def _matvec(a):
    return lambda x: jnp.einsum("sij,sj->si", a, x)


# -- property: chunked resumption ------------------------------------------
@settings(max_examples=200, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    s=st.integers(1, 4),
    n=st.integers(4, 16),
    k=st.integers(1, 7),
)
def test_chunk_resumption_bit_identical(seed, s, n, k):
    """run_chunk(k) repeated until convergence must produce *bitwise* the
    state of one uninterrupted bpcg run: frozen rows never move, so a
    chunk boundary is invisible to the iteration."""
    a, b = _spd_batch(seed, s, n)
    A = _matvec(a)
    full = bpcg(A, b, rel_tol=1e-10, maxiter=150)

    state = bpcg_init(A, b, rel_tol=1e-10)
    guard = 0
    while bool(jnp.any(state.active)):
        state = bpcg_chunk(A, state, k_iters=k, maxiter=150)
        guard += 1
        assert guard < 1000
    res = bpcg_result(state)

    np.testing.assert_array_equal(np.asarray(res.x), np.asarray(full.x))
    np.testing.assert_array_equal(
        np.asarray(res.iterations), np.asarray(full.iterations)
    )
    np.testing.assert_array_equal(np.asarray(res.final_norm), np.asarray(full.final_norm))
    assert bool(jnp.all(res.converged == full.converged))


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    s=st.integers(2, 4),
    n=st.integers(4, 12),
    warm=st.integers(1, 10),
    row=st.integers(0, 3),
)
def test_slot_refill_matches_fresh_solo_solve(seed, s, n, warm, row):
    """Resetting one row mid-flight (new matrix + RHS, the slot-refill
    primitive) must (a) leave the other rows' trajectories bitwise
    untouched and (b) converge the refilled row to the solution of a
    fresh, uninterrupted solve of its new system."""
    row = row % s
    a, b = _spd_batch(seed, s, n)
    a2, b2 = _spd_batch(seed + 1, s, n)
    A = _matvec(a)
    state = bpcg_init(A, b, rel_tol=1e-10)
    state = bpcg_chunk(A, state, k_iters=warm, maxiter=150)

    # refill `row` with a new system; other rows keep matrix + state
    a_new = a.at[row].set(a2[row])
    b_new = b.at[row].set(b2[row])
    A_new = _matvec(a_new)
    mask = np.zeros((s,), dtype=bool)
    mask[row] = True
    fresh = bpcg_init(A_new, b_new, rel_tol=1e-10)
    merged = merge_states(jnp.asarray(mask), fresh, state)
    # untouched rows: bitwise identical after the merge
    keep = ~mask
    np.testing.assert_array_equal(
        np.asarray(merged.x)[keep], np.asarray(state.x)[keep]
    )
    np.testing.assert_array_equal(
        np.asarray(merged.iters)[keep], np.asarray(state.iters)[keep]
    )
    assert int(merged.iters[row]) == 0

    final = bpcg_chunk(A_new, merged, k_iters=None, maxiter=150)
    res = bpcg_result(final)
    assert bool(res.converged[row])
    solo = bpcg(
        lambda x: jnp.einsum("ij,sj->si", a2[row], x),
        b2[row][None],
        rel_tol=1e-10,
        maxiter=150,
    )
    assert int(res.iterations[row]) == int(solo.iterations[0])
    np.testing.assert_allclose(
        np.asarray(res.x[row]), np.asarray(solo.x[0]), rtol=1e-8, atol=1e-12
    )


def test_chunk_resumption_bit_identical_deterministic():
    """Deterministic spot-check of the resumption property (runs even
    without hypothesis installed)."""
    a, b = _spd_batch(7, 3, 20)
    A = _matvec(a)
    full = bpcg(A, b, rel_tol=1e-12, maxiter=200)
    state = bpcg_init(A, b, rel_tol=1e-12)
    for k in (1, 2, 5, 3, 200):
        state = bpcg_chunk(A, state, k_iters=k, maxiter=200)
    res = bpcg_result(state)
    np.testing.assert_array_equal(np.asarray(res.x), np.asarray(full.x))
    np.testing.assert_array_equal(
        np.asarray(res.iterations), np.asarray(full.iterations)
    )


# -- solver-level step program ---------------------------------------------
@pytest.fixture(scope="module")
def small_solver():
    return BatchedGMGSolver(beam_hex(), 1, 1, maxiter=100)


def test_solver_chunked_matches_monolithic(small_solver):
    """prepare + run_chunk driven to convergence reproduces the one-call
    compiled solve (same iteration counts, solutions to fp roundoff)."""
    solver = small_solver
    mats = [MATS_A, MATS_B]
    tr = np.array([[0.0, 0.0, -1e-2], [0.0, 1e-3, -2e-2]])
    ref = solver.solve(mats, tr, rel_tol=1e-8)

    lam, mu = solver.pack_materials(mats)
    prep = solver.prepare(lam, mu, np.ones(2, bool), solver.empty_prep(2))
    state, consumed = solver.run_chunk(
        tr, 1e-8, np.ones(2, bool), solver.empty_state(2), prep, 4,
        do_reset=True,
    )
    # consumed mirrors the per-row iteration delta of the chunk
    np.testing.assert_array_equal(
        np.asarray(consumed), np.asarray(state.iters)
    )
    guard = 0
    while bool(jnp.any(state.active)):
        prev = np.asarray(state.iters)
        state, consumed = solver.run_chunk(
            tr, 1e-8, np.zeros(2, bool), state, prep, 4, do_reset=False
        )
        np.testing.assert_array_equal(
            np.asarray(consumed), np.asarray(state.iters) - prev
        )
        guard += 1
        assert guard < 100
    np.testing.assert_array_equal(
        np.asarray(state.iters), np.asarray(ref.iterations)
    )
    scale = float(jnp.abs(ref.x).max())
    np.testing.assert_allclose(
        np.asarray(state.x), np.asarray(ref.x), atol=1e-12 * scale
    )


def test_solver_refill_row_matches_fresh_solve(small_solver):
    """Mid-flight slot refill at the solver level: the refilled row's
    final solution matches a fresh compiled solve of that scenario and
    the surviving row is not perturbed."""
    solver = small_solver
    mats = [MATS_A, MATS_B]
    tr = np.array([[0.0, 0.0, -1e-2], [0.0, 1e-3, -2e-2]])
    lam, mu = solver.pack_materials(mats)
    prep = solver.prepare(lam, mu, np.ones(2, bool), solver.empty_prep(2))
    state, _ = solver.run_chunk(
        tr, 1e-8, np.ones(2, bool), solver.empty_state(2), prep, 3,
        do_reset=True,
    )
    # refill row 0 with a new scenario while row 1 keeps iterating
    mats2 = [MATS_C, MATS_B]
    tr2 = np.array([[0.0, -2e-3, 5e-3], [0.0, 1e-3, -2e-2]])
    lam2, mu2 = solver.pack_materials(mats2)
    mask = np.array([True, False])
    prep = solver.prepare(lam2, mu2, mask, prep)
    state, _ = solver.run_chunk(tr2, 1e-8, mask, state, prep, 3, do_reset=True)
    guard = 0
    while bool(jnp.any(state.active)):
        state, _ = solver.run_chunk(
            tr2, 1e-8, np.zeros(2, bool), state, prep, 3, do_reset=False
        )
        guard += 1
        assert guard < 100
    ref = solver.solve(mats2, tr2, rel_tol=1e-8)
    for row in range(2):
        assert int(state.iters[row]) == int(ref.iterations[row])
        scale = float(jnp.abs(ref.x[row]).max())
        np.testing.assert_allclose(
            np.asarray(state.x[row]), np.asarray(ref.x[row]),
            atol=1e-10 * scale,
        )


# -- continuous service: stress --------------------------------------------
def _stress_requests():
    """12 mixed scenarios on the p=1/refine=1 key: three material sets,
    varied tractions, tolerances spanning 1e-4..1e-10."""
    reqs = []
    for i in range(12):
        reqs.append(
            SolveRequest(
                p=1,
                refine=1,
                materials=(MATS_A, MATS_B, MATS_C)[i % 3],
                traction=(0.0, 1e-3 * (i % 4), -1e-2 * (1 + 0.3 * i)),
                rel_tol=(1e-4, 1e-7, 1e-10)[i % 3],
                keep_solution=True,
            )
        )
    return reqs


@pytest.mark.slow
def test_continuous_stress_randomized_arrivals():
    """Randomized arrival order + mid-flight submissions: every request
    gets exactly one report, no slot is double-assigned (the admit path
    asserts), and per-request results are independent of arrival order
    and of which requests shared a batch."""
    base = _stress_requests()
    service = ElasticityService(max_batch=4, chunk_iters=3)

    # order A: staggered arrivals — a few up front, the rest submitted
    # mid-flight while earlier requests are still iterating.
    rng = np.random.default_rng(0)
    order_a = [int(i) for i in rng.permutation(len(base))]
    tickets = {}
    for idx in order_a[:3]:
        tickets[service.submit(base[idx])] = idx
    pending = order_a[3:]
    while pending:
        service.step()  # earlier requests iterate while these arrive
        k = int(rng.integers(1, 3))
        for idx in pending[:k]:
            tickets[service.submit(base[idx])] = idx
        pending = pending[k:]
    service.run_until_idle()
    done = service.drain()
    assert len(done) == len(base)  # exactly one report per request
    by_req_a = {}
    returned = sorted(tickets)
    for t, rep in zip(returned, done):
        by_req_a[tickets[t]] = rep
    assert set(by_req_a) == set(range(len(base)))

    # order B: reversed arrival, same service (warm cache, no retraces
    # needed) — reports must agree request-by-request.
    order_b = list(reversed(range(len(base))))
    tickets_b = {service.submit(base[i]): i for i in order_b}
    service.run_until_idle()
    done_b = service.drain()
    assert len(done_b) == len(base)
    by_req_b = {tickets_b[t]: rep for t, rep in zip(sorted(tickets_b), done_b)}

    for i in range(len(base)):
        ra, rb = by_req_a[i], by_req_b[i]
        assert ra.converged and rb.converged
        assert ra.final_rel_norm <= base[i].rel_tol
        assert ra.iterations == rb.iterations
        scale = max(np.abs(ra.x).max(), 1e-30)
        np.testing.assert_allclose(ra.x, rb.x, atol=1e-8 * scale)
        assert not ra.born_converged


def test_drain_is_incremental_and_ordered():
    """drain() pops completed reports in submission order and never
    yields a ticket twice."""
    service = ElasticityService(max_batch=2, chunk_iters=2)
    reqs = [
        SolveRequest(p=1, refine=0, materials=MATS_A, rel_tol=1e-6,
                     traction=(0.0, 0.0, -1e-2 * (i + 1)))
        for i in range(4)
    ]
    for r in reqs:
        service.submit(r)
    seen = []
    while not service.idle():
        service.step()
        seen += service.drain()
    assert service.drain() == []
    assert len(seen) == 4
    # submission order within the drained stream
    tzs = [r.request.traction[2] for r in seen]
    assert tzs == sorted(tzs, reverse=True)


# -- bucketed compile cache -------------------------------------------------
def test_bucket_for_picks_smallest_sufficient():
    service = ElasticityService(max_batch=8)
    assert [service.bucket_for(n) for n in range(1, 10)] == [
        1, 2, 4, 4, 8, 8, 8, 8, 8,
    ]
    odd = ElasticityService(max_batch=6)
    assert [odd.bucket_for(n) for n in (1, 2, 3, 4, 5, 6, 7)] == [
        1, 2, 4, 4, 6, 6, 6,
    ]


def test_generational_padding_uses_bucket(monkeypatch):
    """3 requests with max_batch=8 pad to bucket 4, not 8."""
    service = ElasticityService(max_batch=8)
    captured = {}
    orig = BatchedGMGSolver.solve

    def spy(self, materials, tractions, rel_tol):
        captured["rows"] = len(materials)
        return orig(self, materials, tractions, rel_tol)

    monkeypatch.setattr(BatchedGMGSolver, "solve", spy)
    reports = service.solve(
        [SolveRequest(p=1, refine=0, materials=MATS_A, rel_tol=1e-6)] * 3
    )
    assert captured["rows"] == 4
    assert len(reports) == 3
    assert all(r.converged for r in reports)


def test_continuous_cache_hit_zero_retrace():
    """Re-running an identical continuous workload must not retrace any
    compiled program: the (key, bucket) step/prepare programs all come
    from the jit cache."""
    service = ElasticityService(max_batch=4, chunk_iters=3)
    reqs = [
        SolveRequest(p=1, refine=0, materials=MATS_A if i % 2 else MATS_B,
                     rel_tol=1e-8, traction=(0.0, 0.0, -1e-2 * (i + 1)))
        for i in range(6)
    ]
    first = service.solve_continuous(reqs)
    assert all(r.converged for r in first)
    assert not first[0].cache_hit
    key = service.group_key(reqs[0])
    solver = service._solvers[key]
    traces = (
        [j._cache_size() for j in solver._jit_chunk.values()],
        solver._jit_prepare._cache_size(),
    )
    hits0 = service.stats["cache_hits"]

    second = service.solve_continuous(reqs)
    assert all(r.converged for r in second)
    assert second[0].cache_hit
    assert service.stats["cache_hits"] > hits0
    assert (
        [j._cache_size() for j in solver._jit_chunk.values()],
        solver._jit_prepare._cache_size(),
    ) == traces
    for ra, rb in zip(first, second):
        assert ra.iterations == rb.iterations


def test_prep_row_reuse_skips_power_iterations():
    """Refilled slots whose materials match an already-prepared row (the
    common serving case: bounded material vocabulary) copy that row's
    derived data instead of re-running prepare — after the initial
    batch, a repeat-material workload pays zero further prepare calls,
    and the results still match the generational path."""
    service = ElasticityService(max_batch=2, chunk_iters=3)
    reqs = [
        SolveRequest(p=1, refine=1, materials=MATS_A if i % 2 else MATS_B,
                     rel_tol=1e-8, traction=(0.0, 0.0, -1e-2 * (i + 1)),
                     keep_solution=True)
        for i in range(6)
    ]
    reports = service.solve_continuous(reqs)
    assert all(r.converged for r in reports)
    assert service.stats["prep_calls"] == 1  # the initial batch only
    assert service.stats["prep_row_copies"] >= 4  # every refill reused
    ref = ElasticityService(max_batch=2).solve(list(reqs))
    for rc, rg in zip(reports, ref):
        assert rc.iterations == rg.iterations
        scale = max(np.abs(rg.x).max(), 1e-30)
        np.testing.assert_allclose(rc.x, rg.x, atol=1e-8 * scale)


# -- scheduler invariants under random interleavings -------------------------
_SCHED_SERVICES: dict = {}


def _sched_service(policy: str) -> ElasticityService:
    """One service per policy, shared across hypothesis examples (the
    compiled programs are paid for once); every example drains fully, so
    only the cumulative counters carry over — tests use deltas.  A
    service left non-idle by a failing example is discarded, so later
    examples (and hypothesis shrinking) never see its leftovers."""
    svc = _SCHED_SERVICES.get(policy)
    if svc is not None and not svc.idle():
        svc = None  # poisoned by a failed example: rebuild
    if svc is None:
        svc = _SCHED_SERVICES[policy] = ElasticityService(
            max_batch=2, chunk_iters=2, chunk_policy=policy
        )
    svc.drain()  # discard any completed-but-undrained leftovers
    return svc


@settings(max_examples=12, deadline=None)
@given(
    policy=st.sampled_from(["fixed", "adaptive", "shard-adaptive"]),
    n_upfront=st.integers(1, 3),
    arrivals=st.lists(st.integers(0, 2), min_size=0, max_size=4),
    mat_idx=st.lists(st.integers(0, 2), min_size=7, max_size=7),
    tight=st.lists(st.booleans(), min_size=7, max_size=7),
)
def test_scheduler_no_starvation_and_stats_match_trace(
    policy, n_upfront, arrivals, mat_idx, tight
):
    """Random submit/step interleavings (slots retire and refill at
    arbitrary points) under every policy:

    * no live row is ever starved — every flight holding live rows
      dispatches exactly one chunk per ``step()`` (checked against the
      trace's per-step decisions);
    * every chunk choice respects the policy bounds;
    * the scheduler counters (``chunks``, ``chunk_iters_dispatched``,
      ``wasted_iters``, ``refills``) are exactly the trace's sums —
      stats can never drift from the replayable record."""
    service = _sched_service(policy)
    base = {
        k: service.stats[k]
        for k in (
            "chunks", "chunk_iters_dispatched", "wasted_iters", "refills"
        )
    }
    # Fresh trace per example: the record is bounded (maxlen trimming
    # drops the OLDEST decisions), so index-based slicing across shared
    # examples would eventually skew — clearing keeps exactly this
    # example's decisions while the cumulative stats counters (compared
    # as deltas) are unaffected.
    service.trace.clear()
    reqs = [
        SolveRequest(
            p=1,
            refine=0,
            materials=(MATS_A, MATS_B, MATS_C)[mat_idx[i]],
            traction=(0.0, 0.0, -1e-2 * (i + 1)),
            rel_tol=1e-10 if tight[i] else 1e-4,
        )
        for i in range(len(mat_idx))
    ]
    it = iter(reqs)
    submitted = 0

    def step_and_check():
        service.step()
        decided = {
            d.key for d in service.trace.decisions
            if d.step == service._step_index
        }
        # every flight still holding live rows was dispatched this step
        for key, flight in service._flights.items():
            assert not flight.live_rows() or key in decided, (
                f"flight {key} starved at step {service._step_index}"
            )

    for _ in range(n_upfront):
        service.submit(next(it))
        submitted += 1
    for k in arrivals:
        step_and_check()
        for _ in range(k):
            try:
                service.submit(next(it))
                submitted += 1
            except StopIteration:
                break
    guard = 0
    while not service.idle():
        step_and_check()
        guard += 1
        assert guard < 500
    done = service.drain()
    assert len(done) == submitted  # exactly one report per request
    assert all(r.converged for r in done)

    decisions = service.trace.decisions
    pol = service.chunk_policy
    for d in decisions:
        assert pol.min_chunk <= d.chunk <= pol.max_chunk
        assert d.wasted >= 0
        assert len(d.consumed) == d.bucket  # outcome was finalized
    delta = {k: service.stats[k] - v for k, v in base.items()}
    assert delta["chunks"] == len(decisions)
    assert delta["chunk_iters_dispatched"] == sum(d.chunk for d in decisions)
    assert delta["wasted_iters"] == sum(d.wasted for d in decisions)
    assert delta["refills"] == sum(len(d.refills) for d in decisions)
    # the recorded observations replay to the recorded choices
    assert [pol.chunk_for(d.observation) for d in decisions] == [
        d.chunk for d in decisions
    ]


def test_continuous_lru_eviction_fires_at_capacity():
    """cache_size=1: a second discretization key evicts the first's
    solver; re-solving the first key is a cache miss again."""
    service = ElasticityService(max_batch=2, cache_size=1, chunk_iters=4)
    service.solve_continuous([SolveRequest(p=1, refine=0, rel_tol=1e-6)])
    service.solve_continuous([SolveRequest(p=1, refine=1, rel_tol=1e-6)])
    assert len(service._solvers) == 1
    rep = service.solve_continuous(
        [SolveRequest(p=1, refine=0, rel_tol=1e-6)]
    )[0]
    assert not rep.cache_hit
    assert service.stats["cache_misses"] == 3


def test_in_flight_solver_never_evicted():
    """The LRU never drops a solver whose flight still has live rows:
    a new key arriving mid-flight evicts an idle entry instead."""
    service = ElasticityService(max_batch=2, cache_size=1, chunk_iters=1)
    t0 = service.submit(
        SolveRequest(p=1, refine=1, materials=MATS_A, rel_tol=1e-12)
    )
    service.step()  # key A in flight
    key_a = service.group_key(SolveRequest(p=1, refine=1))
    assert key_a in service._flights
    service.submit(SolveRequest(p=1, refine=0, materials=MATS_B, rel_tol=1e-8))
    service.run_until_idle()
    done = service.drain()
    assert len(done) == 2
    assert all(r.converged for r in done)
    assert done[0].request.rel_tol == 1e-12  # ticket t0 surfaced first
    assert t0 == 0
