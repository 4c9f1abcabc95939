import pytest

from bench.lib.harness import Run, credits
from bench.lib.traffic import Traffic, load_mix

CONFIG = {"refine": 1, "materials": {"1": [50.0, 50.0], "2": [1.0, 1.0]}}


def test_request_depends_on_seed_and_index_only():
    mix = load_mix("single")
    a, b = Traffic(mix, CONFIG, 2**33 + 1), Traffic(mix, CONFIG, 2**33 + 1)
    assert [a.request(i)["traction"] for i in (3, 0)] == \
        [b.request(i)["traction"] for i in (3, 0)]
    assert a.request(0)["traction"] != a.request(1)["traction"]
    assert Traffic(mix, CONFIG, 7).request(0) != a.request(0)


def test_single_mix_draws_within_its_ranges():
    mix = load_mix("single")
    tr = Traffic(mix, CONFIG, 5)
    for i in range(50):
        r = tr.request(i)
        x, y, z = r["traction"]
        assert x == 0.0 and -3e-3 <= y <= 3e-3 and -2e-2 <= z <= -1e-2
        assert r["rel_tol"] == 1e-6
        assert r["materials"] == {1: (50.0, 50.0), 2: (1.0, 1.0)}
    assert tr.warmup()["rel_tol"] == 1e-2


@pytest.mark.parametrize("clients", [1, 3])
def test_closed_loop_keeps_each_client_one_request(clients):
    tr = Traffic(dict(load_mix("single"), clients=clients), CONFIG, 1)
    assert tr.due(0.0, 0, 0) == clients
    assert tr.due(5.0, clients, 0) == 0
    assert tr.due(9.0, clients, 2) == 2


def test_cut_solve_is_credited_its_share_of_time():
    """Two solves of 40 s; the window closes at 66 s, 26 s into the
    second: it counts 1 + 26/40, so the window reads 40 s a solve, as a
    window of whole solves does."""
    run = Run(config={}, mix={}, device={}, window_s=66.0)
    run.requests = {1: [{}, object()], 2: [{}, object()]}
    run.done_in_window = [1]
    run.submitted_at = {1: 0.0, 2: 40.0}
    run.answered_at = {1: 40.0, 2: 80.0}
    assert credits(run) == pytest.approx(1.65)
    assert run.window_s / credits(run) == pytest.approx(40.0)


def test_unknown_mix_and_loop_are_refused():
    with pytest.raises(FileNotFoundError):
        load_mix("no_such_mix")
    with pytest.raises(ValueError, match="loop"):
        Traffic(dict(load_mix("single"), loop="open"), CONFIG, 1)
