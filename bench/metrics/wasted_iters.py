"""Share (%) of the PCG iterations the service dispatched in chunks that
no live row consumed (its wasted_iters and chunk_iters_dispatched
counters), over the run's requests."""


def read(run):
    sent = run.counters.get("chunk_iters_dispatched", 0)
    if not sent:
        return None
    return 100.0 * run.counters.get("wasted_iters", 0) / sent
