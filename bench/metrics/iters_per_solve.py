"""Mean PCG iterations per request (SolveReport.iterations), over the
run's answered requests."""


def read(run):
    its = [rep.iterations for _, rep in run.requests.values() if rep is not None]
    return sum(its) / len(its) if its else None
