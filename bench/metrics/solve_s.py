"""Window seconds per request solved at its rel_tol.  A request the
window completed counts 1; one the window cut counts the share of its
time, from submission to answer, that lay inside the window."""


def read(run):
    return run.window_s / run.credits if run.credits > 0 else None
