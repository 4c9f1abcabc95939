"""Share (%) of the traced window in which no operation ran on the
device: 1 - (union of the device's operation intervals) / (window)."""


def read(run):
    tr = run.window_trace
    if tr is None or not tr.ops:
        return None
    lo, hi = run.window_span
    return 100.0 * (1.0 - tr.busy(lo, hi) / (hi - lo))
