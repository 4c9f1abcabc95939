"""Device self seconds per credited solve, in the traced window, of the
V-cycle the PCG loop body runs: the operations whose outermost ``pcg.*``
scope is ``pcg.precond`` (the V-cycles that ``pcg.init`` and
``pcg.audit`` run are theirs)."""

from bench.lib.scopes import device_split


def read(run):
    return device_split(run).get("pcg.precond")
