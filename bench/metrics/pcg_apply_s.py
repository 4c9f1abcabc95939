"""Device self seconds per credited solve, in the traced window, of the
outer Krylov operator apply: the operations whose outermost ``pcg.*``
scope is ``pcg.apply`` (float64 under the mixed policy)."""

from bench.lib.scopes import device_split


def read(run):
    return device_split(run).get("pcg.apply")
