"""Device self seconds per credited solve, in the traced window, of the
true-residual audit that follows every chunk under a reduced-precision
policy: one operator apply and one V-cycle (scope ``pcg.audit``)."""

from bench.lib.scopes import device_split


def read(run):
    return device_split(run).get("pcg.audit")
