"""Device self seconds per credited solve, in the traced window, of the
PCG loop's vector work: dots, axpys, masks, stall bookkeeping and the
loop condition (scope ``pcg.vector``)."""

from bench.lib.scopes import device_split


def read(run):
    return device_split(run).get("pcg.vector")
