"""Seconds per credited solve, in the traced window, in which the device
ran no program while the service's host work held it: idle stretches
outside program runs whose innermost covering host span is a
``service.*`` span (retire, admit, prep, dispatch, ...)."""

from bench.lib.scopes import idle_split, service_idle


def read(run):
    return service_idle(idle_split(run))
