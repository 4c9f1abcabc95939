"""Seconds from process start to the opening of the measured window:
cache loads and compiles, hierarchy build, prep and warm-up."""


def read(run):
    return run.setup_s
