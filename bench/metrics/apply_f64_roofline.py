"""Roofline share (%) of one fine-level operator apply of the program at
float64, the outer Krylov dtype of the mixed policy (the chip emulates
it), timed from the device trace after the window."""

from bench.lib.applies import share, time_apply


def measure(run):
    if run.config["service"]["precision"] not in ("mixed", "f64"):
        return {}
    return time_apply(run.config, "float64")


def read(run):
    return share(run, run.extra.get("apply_f64_roofline"))
