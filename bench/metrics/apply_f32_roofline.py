"""Roofline share (%) of one fine-level operator apply of the program at
float32, the V-cycle's dtype, timed from the device trace after the
window; the work is the benchmark's own count (bench.lib.work)."""

from bench.lib.applies import share, time_apply


def measure(run):
    return time_apply(run.config, "float32")


def read(run):
    return share(run, run.extra.get("apply_f32_roofline"))
