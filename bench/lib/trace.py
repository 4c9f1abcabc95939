"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

Device planes are those named ``/device:<KIND>:<n>`` other than the
host's; on a TPU each has an ``XLA Ops`` line (one event per executed
operation) and an ``XLA Modules`` line (one event per program run).
Device and host planes share the profiler's clock to about a
millisecond (a v5e trace put a program's device run 1.2 ms before the
host call that launched it), so a host span such as the benchmark's
``bench.window`` annotation of tens of seconds bounds the device events
inside it.  Times here are in seconds.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

__all__ = ["Trace", "load", "find_xplane", "union", "gaps"]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Trace:
    # per device plane: list of (name, start_s, end_s)
    ops: dict = field(default_factory=dict)
    modules: dict = field(default_factory=dict)
    # host spans of every host thread: (name, start_s, end_s)
    host: list = field(default_factory=list)

    def span(self, name: str):
        """(start, end) of the first host span called ``name``."""
        for n, s, e in self.host:
            if n == name:
                return s, e
        raise KeyError(f"no host span {name!r} in the trace")

    def busy(self, lo: float, hi: float) -> float:
        """Seconds in [lo, hi] in which an operation ran, averaged over
        the device planes."""
        if not self.ops:
            return 0.0
        return sum(
            union(ev, lo, hi) for ev in self.ops.values()
        ) / len(self.ops)

    def op_seconds(self, lo: float, hi: float) -> dict:
        """Device self seconds per operation name for the operations that
        start inside [lo, hi], averaged over the device planes.  An
        operation's self time leaves out the operations nested in it (a
        while loop's body), so the values add up to the busy time."""
        out: dict[str, float] = {}
        for ev in self.ops.values():
            for n, s, t in self_times(ev):
                if lo <= s < hi:
                    out[n] = out.get(n, 0.0) + t
        k = max(len(self.ops), 1)
        return {n: v / k for n, v in out.items()}

    def module_times(self, prefix: str) -> list:
        """Durations of every run of the programs whose name starts with
        ``prefix``, on every device plane."""
        return [
            e - s
            for ev in self.modules.values()
            for n, s, e in ev
            if n.startswith(prefix)
        ]

    def idle_gaps(self, lo: float, hi: float, top: int = 10) -> list:
        """The longest stretches in [lo, hi] with no operation on the
        first device plane, each named by the innermost ``bench.`` host
        span covering its start (``"none"`` where none does)."""
        if not self.ops:
            return []
        ev = next(iter(self.ops.values()))
        spans = [h for h in self.host if h[0].startswith("bench.")]
        out = []
        for s, e in gaps(ev, lo, hi):
            cover = [h for h in spans if h[1] <= s < h[2]]
            name = min(cover, key=lambda h: h[2] - h[1])[0] if cover else "none"
            out.append((name, e - s))
        out.sort(key=lambda g: -g[1])
        return out[:top]


def union(events, lo: float, hi: float) -> float:
    """Length of the union of the events' intervals, clipped to [lo, hi]."""
    ivs = sorted((max(s, lo), min(e, hi)) for _, s, e in events)
    total, cur_s, cur_e = 0.0, None, None
    for s, e in ivs:
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def self_times(events) -> list:
    """(name, start, self seconds) of each event of one line, where the
    line nests events (an operation inside a loop inside a program)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    child = [0.0] * len(events)
    stack: list[int] = []
    for i in order:
        _, s, e = events[i]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            child[stack[-1]] += e - s
        stack.append(i)
    return [(n, s, (e - s) - child[i]) for i, (n, s, e) in enumerate(events)]


def op_name(text: str) -> str:
    """An operation's HLO name (``fusion.12``) from the event's text,
    which on a TPU is the whole instruction."""
    return text.split(" = ", 1)[0].lstrip("%")


def gaps(events, lo: float, hi: float) -> list:
    """The intervals of [lo, hi] that no event covers."""
    out, t = [], lo
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if e <= lo or s >= hi:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def _is_device(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith("/device:CUSTOM")


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    tr = Trace()
    for plane in data.planes:
        if _is_device(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    tr.ops[plane.name] = [(op_name(n), s, e)
                                          for n, s, e in _events(line)]
                elif line.name == MODULES_LINE:
                    tr.modules[plane.name] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.host.extend(_events(line))
    return tr


def _events(line) -> list:
    return [
        (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
        for e in line.events
    ]
