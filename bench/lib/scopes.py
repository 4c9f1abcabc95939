"""The traced window split by layer: device self seconds per named scope
of the program, and idle seconds per host span of the service.

The program names its device work with ``jax.named_scope`` (``pcg.*``,
``prep``, ``vcycle.*``, ``pa.apply``) and its host work with
``service.*`` profiler annotations (``docs/OBSERVABILITY.md``).  An
operation belongs to the outermost ``pcg.*`` or ``prep`` scope of its
path, or to ``unscoped``; a V-cycle that ``pcg.init`` or ``pcg.audit``
runs stays with them.  Every value is seconds per credited solve
(``run.credits``), as ``solve_s`` divides the window, so the device
layers, the unscoped rest and the idle time add up to ``solve_s``.

Where the paths come from: the window's trace (``bench.lib.trace``)
names each operation by its HLO name and each program run by its
module.  The scope path of an operation is its HLO ``op_name``
metadata (which a TPU trace shows as ``tf_op``); the harness removes
the trace file and frees its service before the readers run, so the
readers build the configured service again, warm it up as the run did
(the programs load from the compile cache), and read the metadata from
the optimized HLO of the live executables: the same programs, whose
operation names are the trace's.  :func:`xspace_op_paths` reads
``tf_op`` from a kept ``.xplane.pb`` itself, to check the two agree.
"""

from __future__ import annotations

import bisect
import re

from bench.lib import trace as tr

__all__ = ["LAYERS", "UNSCOPED", "IN_PROGRAM", "layer_of", "hlo_op_paths",
           "module_name", "program_id", "op_runs", "xspace_op_paths",
           "device_seconds",
           "idle_seconds", "program_paths", "device_split", "idle_split",
           "service_idle"]

LAYERS = ("pcg.init", "pcg.apply", "pcg.precond", "pcg.vector",
          "pcg.audit", "prep")
UNSCOPED = "unscoped"
# Idle stretches inside a program run: the device's own bubbles between
# operations, not host work.
IN_PROGRAM = "in_program"
HOST_PREFIXES = ("service.", "bench.")

_HLO_OP = re.compile(
    r'^\s*(?:ROOT\s+)?%?([^\s=]+) = [^\n]*?\bop_name="([^"]*)"', re.M)


def layer_of(path: str) -> str:
    """The outermost ``pcg.*`` / ``prep`` scope of an ``op_name`` path
    such as ``jit(_chunk_resume)/while/body/pcg.precond/...``; a
    transform's wrapper (``vmap(pcg.apply)``) is looked through."""
    for part in path.split("/"):
        name = part.rsplit("(", 1)[-1].rstrip(")")
        if name in LAYERS:
            return name
    return UNSCOPED


def hlo_op_paths(text: str) -> dict:
    """Instruction name -> ``op_name`` metadata of an HLO module's text."""
    return {m.group(1): m.group(2) for m in _HLO_OP.finditer(text)}


def module_name(event_name: str) -> str:
    """``jit__chunk_resume`` from a modules-line event
    ``jit__chunk_resume(1234)``."""
    return event_name.split("(", 1)[0]


def program_id(event_name: str) -> int:
    """``1234`` from a modules-line event ``jit__chunk_resume(1234)``."""
    return int(event_name.rsplit("(", 1)[1].rstrip(")"))


def op_runs(trace, plane: str):
    """(operation, start, self seconds, program run) of every operation
    on ``plane``; the run is the modules-line event whose interval holds
    the operation's start (``""`` where none does)."""
    runs = sorted(trace.modules.get(plane, []), key=lambda m: m[1])
    starts = [m[1] for m in runs]
    for op, s, t in tr.self_times(trace.ops[plane]):
        i = bisect.bisect_right(starts, s) - 1
        yield op, s, t, runs[i][0] if i >= 0 and s < runs[i][2] else ""


def device_seconds(trace, lo: float, hi: float, paths: dict):
    """Device self seconds per layer of the operations that start in
    [lo, hi], averaged over the device planes, and the operations with
    most self time (ten in all and five unscoped) as [operation,
    program, seconds, layer, path].  ``paths`` maps a program's module
    name to its {operation: op_name}."""
    out = dict.fromkeys(LAYERS + (UNSCOPED,), 0.0)
    top: dict = {}
    for plane in trace.ops:
        for op, s, t, run in op_runs(trace, plane):
            if not lo <= s < hi:
                continue
            mod = module_name(run)
            path = paths.get(mod, {}).get(op, "")
            layer = layer_of(path)
            out[layer] += t
            prev = top.get((mod, op), (0.0, layer, path))
            top[(mod, op)] = (prev[0] + t, layer, path)
    k = max(len(trace.ops), 1)
    ranked = [[op, mod, s / k, layer, path] for (mod, op), (s, layer, path)
              in sorted(top.items(), key=lambda kv: -kv[1][0])]
    return ({n: v / k for n, v in out.items()},
            ranked[:10] + [r for r in ranked if r[3] == UNSCOPED][:5])


def idle_seconds(trace, lo: float, hi: float) -> dict:
    """Idle seconds in [lo, hi] on the first device plane: inside a
    program run as :data:`IN_PROGRAM`; outside, each stretch under the
    innermost ``service.*`` / ``bench.*`` host span covering it (the
    shortest), or ``none``."""
    if not trace.ops:
        return {}
    plane, ev = next(iter(trace.ops.items()))
    runs = sorted((s, e) for _, s, e in trace.modules.get(plane, []))
    spans = [h for h in trace.host if h[0].startswith(HOST_PREFIXES)]
    out: dict[str, float] = {}

    def add(name, dt):
        if dt > 0:
            out[name] = out.get(name, 0.0) + dt

    for s, e in tr.gaps(ev, lo, hi):
        # the parts of the gap inside program runs
        outside = [(s, e)]
        for rs, re_ in runs:
            if re_ <= s or rs >= e:
                continue
            add(IN_PROGRAM, min(e, re_) - max(s, rs))
            outside = [piece for a, b in outside
                       for piece in ((a, min(b, rs)), (max(a, re_), b))
                       if piece[1] > piece[0]]
        for a, b in outside:
            cover = [h for h in spans if h[1] < b and h[2] > a]
            cuts = sorted({a, b} | {t for h in cover for t in h[1:]
                                    if a < t < b})
            for x, y in zip(cuts, cuts[1:]):
                inner = [h for h in cover if h[1] <= x and h[2] >= y]
                name = (min(inner, key=lambda h: h[2] - h[1])[0]
                        if inner else "none")
                add(name, y - x)
    return out


def _per_solve(values: dict, credits: float) -> dict:
    return {k: v / credits for k, v in values.items()}


def program_paths(run) -> dict:
    """{module name: {operation: op_name}} of the programs the run's
    service builds: the configured service is built and warmed up again
    (loading its programs from the compile cache), and the optimized HLO
    of every live executable is read."""
    from bench.lib.harness import Session
    from bench.lib.traffic import Traffic

    session = Session(run.config, platforms=(run.device["platform"],))
    try:
        session.warm_up(Traffic(run.mix, run.config, seed=0))
        out: dict = {}
        for exe in session.dev.client.live_executables():
            for mod in exe.hlo_modules():
                out.setdefault(mod.name, {}).update(
                    hlo_op_paths(mod.to_string()))
        return out
    finally:
        session.close()


def _program_has_scopes() -> bool:
    from repro.solvers import batched

    return hasattr(batched, "PCG_APPLY")


def device_split(run) -> dict:
    """Device self seconds per layer per credited solve in the traced
    window, computed once per run and kept (and logged by the harness)
    as ``run.extra["scope_seconds"]``; empty where the trace has no
    device operations or the program names no scopes."""
    if "scope_seconds" in run.extra:
        return run.extra["scope_seconds"]
    trace, out = run.window_trace, {}
    if (trace is not None and trace.ops and run.credits > 0
            and _program_has_scopes()):
        lo, hi = run.window_span
        per_layer, top = device_seconds(trace, lo, hi, program_paths(run))
        if any(per_layer[n] for n in LAYERS):
            out = _per_solve(per_layer, run.credits)
            out["busy"] = trace.busy(lo, hi) / run.credits
            out["window"] = (hi - lo) / run.credits
            out["solve_s"] = run.window_s / run.credits
            out["top_ops"] = [[op, mod, s / run.credits, layer, path]
                              for op, mod, s, layer, path in top]
    run.extra["scope_seconds"] = out
    return out


def idle_split(run) -> dict:
    """Idle seconds per host span per credited solve in the traced
    window, kept (and logged) as ``run.extra["idle_seconds"]``; empty
    where the service opens no ``service.*`` span."""
    if "idle_seconds" in run.extra:
        return run.extra["idle_seconds"]
    trace, out = run.window_trace, {}
    if (trace is not None and run.credits > 0
            and any(h[0].startswith("service.") for h in trace.host)):
        lo, hi = run.window_span
        out = _per_solve(idle_seconds(trace, lo, hi), run.credits)
    run.extra["idle_seconds"] = out
    return out


def service_idle(split: dict):
    """The idle seconds under ``service.*`` spans, or None."""
    if not split:
        return None
    return sum(v for k, v in split.items() if k.startswith("service."))


def xspace_op_paths(path: str) -> dict:
    """{device plane name: {(program id, operation): scope path}} from a
    ``.xplane.pb``: the ``tf_op`` stat of each event metadata of the
    device planes, its ``:<type>`` suffix cut, keyed by its
    ``program_id`` stat (the number in the program's modules-line event,
    :func:`program_id`) and the operation's HLO name
    (:func:`bench.lib.trace.op_name`).  A small reader of the protobuf
    wire format, so that it needs no TensorFlow: XSpace.planes (1);
    XPlane.name (2), event_metadata (4), stat_metadata (5), both maps
    with key (1) and value (2); XEventMetadata.name (2), stats (5);
    XStatMetadata.name (2); XStat.metadata_id (1), uint64_value (3),
    int64_value (4), str_value (5), ref_value (7)."""
    with open(path, "rb") as f:
        space = _fields(memoryview(f.read()))
    out = {}
    for plane_buf in space.get(1, []):
        plane = _fields(plane_buf)
        name = _text(plane, 2)
        if not name.startswith("/device:"):
            continue
        stat_names = {}
        for entry in plane.get(5, []):
            meta = _fields(_fields(entry)[2][0])
            stat_names[meta[1][0]] = _text(meta, 2)
        ops = {}
        for entry in plane.get(4, []):
            meta = _fields(_fields(entry)[2][0])
            stats = {}
            for stat_buf in meta.get(5, []):
                stat = _fields(stat_buf)
                stats[stat_names.get(stat.get(1, [0])[0])] = stat
            if "tf_op" not in stats:
                continue
            tf_op = stats["tf_op"]
            value = (_text(tf_op, 5) if 5 in tf_op  # or interned:
                     else stat_names.get(tf_op.get(7, [0])[0], ""))
            pid = stats.get("program_id", {})
            pid = pid.get(3, pid.get(4, [0]))[0]
            op = tr.op_name(_text(meta, 2))
            # ``<path>:<type>``; the HLO op_name is the path
            ops[(pid, op)] = value.rsplit(":", 1)[0]
        if ops:
            out[name] = ops
    return out


def _text(msg: dict, field: int) -> str:
    return bytes(msg.get(field, [b""])[0]).decode()


def _fields(buf: memoryview) -> dict:
    """{field number: [values]} of one protobuf message: varints as
    ints, length-delimited fields as views of ``buf`` (fixed-width ones
    skipped)."""
    out: dict[int, list] = {}
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        out.setdefault(field, []).append(value)
    return out


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7
