"""The one traffic generator: turns a mix's parameter file and a seed
into the requests a run submits, and decides when each is submitted.

A mix file (``bench/traffic/<mix>.json``) holds only parameters:

``loop``         ``"closed"``: each of ``clients`` callers submits its
                 next request when its last one returns.
``clients``      requests kept outstanding.
``rel_tol``      every request's relative tolerance.
``traction``     per component (``x``, ``y``, ``z``), a ``[low, high]``
                 range drawn uniformly per request, on the face x = 8.
``warmup_rel_tol``  the set-up request's tolerance; it must need at
                 least two iterations, so that both step programs run.

Every request carries the configuration's own material table.

Request ``i`` of seed ``s`` is drawn from its own stream
(``SeedSequence([s, i])``), so a request does not depend on how many
came before it, and any whole number is a seed.

The harness asks :meth:`Traffic.due` at the window's opening and at
every step boundary how many requests to submit now; the arrival
process lives here, not in the harness.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

__all__ = ["Traffic", "load_mix"]

TRAFFIC_DIR = Path(__file__).resolve().parents[1] / "traffic"


def load_mix(name: str) -> dict:
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


class Traffic:
    def __init__(self, mix: dict, config: dict, seed: int):
        if mix.get("loop") != "closed":
            raise ValueError(f"unsupported loop {mix.get('loop')!r}")
        self.mix, self.config, self.seed = mix, config, int(seed)
        self.clients = int(mix["clients"])
        self.materials = {int(a): tuple(v)
                          for a, v in config["materials"].items()}

    def due(self, elapsed_s: float, submitted: int, answered: int) -> int:
        """Requests to submit now, ``elapsed_s`` into the window, with
        ``submitted`` sent and ``answered`` returned so far.  Closed
        loop: every client keeps one request outstanding."""
        return self.clients + answered - submitted

    def request(self, i: int) -> dict:
        """Keyword arguments of request ``i``: materials, traction, rel_tol."""
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, i]))
        tr = self.mix["traction"]
        return {
            "materials": self.materials,
            "traction": tuple(float(rng.uniform(*tr[c])) for c in "xyz"),
            "rel_tol": float(self.mix["rel_tol"]),
        }

    def warmup(self) -> dict:
        return {
            "materials": self.materials,
            "traction": (0.0, 0.0, -1e-2),
            "rel_tol": float(self.mix["warmup_rel_tol"]),
        }
