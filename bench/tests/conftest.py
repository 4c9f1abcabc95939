"""The benchmark's own tests run on the CPU at small sizes, with the
program from ``src/`` and no persistent compile cache.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def no_cache(monkeypatch):
    from bench.lib import harness

    monkeypatch.setattr(harness, "use_cache", lambda: "off")


@pytest.fixture
def tpu_policies(monkeypatch):
    """The precision rules of a TPU on the CPU: float64 is emulated
    there, so a stalled float32 row has no float64 re-solve and retires
    stalled, as in the cells on the chip."""
    from repro.core import precision

    monkeypatch.setattr(precision, "emulates_f64", lambda backend=None: True)
