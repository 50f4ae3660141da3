"""The benchmark's own tests run on the CPU at small sizes."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


import pytest                                                # noqa: E402


@pytest.fixture
def faithful(monkeypatch):
    """The program's Mamba-1 layer with falcon-mamba's numerics put in
    (``chipbench/witness.py``): the weightless norms on dt, B and C and a
    float32 residual, so that it serves the model the reference computes."""
    from chipbench import witness
    witness.faithful(monkeypatch.setattr)
