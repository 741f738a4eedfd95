"""Tests of the benchmark's own code, on the CPU at tiny sizes.

    PYTHONPATH=src python -m pytest bench/tests
"""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def weights_at_tiny_width(monkeypatch):
    """Draw the tiny cell's weights at 1/sqrt(hidden), as 0.02 is about
    1/sqrt(2560) at full width: the model then depends on its attention
    and MLP as the full-size one does, instead of echoing its input token
    through the tied embedding."""
    import tiny
    import weights
    monkeypatch.setattr(weights, "STD",
                        tiny.CONFIG["hidden_size"] ** -0.5)
