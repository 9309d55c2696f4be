"""Shared set-up of the benchmark's self-tests (run by hand:
``pytest bench/tests``).  They run on the CPU: the harness's look for a
chip is skipped and the Pallas kernels run in interpret mode."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def kernels_on_cpu(monkeypatch):
    """Plan onto the kernel backends and run them in interpret mode."""
    from repro.kernels import common
    monkeypatch.setattr(common, "is_cpu", lambda devices=None: False)
    monkeypatch.setattr(common, "default_interpret",
                        lambda interpret=None: True)
