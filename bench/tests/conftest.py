"""Drive the benchmark's harness on the CPU, at sizes a test run holds.

`cpu_harness` lets a run skip the harness's look for a TPU and keeps
JAX's persistent compilation cache as the test process has it.
"""
from __future__ import annotations

from pathlib import Path

import pytest

DATA = Path(__file__).parent / "data"


@pytest.fixture
def cpu_harness(monkeypatch):
    import jax

    from bench.lib import harness
    monkeypatch.setattr(harness, "accelerator",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "use_checkout_cache", lambda: None)
    return harness


def tiny(harness, cell: str, config: str, **traffic) -> object:
    """The named cell of `BENCHMARK.json` on a tiny configuration."""
    work = harness.Workload.find(cell)
    work.config = harness.load_json(DATA / f"{config}.json")
    work.traffic = dict(work.traffic, **traffic)
    return work


def run_tiny(harness, work, seed: int = 2**31 + 5, seconds: float = 0.01):
    import time
    return harness.run(work, seed, seconds, False, time.perf_counter())
