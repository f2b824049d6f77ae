"""Shared helpers for the per-table benchmarks."""
from __future__ import annotations

import os
import subprocess
import time

# JSON dump schema, bumped whenever the row-dict layout changes in a way
# the regression gate must not silently accept (see check_regression.py).
# v3: solver columns are registry-keyed sub-dicts (`PlanResult.summary()`
# rows keyed by the planner-registry solver name, e.g. "gh"/"agh"/
# "agh+reference") instead of flat per-method key prefixes.
# v4: rows carry an "engine" field ("numpy"/"xla") that is part of the
# row identity — xla and numpy rows of the same size never collide —
# and xla rows report jit compile time separately (`compile_s`) so the
# runtime gate sees steady-state timings only.
JSON_SCHEMA_VERSION = 4

_made_dirs: set[str] = set()


def ensure_outdir(path: str) -> None:
    """Create the directory holding `path` exactly once per process —
    repeated `--json` dumps (one per section invocation in CI) share the
    memo instead of re-running makedirs."""
    d = os.path.dirname(os.path.abspath(path))
    if d in _made_dirs:
        return
    os.makedirs(d, exist_ok=True)
    _made_dirs.add(d)


def git_sha() -> str:
    """Current commit SHA (`unknown` outside a work tree) — stamped into
    every JSON dump so the CI gate can reject stale baselines."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        if out.returncode == 0:
            return out.stdout.strip()
    except (OSError, subprocess.SubprocessError):
        pass
    return "unknown"


def emit(name: str, us_per_call: float, derived: str) -> None:
    """CSV line per harness contract: name,us_per_call,derived."""
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def agh_mode(inst, local_search: str):
    """`core.agh.agh` under one of its test-oracle local-search modes
    ("reference", "batched-rescan"), which the planner facade does not
    offer, as the `PlanResult` a facade solve would give."""
    from repro.core.agh import agh
    from repro.planner import PlanOptions
    from repro.planner.api import build_result
    stats: dict = {}
    t0, c0 = time.perf_counter(), time.process_time()
    sol = agh(inst, local_search=local_search, stats=stats)
    wall, cpu = time.perf_counter() - t0, time.process_time() - c0
    return build_result("agh", inst, sol, wall, cpu, stats, PlanOptions())


class Timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.dt = time.perf_counter() - self.t0

    @property
    def us(self) -> float:
        return self.dt * 1e6
