"""Process-level state: the compile-cache policy and one process per solve.

Importing a library module sets no compile-cache option; the entry
points call `repro.runtime.use_compile_cache()`, which honours
``JAX_COMPILATION_CACHE_DIR`` and otherwise uses the checkout's
``.jax_cache/``.  Planning, evaluation and the Table-2 grid run in the
calling process: none of them starts a worker process.
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.core import agh, evaluate, random_instance
from repro.planner import plan
from repro.runtime import CACHE_DIR

ROOT = Path(__file__).resolve().parents[1]


def _python(code: str, **env_extra: str) -> list[str]:
    """Run `code` in a fresh CPU-only interpreter; its stdout lines."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu",
               **env_extra)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    return r.stdout.split()


def test_imports_set_no_compile_cache_option():
    out = _python(
        "import jax, repro.risk.solver, repro.core.xla.engine\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "print(jax.config.jax_persistent_cache_min_compile_time_secs)")
    assert out == ["None", "1.0"]


@pytest.mark.parametrize("from_env", [False, True])
def test_use_compile_cache_directory(tmp_path, from_env):
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)} if from_env else {}
    expected = str(tmp_path) if from_env else str(CACHE_DIR)
    out = _python(
        "import jax\n"
        "from repro.runtime import use_compile_cache\n"
        "print(use_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n", **env)
    assert out == [expected, expected]
    assert CACHE_DIR == ROOT / ".jax_cache"


def _plan():
    plan("agh", instance=random_instance(6, 6, 10, seed=1))


def _evaluate():
    inst = random_instance(6, 6, 10, seed=1)
    evaluate(inst, agh(inst), S=8, seed=1)


def _table2():
    from benchmarks import table2
    table2.run(S=2)


@pytest.mark.parametrize("run", [_plan, _evaluate, _table2],
                         ids=["plan-agh", "evaluate", "table2"])
def test_planning_starts_no_process(monkeypatch, run):
    import concurrent.futures as cf
    import multiprocessing

    def refuse(*args, **kwargs):
        raise AssertionError("a solve started a worker process")

    monkeypatch.setattr(cf, "ProcessPoolExecutor", refuse)
    monkeypatch.setattr(multiprocessing, "Process", refuse)
    monkeypatch.setattr(multiprocessing.process.BaseProcess, "start", refuse)
    monkeypatch.syspath_prepend(str(ROOT))
    run()
