"""The harness on the CPU: per-call inputs, the window rule, the result
line, and the command's refusal to run without a TPU."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench.lib import harness as H
from bench.lib.instances import (InputPool, entry_rng, entry_seed,
                                 table6_synthetic)
from bench.tests.conftest import run_tiny, tiny

ROOT = Path(__file__).resolve().parents[2]


def test_same_seed_and_call_give_the_same_inputs():
    a, b = InputPool(11, 4, 2**31 + 9), InputPool(11, 4, 2**31 + 9)
    assert [a.index(i) for i in range(12)] == [b.index(i) for i in range(12)]
    assert np.array_equal(entry_rng(11, 3).random(5),
                          entry_rng(11, 3).random(5))
    assert entry_seed(11, 3) == entry_seed(11, 3)
    assert entry_seed(11, 3) != entry_seed(11, 3, InputPool.WARMUP)


def test_seeds_send_one_set_of_inputs_in_other_orders():
    runs = [[InputPool(11, 4, s).index(i) for i in range(12)]
            for s in (1, 2, 3, 2**33)]
    for r in runs:
        assert len(set(r)) == 12                      # no call repeats
        for b in range(3):                            # same set per block
            assert sorted(r[4 * b:4 * b + 4]) == list(range(4 * b, 4 * b + 4))
    assert len({tuple(r) for r in runs}) > 1          # other orders


def test_negative_seed_is_refused():
    with pytest.raises(ValueError):
        InputPool(11, 4, -1)


class _FakeCell:
    def __init__(self, clock, durations, fail=()):
        self.clock, self.durations, self.fail = clock, durations, fail

    def call(self, i):
        self.clock[0] += self.durations[i]
        if i in self.fail:
            raise RuntimeError("planted failure")
        return {"S": 10}


@pytest.mark.parametrize("seconds,min_calls,max_calls,calls,window", [
    (1.0, 1, None, 3, 1.2),   # first completion at or after 1.0 s: call 3
    (1.0, 5, None, 5, 2.0),   # ... unless fewer than min_calls completed
    (0.4, 1, None, 1, 0.4),   # a completion exactly at the limit ends it
    (1.0, 5, 2, 2, 0.8),      # a traced run stops after its traced calls
])
def test_window_ends_at_first_completion_after_seconds(
        monkeypatch, seconds, min_calls, max_calls, calls, window):
    clock = [0.0]
    monkeypatch.setattr(H.time, "perf_counter", lambda: clock[0])
    records, window_s, attempted, failed = H.run_window(
        _FakeCell(clock, [0.4] * 10), seconds, min_calls, "test.call",
        max_calls=max_calls)
    assert (len(records), attempted, failed) == (calls, calls, 0)
    assert window_s == pytest.approx(window)
    run = H.RunData(records=records, window_s=window_s, setup_s=1.0,
                    traffic={}, config={})
    rate = H.load_module(H.BENCH / "metrics" / "risk_scen_per_s.py", "r")
    per = H.load_module(H.BENCH / "metrics" / "plan_s.py", "p")
    # Whole work over whole time.
    assert rate.read(run) == pytest.approx(10 * calls / window)
    assert per.read(run) == pytest.approx(window / calls)


def test_failed_calls_are_counted_and_end_the_window(monkeypatch):
    clock = [0.0]
    monkeypatch.setattr(H.time, "perf_counter", lambda: clock[0])
    records, window_s, attempted, failed = H.run_window(
        _FakeCell(clock, [0.4] * 10, fail={1}), 1.0, 3, "test.call")
    assert (attempted, failed, len(records)) == (3, 1, 2)
    assert [r.i for r in records] == [0, 2]


def test_result_line(cpu_harness, capsys):
    work = tiny(cpu_harness, "paper-table6-20.plan", "tiny-plan",
                warmup_calls=0)
    res = run_tiny(cpu_harness, work)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= work.traffic["min_calls"]
    assert {k: v["unit"] for k, v in res["metrics"].items()} == {
        "plan_s": "s", "plan_objective": "usd", "setup_s": "s"}
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert set(res["checks"]) == {"objective_gap", "max_violation",
                                  "keys_gap", "screen_mismatch"}
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    json.dumps(res)
    err = capsys.readouterr().err.strip().splitlines()
    assert [ln.split(":")[0] for ln in err[-4:]] == [
        "check objective_gap", "check max_violation", "check keys_gap",
        "check screen_mismatch"]


def _bench(args: list[str], cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_command_exits_nonzero_without_a_tpu():
    out = _bench(["--workload", "paper-table6-20.plan", "--seed", "3",
                  "--seconds", "1", "--trace", "0"], ROOT)
    assert out.returncode == 3, out.stderr
    assert out.stdout == ""
    assert "not a TPU" in out.stderr


def test_command_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _bench(["--workload", "paper-table6-20.plan", "--seed", "3",
                  "--seconds", "1", "--trace", "0"], tmp_path)
    assert out.returncode == 2 and out.stdout == ""


def test_generator_copy_matches_the_programs():
    from repro.core.instance import random_instance
    for size in ((20, 20, 20), (100, 80, 40)):
        mine = table6_synthetic(*size, seed=42)
        theirs = random_instance(*size, seed=42)
        for key, value in mine.items():
            assert np.array_equal(np.asarray(value),
                                  np.asarray(getattr(theirs, key))), key
