"""The reader of the risk driver's candidate-call counter."""
from __future__ import annotations

import types

import pytest

from bench.lib.harness import BENCH, CallRecord, load_module


@pytest.fixture(scope="module")
def reader():
    return load_module(BENCH / "metrics" / "candidate_calls.risk.py",
                       "m_candidate_calls_risk")


def _run(diags):
    recs = [CallRecord(i, 0.0, 1.0, {"diagnostics": d})
            for i, d in enumerate(diags)]
    return types.SimpleNamespace(records=recs)


def test_candidate_calls_per_report(reader):
    assert reader.read(_run([{"candidate_calls": 96},
                             {"candidate_calls": 98}])) == pytest.approx(97)


@pytest.mark.parametrize("diags", [
    [{"candidate_rows": 181685}],                       # no counter
    [{"candidate_calls": 96}, {"candidate_rows": 5}],   # one report lacks it
    [],                                                 # no report
])
def test_candidate_calls_reads_nothing_without_the_counter(reader, diags):
    assert reader.read(_run(diags)) is None
