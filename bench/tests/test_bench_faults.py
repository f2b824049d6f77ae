"""Each cell's correctness check, driven end to end on the CPU at a tiny
size: sound runs pass, and `correct` comes out false with the timed path
broken underneath (a fault per kind of cell) or with the control, the
float32 reference, in the program's place."""
from __future__ import annotations

import numpy as np
import pytest

from bench.tests.conftest import run_tiny, tiny

PLAN = dict(warmup_calls=0)
RISK = dict(S=256, chunk=256, warmup_calls=0, check_reports=2,
            check_scenarios=40)


def _failed_checks(res) -> set[str]:
    return {k for k, c in res["checks"].items() if c["value"] > c["limit"]}


@pytest.mark.parametrize("cell,traffic", [
    ("paper-table6-20.plan", PLAN), ("paper-table6-20.replan", PLAN)])
def test_plan_cells_sound_run_is_correct(cpu_harness, cell, traffic):
    res = run_tiny(cpu_harness, tiny(cpu_harness, cell, "tiny-plan",
                                     **traffic))
    assert res["correct"] is True, res["checks"]


def test_plan_answer_altered_where_produced(cpu_harness, monkeypatch):
    from repro.core.xla import engine
    real = engine.agh_xla

    def altered(inst, **kw):
        sol = real(inst, **kw)
        sol.x[0] *= 0.9             # a tenth of type 0's traffic vanishes
        return sol

    monkeypatch.setattr(engine, "agh_xla", altered)
    res = run_tiny(cpu_harness, tiny(cpu_harness, "paper-table6-20.plan",
                                     "tiny-plan", **PLAN))
    assert res["correct"] is False
    assert "max_violation" in _failed_checks(res)


def test_replan_returning_its_state_unchanged(cpu_harness, monkeypatch):
    from repro import PlanSession
    monkeypatch.setattr(PlanSession, "replan",
                        lambda self, instance=None, **kw: self.last_result)
    res = run_tiny(cpu_harness, tiny(cpu_harness, "paper-table6-20.replan",
                                     "tiny-plan", **PLAN))
    assert res["correct"] is False
    assert "objective_gap" in _failed_checks(res)


@pytest.mark.parametrize("cell", ["paper-table6-20.plan", "paper-table6-20.replan"])
def test_plan_control_fails_the_check(cpu_harness, cell):
    work = tiny(cpu_harness, cell, "tiny-plan", **PLAN)
    cell_ = cpu_harness.new_cell(work)
    cell_.start(5)
    records, *_ = cpu_harness.run_window(
        cell_, 0.01, work.traffic["min_calls"], "test")
    sound = {c.name: c.ok for c in cell_.check(records, 5)}
    control = {c.name: c.ok for c in cell_.check(records, 5, control=True)}
    assert all(sound.values())
    assert control["objective_gap"] is False
    assert control["keys_gap"] is False


@pytest.mark.parametrize("cell", ["paper-table6-20.plan",
                                  "paper-table6-20.replan"])
def test_device_keys_altered_where_produced(cpu_harness, monkeypatch, cell):
    # The host driver keeps the plan feasible whatever keys it is given:
    # only the check of the device programs' output sees this.
    from repro.core.xla import kernels
    real = kernels._phase2_keys_jit

    def altered(*args):
        kap0, kap1 = real(*args)
        return kap0 * (1.0 + 1e-7), kap1

    monkeypatch.setattr(kernels, "_phase2_keys_jit", altered)
    res = run_tiny(cpu_harness, tiny(cpu_harness, cell, "tiny-plan",
                                     **PLAN))
    assert res["correct"] is False
    assert "keys_gap" in _failed_checks(res)
    assert "max_violation" not in _failed_checks(res)


def test_device_keys_half_of_the_lanes_left_out(cpu_harness, monkeypatch):
    from repro.core.xla import kernels
    real = kernels._phase2_keys_jit

    def half(*args):
        kap0, kap1 = real(*args)
        h = (kap0.shape[0] + 1) // 2        # the rest take the first's mean
        return (kap0.at[h:].set(kap0[:h].mean(axis=0)),
                kap1.at[h:].set(kap1[:h].mean(axis=0)))

    monkeypatch.setattr(kernels, "_phase2_keys_jit", half)
    res = run_tiny(cpu_harness, tiny(cpu_harness, "paper-table6-20.plan",
                                     "tiny-plan", **PLAN))
    assert res["correct"] is False
    assert "keys_gap" in _failed_checks(res)


def test_device_screen_prunes_every_source(cpu_harness, monkeypatch):
    from repro.core.xla import kernels
    real = kernels._screen_jit
    monkeypatch.setattr(kernels, "_screen_jit",
                        lambda *args: real(*args) & False)
    # At the tiny size every screened source is dead, so pruning them
    # all would be right: the fault needs sources that can improve.
    res = run_tiny(cpu_harness, tiny(cpu_harness, "paper-table6-20.plan",
                                     "small", **PLAN))
    assert res["correct"] is False
    assert "screen_mismatch" in _failed_checks(res)


def test_window_calls_are_tapped_and_programs_put_back(cpu_harness):
    from repro.core.xla import kernels
    real = (kernels._phase2_keys_jit, kernels._screen_jit)
    work = tiny(cpu_harness, "paper-table6-20.plan", "tiny-plan", **PLAN)
    cell_ = cpu_harness.new_cell(work)
    cell_.start(2**31 + 3)
    records, *_ = cpu_harness.run_window(cell_, 0.01, 32, "test")
    cell_.release()
    tags = {c.tag for c in cell_.tap.calls}
    assert len(tags) == work.traffic["keys_calls"]
    assert tags <= {r.answer["entry"] for r in records}
    assert {c.program for c in cell_.tap.calls} == {"_phase2_keys_jit",
                                                    "_screen_jit"}
    assert (kernels._phase2_keys_jit, kernels._screen_jit) == real


@pytest.mark.parametrize("cell", ["paper-table6-20.risk"])
def test_risk_sound_run_is_correct(cpu_harness, cell):
    res = run_tiny(cpu_harness, tiny(cpu_harness, cell, "tiny-risk", **RISK))
    assert res["correct"] is True, res["checks"]


def test_risk_half_of_the_batch_left_out(cpu_harness, monkeypatch):
    from repro.risk import solver
    real = solver.BatchedStage2Solver.solve_scenarios

    def half(self, batch):
        out = real(self, batch)
        out.costs[1::2] = out.costs[0::2].mean()   # the rest's mean
        return out

    monkeypatch.setattr(solver.BatchedStage2Solver, "solve_scenarios", half)
    res = run_tiny(cpu_harness, tiny(cpu_harness, "paper-table6-20.risk",
                                     "tiny-risk", **RISK))
    assert res["correct"] is False
    assert _failed_checks(res) == {"cost_gap"}


def test_risk_answer_altered_where_produced(cpu_harness, monkeypatch):
    from repro.risk import solver
    real = solver._candidate_kernel

    def altered(*args):
        ok, p, *rest = real(*args)
        return (ok, p * (1.0 + 1e-7), *rest)   # device costs off by 1e-7

    monkeypatch.setattr(solver, "_candidate_kernel", altered)
    res = run_tiny(cpu_harness, tiny(cpu_harness, "paper-table6-20.risk",
                                     "tiny-risk", **RISK))
    assert res["correct"] is False
    assert _failed_checks(res) == {"cost_gap"}


@pytest.mark.parametrize("cell", ["paper-table6-20.risk"])
def test_risk_control_fails_the_check(cpu_harness, cell):
    # The float32 control's error grows with the LP: at (20,20,20) it
    # reads well above the cell's limit.
    work = tiny(cpu_harness, cell, "small", **RISK)
    cell_ = cpu_harness.new_cell(work)
    cell_.start(5)
    records, *_ = cpu_harness.run_window(cell_, 0.01, 2, "test")
    sound = cell_.check(records, 5)
    control = cell_.check(records, 5, control=True)
    assert all(c.ok for c in sound)
    assert not control[0].ok
    assert control[0].value > 3 * sound[0].value


def test_references_agree_with_the_programs_own_arithmetic():
    """The plain references price what the program prices, on the
    program's own solves (the CPU, float64)."""
    from bench.lib.harness import make_instance
    from bench.lib.plan_ref import PlanModel
    from bench.lib.stage2_ref import Stage2Reference, scenario_draws
    from repro import plan
    from repro.core import objective
    from repro.risk import risk_evaluate
    inst, fields = make_instance(
        {"generator": {"name": "table6_synthetic",
                       "args": {"I": 10, "J": 8, "K": 8, "seed": 9}}})
    sol = plan("agh", instance=inst).solution
    assert PlanModel(fields).objective(sol) == pytest.approx(
        objective(inst, sol), rel=1e-13)
    fam = {"d_infl": 0.15, "e_infl": 0.10, "lam_pm": 0.20}
    ex = risk_evaluate(inst, sol, S=30, engine="exact", seed=77, **fam)
    keep = np.array([0, 7, 29])
    d = scenario_draws(fields, 77, 30, fam, keep)
    ref = Stage2Reference(fields, sol)
    for row, s in enumerate(keep):
        got = ref.cost(d["tau"][row], d["e_base"][row], d["lam"][row])
        assert got == pytest.approx(ex.stage2_costs[s], rel=1e-12)


def test_xla_reference_grids_match_the_programs():
    """The device reference builds the grids the program precomputes,
    from the raw fields alone (the CPU, float64)."""
    from bench.lib.harness import make_instance
    from bench.lib.xla_ref import XlaTables
    inst, fields = make_instance(
        {"generator": {"name": "table6_synthetic",
                       "args": {"I": 20, "J": 20, "K": 20, "seed": 42}}})
    t = XlaTables(fields)
    I, JK = inst.I, inst.J * inst.K
    assert np.array_equal(t.m1_valid, inst.m1_feasible.reshape(I, JK))
    assert np.array_equal(t.m1_nm, inst.m1_nm.reshape(I, JK))
    for mine, theirs in ((t.m1_delay, inst.m1_delay),
                         (t.load_per_x, inst.load_per_x),
                         (t.e_floor, inst.e_bar_floor)):
        ok = t.m1_valid
        assert np.allclose(mine[ok], theirs.reshape(I, JK)[ok],
                           rtol=1e-13, atol=0)
