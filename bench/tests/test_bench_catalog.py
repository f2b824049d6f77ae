"""The catalog cell (`mla-moe-catalog.plan`) on the CPU at a tiny size:
sound runs pass; `correct` comes out false with the control (the float32
split reference) in the program's place, and with the program pricing
delay and load from the resident bytes; the split references price what
the program prices."""
from __future__ import annotations

import numpy as np
import pytest

from bench.lib.catalog import catalog_fields, model_rows
from bench.lib.harness import RunData, load_json, load_module
from bench.tests.conftest import DATA, run_tiny, tiny

CELL = "mla-moe-catalog.plan"
PLAN = dict(warmup_calls=0)


def _failed_checks(res) -> set[str]:
    return {k for k, c in res["checks"].items() if c["value"] > c["limit"]}


def test_catalog_sound_run_is_correct(cpu_harness):
    res = run_tiny(cpu_harness, tiny(cpu_harness, CELL, "tiny-catalog",
                                     **PLAN))
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"plan_s", "setup_s"}


def test_catalog_control_fails_the_check(cpu_harness):
    work = tiny(cpu_harness, CELL, "tiny-catalog", **PLAN)
    cell = cpu_harness.new_cell(work)
    cell.start(5)
    records, *_ = cpu_harness.run_window(cell, 0.01,
                                         work.traffic["min_calls"], "test")
    sound = {c.name: c.ok for c in cell.check(records, 5)}
    control = {c.name: c.ok for c in cell.check(records, 5, control=True)}
    assert all(sound.values())
    assert control["objective_gap"] is False
    assert control["keys_gap"] is False


def test_delay_and_load_from_resident_bytes_fail(cpu_harness, monkeypatch):
    # The program forgets the rows' active bytes: d_comp and alpha from B.
    from repro.core.instance import Instance
    real = Instance.__post_init__

    def resident(self):
        self.B_act = None
        real(self)

    monkeypatch.setattr(Instance, "__post_init__", resident)
    res = run_tiny(cpu_harness, tiny(cpu_harness, CELL, "tiny-catalog",
                                     **PLAN))
    assert res["correct"] is False
    assert {"objective_gap", "keys_gap"} <= _failed_checks(res)


def test_split_references_agree_with_the_programs_own_arithmetic():
    """The split references price what the program prices, and build the
    grids it precomputes, on the program's own solves (the CPU, float64);
    the benchmark's rows are the program's `mla_moe_row`."""
    from bench.lib.plan_ref_split import SplitPlanModel
    from bench.lib.xla_ref_split import SplitXlaTables
    from repro import plan
    from repro.core import objective
    from repro.core.catalog import mla_moe_row
    from repro.core.instance import Instance
    config = load_json(DATA / "tiny-catalog.json")
    fields = catalog_fields(config)
    inst = Instance(**fields)
    assert np.all(inst.B_act < inst.B)
    rows = model_rows(config)
    for j, m in enumerate(config["models"]):
        row = mla_moe_row(m["name"], m.get("config", config))
        assert (row.B, row.B_act, row.beta) == pytest.approx(
            (rows["B"][j], rows["B_act"][j], rows["beta"][j]), rel=1e-15)
    sol = plan("agh", instance=inst).solution
    ref = SplitPlanModel(fields)
    assert ref.objective(sol) == pytest.approx(objective(inst, sol),
                                               rel=1e-13)
    assert max(ref.violations(sol).values()) <= 1e-9
    t = SplitXlaTables(fields)
    I, JK = inst.I, inst.J * inst.K
    assert np.array_equal(t.m1_valid, inst.m1_feasible.reshape(I, JK))
    assert np.array_equal(t.m1_nm, inst.m1_nm.reshape(I, JK))
    ok = t.m1_valid
    for mine, theirs in ((t.m1_delay, inst.m1_delay),
                         (t.load_per_x, inst.load_per_x),
                         (t.e_floor, inst.e_bar_floor)):
        assert np.allclose(mine[ok], theirs.reshape(I, JK)[ok],
                           rtol=1e-13, atol=0)


def test_catalog_types_are_the_table6_draw():
    from bench.lib.catalog import table6_types
    from bench.lib.instances import table6_synthetic
    t6 = table6_synthetic(20, 20, 20, 42)
    mine = table6_types(20, 42)
    for k in ("lam", "h", "f", "theta", "Delta", "eps", "rho", "phi", "tau"):
        assert np.array_equal(mine[k], t6[k]), k


def test_mem_bound_share_reads_the_counters(cpu_harness):
    metric = load_module(DATA.parents[1] / "metrics"
                         / "mem_bound_share.plan.py", "mem_bound_share")
    shares = {}
    for cell, config in ((CELL, "tiny-catalog"),
                         ("paper-table6-20.plan", "tiny-plan")):
        work = tiny(cpu_harness, cell, config, **PLAN)
        c = cpu_harness.new_cell(work)
        c.start(7)
        records, window_s, *_ = cpu_harness.run_window(c, 0.01, 4, "test")
        run = RunData(records=records, window_s=window_s, setup_s=0.0,
                      traffic=work.traffic, config=work.config)
        shares[cell] = metric.read(run)
        d = records[0].answer["diagnostics"]
        assert shares[cell] == pytest.approx(
            100.0 * d["m1_mem_bound"] / d["m1_cells"])
        for rec in records:
            del rec.answer["diagnostics"]["m1_cells"]
        assert metric.read(run) is None     # a program without the counter
    assert shares[CELL] > 0 and shares["paper-table6-20.plan"] == 0


def test_catalog_config_states_every_row_and_tier():
    config = load_json(DATA.parents[1] / "configs" / "mla-moe-catalog.json")
    fields = catalog_fields(config)
    assert len(fields["model_names"]) == 10 and len(fields["C_gpu"]) == 24
    assert len(fields["lam"]) == 20
    assert config["reduced"] == {}
    B = dict(zip(fields["model_names"], fields["B"]))
    assert round(B["DeepSeek-V3"] / 2, 1) == 671.0
