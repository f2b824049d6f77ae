"""Sparse-expert model rows: resident weights `B` and the weights a token
reads, `B_act`, apart in the planner.

`B_act` sets the per-token terms (d_comp, and through it D_cfg and T_res;
alpha, and through it the compute load); `B` keeps every memory and
storage term.  A row without `B_act` is the dense row it always was, bit
for bit.  A catalog of DeepSeek-V2/V3-block rows runs through every
entry point (`plan` on both engines, the scalar oracle, the MILP,
`PlanSession`, `risk_evaluate`, `serve`) and each is compared with its
reference or oracle.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

from repro import PlanSession, plan
from repro.core import (agh, default_instance, is_feasible, objective,
                        random_instance)
from repro.core import _scalar_ref as ref
from repro.core.bridge import calibrate_from_dryrun
from repro.core.catalog import (ModelRow, mla_moe_params, mla_moe_row,
                                with_model_rows)
from repro.core.milp import solve_milp
from repro.core.stage2 import Stage2System
from repro.planner.specs import ScenarioSpec, SLOSpec, WorkloadSpec

CATALOG = (Path(__file__).resolve().parents[1] / "bench" / "configs"
           / "mla-moe-catalog.json")

DERIVED = ("d_comp", "alpha", "D_cfg", "T_res", "mem_ok", "cfg_m1", "m1_nm",
           "m1_delay", "kv_tok_per_x", "load_per_x", "B_eff", "p_s_B")


def _configs() -> dict:
    cfg = json.loads(CATALOG.read_text())
    return {m["name"]: m.get("config", cfg) for m in cfg["models"]}


@pytest.fixture(scope="module")
def catalog():
    """The paper's six types with one dense row and two sparse-expert rows
    (DeepSeek-V2, DeepSeek-V3) on its ten GPU tiers; at $300/day the
    strict-accuracy type needs DeepSeek-V2, on eight RTX4090s at INT4."""
    cfgs = _configs()
    rows = (ModelRow("dense-8b", B=16.0, beta=98.0),
            mla_moe_row("DeepSeek-V2", cfgs["DeepSeek-V2"]),
            mla_moe_row("DeepSeek-V3", cfgs["DeepSeek-V3"]))
    inst = ScenarioSpec(workload=WorkloadSpec(models=rows),
                        slo=SLOSpec(budget=300.0)).build()
    sol = agh(inst)
    return inst, sol


def _suite():
    return [("default", default_instance()),
            ("random-8-5-6", random_instance(8, 5, 6, seed=2)),
            ("random-10-10-10", random_instance(10, 10, 10, seed=3)),
            ("stressed-1.3", default_instance().stressed(1.3))]


@pytest.mark.parametrize("name,inst", _suite())
def test_unset_and_equal_active_bytes_are_the_dense_row(name, inst):
    same = dataclasses.replace(inst, B_act=inst.B.copy())
    for attr in DERIVED:
        assert np.array_equal(getattr(inst, attr), getattr(same, attr)), attr
    assert (inst.m1_cells, inst.m1_mem_bound) == (same.m1_cells,
                                                  same.m1_mem_bound)
    assert objective(inst, agh(inst)) == objective(same, agh(same))


def test_active_bytes_set_the_per_token_terms_only(catalog):
    inst, _ = catalog
    dense = dataclasses.replace(inst, B_act=None)
    ratio = (inst.B_act / inst.B)[None, :, None]
    assert np.allclose(inst.d_comp, dense.d_comp * ratio, rtol=1e-14)
    assert np.allclose(inst.alpha, dense.alpha * ratio, rtol=1e-14)
    assert np.allclose(inst.load_per_x, dense.load_per_x * ratio,
                       rtol=1e-14)
    for attr in ("B_eff", "mem_ok", "p_s_B", "data_gb"):
        assert np.array_equal(getattr(inst, attr), getattr(dense, attr))
    # The sparse rows fit delay SLOs that their resident bytes miss.
    assert inst.m1_cells > dense.m1_cells
    assert inst.m1_mem_bound > 0 and dense.m1_mem_bound == 0


@pytest.mark.parametrize("B_act", [[1.0, 0.0, 1.0], [1.0, 500.0, 1.0],
                                   [1.0, 2.0]])
def test_active_bytes_are_validated(catalog, B_act):
    inst, _ = catalog
    with pytest.raises(ValueError, match="B_act"):
        dataclasses.replace(inst, B_act=np.array(B_act))


def test_counters_reach_every_solver(catalog):
    inst, _ = catalog
    for solver, engine in (("gh", None), ("agh", "numpy"), ("agh", "xla")):
        d = plan(solver, instance=inst, engine=engine).diagnostics
        assert (d["m1_cells"], d["m1_mem_bound"]) == (inst.m1_cells,
                                                      inst.m1_mem_bound)
    # The count is the M1 winners' nm above the delay-only minimum.
    fits = inst.D_cfg <= inst.Delta[:, None, None, None]
    nm_delay = np.where(fits, inst.nm, np.iinfo(np.int64).max).min(axis=3)
    assert inst.m1_mem_bound == int(np.sum(inst.m1_feasible
                                           & (inst.m1_nm > nm_delay)))
    assert inst.m1_cells == int(inst.m1_feasible.sum())


def test_catalog_plans_agree_across_engines_and_oracles(catalog):
    inst, sol = catalog
    assert is_feasible(inst, sol, enforce_zeta=False)
    deployed = {inst.model_names[j] for j in np.nonzero(sol.q > 0.5)[0]}
    assert "DeepSeek-V2" in deployed
    o_np = plan("agh", instance=inst).objective
    o_xla = plan("agh", instance=inst, engine="xla").objective
    assert o_xla == o_np == objective(inst, sol)
    assert objective(inst, ref.agh_scalar(inst)) == pytest.approx(
        o_np, rel=1e-12)
    exact = solve_milp(inst, time_limit=60.0)
    assert is_feasible(inst, exact, enforce_zeta=False)
    assert objective(inst, exact) <= o_np + 1e-9 * o_np


def test_stage2_coefficients_are_the_instances(catalog):
    inst, sol = catalog
    s = Stage2System(inst, sol)
    n = np.array([c[0] for c in inst.configs], float)[s.cfg_p][s.tp]
    m = np.array([c[1] for c in inst.configs], float)[s.cfg_p][s.tp]
    cfg = s.cfg_p[s.tp]
    ti, tj, tk = s.ti, s.tj, s.tk
    assert s.nx > 0 and np.any(inst.B_act[tj] < inst.B[tj])
    delay = s.dA * inst.tau[ti] + s.dB
    assert np.allclose(delay, inst.D_cfg[ti, tj, tk, cfg], rtol=1e-14)
    assert np.allclose(s.gA * inst.lam[ti], inst.load_per_x[ti, tj, tk],
                       rtol=1e-14)
    assert np.array_equal(n * m, inst.nm[cfg])


def test_risk_on_sparse_rows_matches_the_exact_engine(catalog):
    from repro.risk import risk_evaluate
    inst, sol = catalog
    kw = dict(S=64, seed=11, d_infl=0.15, e_infl=0.10, lam_pm=0.20)
    pd = risk_evaluate(inst, sol, engine="pdhg", **kw)
    ex = risk_evaluate(inst, sol, engine="exact", **kw)
    np.testing.assert_allclose(pd.stage2_costs, ex.stage2_costs, rtol=1e-8,
                               atol=0)


def test_session_replan_and_serve_on_sparse_rows(catalog):
    from repro.serving import ControllerSpec, TrafficSpec, serve
    inst, _ = catalog
    ses = PlanSession(solver="agh")
    ses.plan(instance=inst)
    moved = inst.with_lam(inst.lam * np.linspace(0.9, 1.1, inst.I))
    res = ses.replan(instance=moved)
    assert ses.warm_replans == 1
    assert is_feasible(moved, res.solution, enforce_zeta=False)
    out = serve(res.solution, instance=moved,
                traffic=TrafficSpec(horizon_s=600.0, window_s=300.0,
                                    rate_scale=0.02, seed=3),
                controller=ControllerSpec(mode="static"))
    assert out.n_arrived > 0 and out.n_served > 0


def test_deepseek_v3_row():
    cfg = _configs()["DeepSeek-V3"]
    total, active = mla_moe_params(cfg)
    assert round(total / 1e9, 1) == 671.0
    assert round(active / 1e9, 1) == 36.6
    row = mla_moe_row("DeepSeek-V3", cfg)
    assert row.beta * 1e3 == pytest.approx(70_272, abs=1e-9)
    assert (row.B, row.B_act) == (2 * total / 1e9, 2 * active / 1e9)


@pytest.mark.parametrize("name,total,active", [
    ("DeepSeek-V2", 236e9, 21e9), ("Kimi-K2-Instruct", 1e12, 32e9),
    ("Moonlight-16B-A3B", 16e9, 3e9), ("DeepSeek-V3", 671e9, 37e9)])
def test_rows_match_the_counts_in_the_models_names(name, total, active):
    """The names count the input embedding as active (a token reads its
    row; the planner's `B_act` leaves it out, as it is no weight stream):
    with it, both counts are within 3% of the names'."""
    cfg = _configs()[name]
    t, a = mla_moe_params(cfg)
    embedding = cfg["vocab_size"] * cfg["hidden_size"]
    assert abs(t / total - 1) < 0.03
    assert abs((a + embedding) / active - 1) < 0.03


def test_spec_rows_keep_the_types_relative_difficulty():
    base = default_instance()
    rows = (ModelRow("a", B=16.0, beta=98.0),
            ModelRow("b", B=400.0, beta=60.0, B_act=40.0))
    inst = with_model_rows(base, rows)
    assert list(inst.model_names) == ["a", "b"]
    assert np.array_equal(inst.B_act, [16.0, 40.0])
    # Row a is dense: the paper's curve at its own size; row b is rated
    # at its dense-equivalent size sqrt(400 * 40) GB.
    assert rows[0].base_error == 0.049 * 8.0 ** -0.75 + 0.006
    assert rows[1].base_error == pytest.approx(
        0.049 * (np.sqrt(16_000.0) / 2) ** -0.75 + 0.006, rel=1e-15)
    difficulty = inst.e_base[:, 0] / rows[0].base_error
    assert np.allclose(difficulty / difficulty.mean(),
                       base.e_base[:, 0] / base.e_base[:, 0].mean(),
                       rtol=1e-12)
    assert np.allclose(inst.e_base[:, 1] / inst.e_base[:, 0],
                       rows[1].base_error / rows[0].base_error, rtol=1e-12)


def test_calibration_scales_active_bytes_not_resident(tmp_path, catalog):
    inst, _ = catalog
    rows = [{"status": "ok", "shape": "decode_32k", "arch": "v2",
             "hlo_bytes_per_device": 1.5 * 2 * 21e9, "n_devices": 1,
             "params_active": 21e9},
            {"status": "ok", "shape": "decode_32k", "arch": "dense",
             "hlo_bytes_per_device": 3.0 * 2 * 8e9, "n_devices": 1,
             "params_active": 8e9}]
    path = tmp_path / "dryrun.json"
    path.write_text(json.dumps(rows))
    cal = calibrate_from_dryrun(inst, str(path), {"dense": 0, "v2": 1})
    assert np.array_equal(cal.B, inst.B)
    assert np.array_equal(cal.B_eff, inst.B_eff)
    assert np.array_equal(cal.mem_ok, inst.mem_ok)
    assert cal.B_act[1] == inst.B_act[1] * 1.5
    assert cal.B_act[2] == inst.B_act[2]
    # The dense row's stream cannot pass its resident weights.
    assert cal.B_act[0] == inst.B[0]
    assert np.allclose(cal.d_comp[:, 1], inst.d_comp[:, 1] * 1.5,
                       rtol=1e-14)
