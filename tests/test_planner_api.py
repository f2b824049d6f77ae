"""Unified planner API: shim equivalence, registry, specs, sessions.

The facade contract (ISSUE 5): `plan()` solutions are BITWISE-identical
to direct calls of the legacy entry points (`gh`/`agh`/`solve_milp`/
`dvr`/`hf`/`lpr`) — the old functions stay the implementation, the
facade is a wrapper, and these tests pin that nothing drifts in between.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import (agh, default_instance, dvr, gh, hf, lpr, objective,
                        random_instance, solve_milp)
from repro.planner import (PlanOptions, PlanRequest, PlanResult, PlanSession,
                           SolverSpec, UnknownSolverError, plan,
                           register_solver, scenario, solver_names,
                           unregister_solver)
from repro.planner.specs import FleetSpec, ScenarioSpec, WorkloadSpec


def _instances():
    return [
        ("default", default_instance()),
        ("random-6-6-10", random_instance(6, 6, 10, seed=1)),
        ("random-8-5-6", random_instance(8, 5, 6, seed=2)),
        ("stressed-1.15", default_instance().stressed(1.15)),
        ("tight-budget", random_instance(6, 6, 10, seed=4, budget=40.0)),
    ]


def _assert_bitwise_equal(a, b, label):
    for f in ("x", "y", "q", "w", "z", "u"):
        assert np.array_equal(getattr(a, f), getattr(b, f)), \
            f"{label}: field {f} differs"


# ---------------------------------------------------------------------------
# Shim layer: facade == direct calls, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,inst", _instances())
def test_facade_gh_bitwise_equals_direct(name, inst):
    res = plan("gh", instance=inst)
    _assert_bitwise_equal(res.solution, gh(inst), f"gh/{name}")
    assert res.objective == pytest.approx(objective(inst, res.solution),
                                          abs=0.0)


@pytest.mark.parametrize("name,inst", _instances())
def test_facade_agh_bitwise_equals_direct(name, inst):
    res = plan("agh", instance=inst)
    _assert_bitwise_equal(res.solution, agh(inst), f"agh/{name}")
    assert res.diagnostics["orderings_evaluated"] >= 1


def test_facade_agh_options_map_through():
    inst = random_instance(6, 6, 10, seed=1)
    opts = PlanOptions(restarts=2, passes=2, patience=3, seed=5)
    res = plan("agh", instance=inst, options=opts)
    direct = agh(inst, R=2, L=2, patience=3, seed=5)
    _assert_bitwise_equal(res.solution, direct, "agh/options")


def test_facade_milp_bitwise_equals_direct():
    # Small enough that HiGHS converges to proven optimality in well
    # under a second — far from the time limit, so branch-and-bound is
    # deterministic and the facade/direct solutions are bitwise equal.
    inst = random_instance(3, 3, 4, seed=3)
    res = plan("milp", instance=inst,
               options=PlanOptions(time_limit=120.0))
    direct = solve_milp(inst, time_limit=120.0)
    assert res.diagnostics["status"] == direct.method == "DM"
    _assert_bitwise_equal(res.solution, direct, "milp")
    # the alias resolves to the same canonical spec
    assert plan("dm", instance=inst,
                options=PlanOptions(time_limit=120.0)).solver == "milp"


@pytest.mark.parametrize("solver,fn", [("dvr", dvr), ("hf", hf)])
def test_facade_baselines_bitwise_equal_direct(solver, fn):
    inst = default_instance()
    res = plan(solver, instance=inst)
    _assert_bitwise_equal(res.solution, fn(inst), solver)


def test_facade_lpr_bitwise_equals_direct():
    inst = random_instance(3, 3, 4, seed=3)
    res = plan("lpr", instance=inst, options=PlanOptions(time_limit=120.0))
    _assert_bitwise_equal(res.solution, lpr(inst, time_limit=120.0), "lpr")


def test_gh_rejects_unknown_kwargs_loudly():
    """Satellite: `gh` has an explicit signature now — a typo'd option is
    a TypeError at the call site, not a silently ignored kwarg."""
    inst = default_instance()
    with pytest.raises(TypeError):
        gh(inst, ordering=np.arange(inst.I))  # typo of order=


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def test_unknown_solver_lists_registered_names():
    with pytest.raises(UnknownSolverError) as ei:
        plan("aghh", instance=default_instance())
    msg = str(ei.value)
    for name in ("gh", "agh", "milp", "lpr", "dvr", "hf"):
        assert name in msg
    assert "aghh" in msg


def test_register_custom_solver_roundtrip():
    def _noop(inst, options, warm_start):
        return gh(inst), {"custom": True}

    spec = SolverSpec("custom-test", _noop, "test-only solver")
    register_solver(spec)
    try:
        assert "custom-test" in solver_names()
        res = plan("custom-test", instance=default_instance())
        assert res.diagnostics["custom"] is True
        with pytest.raises(ValueError, match="already registered"):
            register_solver(spec)
    finally:
        unregister_solver("custom-test")
    assert "custom-test" not in solver_names()


def test_register_before_first_lookup_loads_builtins():
    """A plugin registering a builtin name at import time (before any
    get_solver call) must fail loudly at registration — not poison the
    deferred builtin import for every later lookup."""
    with pytest.raises(ValueError, match="already registered"):
        register_solver(SolverSpec("gh", lambda i, o, w: None, "clash"))
    # registry still fully works afterwards
    assert "agh" in solver_names()


def test_overwrite_clears_stale_alias():
    """Overwriting a name that was previously an alias ("dm" -> "milp")
    must make the new spec reachable — a stale alias entry would silently
    route lookups to the old target."""
    def _custom(inst, options, warm_start):
        return gh(inst), {"custom_dm": True}

    register_solver(SolverSpec("dm", _custom, "test"), overwrite=True)
    try:
        res = plan("dm", instance=default_instance())
        assert res.diagnostics.get("custom_dm") is True
        assert res.solver == "dm"
    finally:
        unregister_solver("dm")
        # restore the builtin alias for the rest of the suite
        from repro.planner.registry import _ALIASES
        _ALIASES["dm"] = "milp"
    assert plan("dm", instance=random_instance(3, 3, 4, seed=3),
                options=PlanOptions(time_limit=60.0)).solver == "milp"


# ---------------------------------------------------------------------------
# PlanResult structure + JSON round trip
# ---------------------------------------------------------------------------

def test_plan_result_json_round_trip():
    inst = default_instance()
    res = plan("agh", instance=inst)
    res2 = PlanResult.from_json(res.to_json())
    _assert_bitwise_equal(res2.solution, res.solution, "json")
    assert res2.objective == res.objective
    assert res2.cost_breakdown == res.cost_breakdown
    assert res2.slack == res.slack
    assert res2.violations == res.violations
    assert res2.diagnostics == res.diagnostics
    assert res2.options == res.options
    assert res2.feasible == res.feasible
    # summary rows are JSON-safe and registry-keyed
    row = res.summary()
    assert row["solver"] == "agh"
    assert isinstance(row["objective"], float)


def test_plan_result_reports_cost_and_slack():
    inst = default_instance()
    res = plan("gh", instance=inst)
    assert res.objective == pytest.approx(
        sum(res.cost_breakdown.values()), rel=1e-12)
    assert res.feasible
    # every slack of a feasible plan is >= (tiny negative float fuzz)
    assert all(v >= -1e-6 for v in res.slack.values()), res.slack
    assert set(res.slack) == {"budget", "memory", "compute", "storage",
                              "delay", "error", "unmet"}
    assert res.wall_s > 0 and res.cpu_s >= 0


def test_plan_request_validation():
    inst = default_instance()
    with pytest.raises(ValueError, match="exactly one"):
        plan(PlanRequest(solver="gh"))
    with pytest.raises(ValueError, match="exactly one"):
        plan(PlanRequest(solver="gh", instance=inst,
                         scenario="paper-default"))
    with pytest.raises(ValueError, match="not both"):
        plan(PlanRequest(solver="gh", instance=inst), instance=inst)


@pytest.mark.parametrize("name", ["workers", "batch_width", "local_search",
                                  "validate"])
def test_plan_options_has_no_sweep_or_test_option(name):
    """Options that only sweeps and tests set are keywords of
    `core.agh.agh`, not fields of the facade."""
    with pytest.raises(TypeError):
        PlanOptions(**{name: 0})


def test_plan_options_round_trip():
    opts = PlanOptions(restarts=4, ablation=frozenset({"no_m1"}),
                       order=(2, 0, 1))
    opts2 = PlanOptions.from_dict(opts.to_dict())
    assert opts2 == opts


# ---------------------------------------------------------------------------
# Scenario specs
# ---------------------------------------------------------------------------

def test_paper_default_scenario_matches_default_instance():
    inst = scenario("paper-default").build()
    want = default_instance()
    assert np.array_equal(inst.lam, want.lam)
    assert np.array_equal(inst.e_base, want.e_base)
    assert inst.delta == want.delta
    assert list(inst.tier_names) == list(want.tier_names)


def test_named_scenarios_build_and_override():
    assert scenario("budget-tight").build().delta == 72.0
    assert scenario("budget-tight", budget=80.0).build().delta == 80.0
    tpu = scenario("tpu-fleet").build()
    assert any(t.startswith("v5e") for t in tpu.tier_names)
    assert max(tpu.tp_degrees) == 16
    stressed = scenario("stress-1.5x").build()
    assert np.allclose(stressed.tau, default_instance().tau * 1.5)


def test_unknown_scenario_lists_registered_names():
    with pytest.raises(KeyError, match="paper-default"):
        scenario("no-such-scenario")


def test_demand_paths():
    spec = scenario("azure-diurnal", n_windows=32)
    inst = spec.build()
    path = spec.demand_path(inst)
    assert path.shape == (32, inst.I)
    assert (path > 0).all()
    flat = scenario("paper-default", n_windows=8)
    assert np.array_equal(flat.demand_path(inst)[0], inst.lam)
    rw = dataclasses.replace(
        spec, workload=dataclasses.replace(spec.workload,
                                           demand="random-walk",
                                           n_windows=16))
    assert rw.demand_path(inst).shape == (16, inst.I)


def test_synthetic_scenario_spec():
    spec = ScenarioSpec(workload=WorkloadSpec(family="synthetic",
                                              I=6, J=6, K=10), seed=1)
    inst = spec.build()
    want = random_instance(6, 6, 10, seed=1)
    assert np.array_equal(inst.lam, want.lam)
    with pytest.raises(ValueError, match="catalog"):
        ScenarioSpec(fleet=FleetSpec(catalog="asic")).build()


def test_plan_accepts_scenario_names():
    res = plan("gh", scenario="budget-tight")
    assert res.feasible is not None
    assert res.solution.x.shape[0] == 6


# ---------------------------------------------------------------------------
# PlanSession warm replanning
# ---------------------------------------------------------------------------

def test_session_cold_then_warm_replan():
    inst = random_instance(6, 6, 10, seed=1)
    ses = PlanSession()
    r0 = ses.plan(instance=inst)
    assert ses.plans == 1 and ses.warm_replans == 0
    assert not r0.diagnostics["warm_started"]
    drifted = inst.with_lam(inst.lam * 1.08)
    r1 = ses.replan(instance=drifted)
    assert ses.plans == 2 and ses.warm_replans == 1
    assert r1.diagnostics["warm_started"]
    assert r1.feasible
    # incumbent rolls forward
    _assert_bitwise_equal(ses.incumbent, r1.solution, "incumbent")
    # lam= shorthand replans the remembered instance
    r2 = ses.replan(lam=inst.lam * 0.95)
    assert r2.feasible and ses.warm_replans == 2


def test_session_without_incumbent_degrades_to_cold():
    ses = PlanSession()
    res = ses.replan(instance=default_instance())
    assert not res.diagnostics.get("warm_started", False)
    assert ses.plans == 1 and ses.warm_replans == 0


def test_session_remembers_winning_order():
    inst = random_instance(8, 5, 6, seed=2)
    ses = PlanSession()
    ses.plan(instance=inst)
    if ses.winning_order is not None:
        assert sorted(ses.winning_order) == list(range(inst.I))
    r1 = ses.replan(instance=inst.with_lam(inst.lam * 1.05))
    # replayed priority ordering keeps the replan's quality contract:
    # never worse than the incumbent re-scored... (empirical bound is in
    # test_perf_smoke); here just assert the plumbing round-trips.
    assert r1.diagnostics["restarts"] == ses.replan_restarts


def test_session_drives_rolling_replay():
    from repro.core import rolling
    inst = default_instance()
    path = np.outer(np.linspace(0.9, 1.1, 12), inst.lam)
    ses = PlanSession(options=PlanOptions(restarts=1, patience=2))
    res = rolling(inst, path, ses, replan_every=4)
    assert res.per_window_cost.shape == (12,)
    assert ses.plans >= 2            # initial plan + >=1 window replan
    assert ses.warm_replans >= 1


def test_session_seed_installs_external_incumbent():
    inst = random_instance(6, 6, 10, seed=1)
    res = plan("agh", instance=inst)
    ses = PlanSession()
    ses.seed(inst, res)
    _assert_bitwise_equal(ses.incumbent, res.solution, "seed")
    r1 = ses.replan(lam=inst.lam * 1.05)
    assert r1.diagnostics["warm_started"] and ses.warm_replans == 1


def test_non_warm_solver_session_stays_cold():
    ses = PlanSession(solver="gh")
    inst = default_instance()
    ses.plan(instance=inst)
    r = ses.replan(instance=inst.with_lam(inst.lam * 1.1))
    # gh cannot warm-start: the facade drops the incumbent and reports so.
    assert r.diagnostics["warm_started"] is False
    assert ses.warm_replans == 0