"""Equivalence suite for the XLA allocator engine (`engine="xla"`).

The numpy engine is the bit-exact oracle; the XLA tier is accepted on a
*dominance* contract rather than bit-identity: on every instance of the
equivalence suite it must return a solution whose objective is <= the
numpy engine's (plus a float32-safe slack that in practice is never
needed — on CPU x64 the two match exactly), with feasibility verified by
the frozen scalar path.  The tier must also ride the whole planner
surface: `plan(..., engine="xla")`, `PlanOptions(engine=...)`, and
warm replans through `PlanSession(engine="xla")`.

Skipped wholesale when jax is unavailable; the jax-free
`EngineUnavailableError` contract is tested unconditionally.
"""
import numpy as np
import pytest

from repro.core import agh, default_instance, is_feasible, objective, \
    random_instance
from repro.core.solution import feasibility
from repro.planner import (EngineUnavailableError, PlanOptions, PlanSession,
                           plan)

jax = pytest.importorskip("jax")

from repro.core.xla.engine import agh_xla  # noqa: E402  (needs jax)


def _instances():
    return [
        ("default", default_instance()),
        ("random-6-6-10", random_instance(6, 6, 10, seed=1)),
        ("random-8-5-6", random_instance(8, 5, 6, seed=2)),
        ("random-10-10-10", random_instance(10, 10, 10, seed=3)),
        ("stressed-1.15", default_instance().stressed(1.15)),
        ("stressed-1.3", default_instance().stressed(1.3)),
        ("tight-budget", random_instance(6, 6, 10, seed=4, budget=40.0)),
    ]


def _tol(obj):
    return 1e-6 * max(1.0, abs(obj))


def _assert_feasible_scalar(inst, sol, label):
    """Feasibility via the frozen per-constraint walk (the same checker
    the scalar reference path relies on), not the engine's own state."""
    viol = feasibility(inst, sol, enforce_zeta=False)
    bad = {k: v for k, v in viol.items() if v > 1e-4}
    assert not bad, f"{label}: constraint violations {bad}"


@pytest.mark.parametrize("name,inst", _instances())
def test_xla_objective_dominates_numpy(name, inst):
    """engine='xla' evaluates every lane (no early stop), so its best
    objective can never exceed the sequential numpy engine's."""
    sol_np = agh(inst, seed=0)
    sol_x = agh_xla(inst, seed=0)
    o_np, o_x = objective(inst, sol_np), objective(inst, sol_x)
    assert o_x <= o_np + _tol(o_np), (name, o_x, o_np)
    assert is_feasible(inst, sol_x, enforce_zeta=False)
    _assert_feasible_scalar(inst, sol_x, name)
    assert sol_x.method == "AGH-XLA"


def test_xla_stats_counters():
    inst = random_instance(8, 5, 6, seed=2)
    stats = {}
    agh_xla(inst, stats=stats)
    assert stats["engine"] == "xla"
    assert isinstance(stats["early_stopped"], bool)
    # The first improvement wave always covers at least patience+1
    # orderings, so the evaluated set is never smaller than the
    # sequential driver's minimum stop point.
    assert stats["orderings_evaluated"] >= 6
    assert stats["device_calls_phase2"] > 0
    # The screen must actually screen: on this instance most sources are
    # proven move-free on device without an exact host scan.
    assert stats["screened_clean"] > 0
    assert stats["screened_clean"] <= stats["screen_sources"]


def test_plan_facade_engine_kwarg():
    inst = random_instance(6, 6, 10, seed=1)
    res_np = plan(instance=inst)
    res_x = plan(instance=inst, engine="xla")
    assert res_x.options["engine"] == "xla"
    assert res_x.diagnostics["engine"] == "xla"
    assert res_x.objective <= res_np.objective + _tol(res_np.objective)
    assert res_x.feasible
    with pytest.raises(ValueError, match="not both"):
        from repro.planner import PlanRequest
        plan(PlanRequest(instance=inst), engine="xla")


def test_plan_unknown_engine_rejected():
    inst = default_instance()
    with pytest.raises(ValueError, match="unknown engine"):
        plan(instance=inst, options=PlanOptions(engine="tpu"))


def test_session_warm_replan_xla():
    """Warm replans ride the same tier: the incumbent seeds the xla
    multi-start and the drifted solve stays feasible and competitive
    with a cold numpy solve of the drifted instance."""
    inst = random_instance(6, 6, 10, seed=1)
    ses = PlanSession(engine="xla")
    ses.plan(instance=inst)
    assert ses.options.engine == "xla"
    drift = inst.with_lam(inst.lam * 1.12)
    res = ses.replan(instance=drift)
    assert ses.warm_replans == 1
    assert res.diagnostics["engine"] == "xla"
    assert res.diagnostics.get("warm_started") is True
    assert res.feasible
    cold = plan(instance=drift)
    # Warm replan trades ordering coverage for wall clock; it must stay
    # within the replan-protocol band of the cold solve (same contract
    # the numpy session tests pin), not strictly dominate it.
    assert res.objective <= cold.objective * 1.05 + 1e-9
    _assert_feasible_scalar(drift, res.solution, "warm-replan")


def test_warm_start_dominates_incumbent():
    inst = random_instance(8, 5, 6, seed=2)
    s1 = agh_xla(inst, seed=0)
    drift = inst.with_lam(inst.lam * 1.1)
    stats = {}
    s2 = agh_xla(drift, warm_start=s1, stats=stats)
    assert stats["warm_started"] is True
    assert "warm_objective" in stats
    assert objective(drift, s2) <= stats["warm_objective"] + 1e-9
    assert is_feasible(drift, s2, enforce_zeta=False)


@pytest.mark.parametrize("inst,seed", [
    (random_instance(8, 5, 6, seed=2), 0),
    (random_instance(10, 10, 10, seed=3), 1),
    (default_instance().stressed(1.15), 2),
], ids=["random-8-5-6", "random-10-10-10", "stressed-1.15"])
def test_xla_never_worse_than_sequential_numpy(inst, seed):
    """Under finite patience the lane waves replay the sequential
    early-stop rule, so the xla tier's evaluated orderings are a superset
    of the numpy driver's prefix and its plan is never dearer."""
    o_np = objective(inst, agh(inst, seed=seed))
    o_x = objective(inst, agh_xla(inst, seed=seed))
    assert o_x <= o_np + _tol(o_np)


def test_screen_shapes_share_a_bucket():
    """Source and group counts inside one ladder step reuse one compiled
    screen program (a compile per count would be paid on every call)."""
    from repro.core.xla import kernels
    from repro.core.xla.tensors import tensors_for
    tx = tensors_for(random_instance(6, 6, 10, seed=1))
    group = (0, 0, np.zeros(tx.JK, bool), np.array([0]), np.array([1.0]),
             np.array([0.1]), np.array([True]))
    src = (0, 1, 0.0, 1.0, 1.0, 1.0, 1.0, 0.0)
    load = np.zeros((1, tx.JK))
    kernels.screen_sources(tx, [group] * 3, [src] * 5, load)
    compiled = kernels._screen_jit._cache_size()
    alive = kernels.screen_sources(tx, [group] * 7, [src] * 9, load)
    assert kernels._screen_jit._cache_size() == compiled
    assert alive.shape == (9,)


def test_numpy_default_untouched():
    """engine='numpy' (and the default) never imports jax machinery and
    stays bit-identical to a direct agh() call."""
    inst = random_instance(6, 6, 10, seed=1)
    res = plan(instance=inst)
    assert res.options["engine"] == "numpy"
    direct = agh(inst)
    assert abs(res.objective - objective(inst, direct)) <= 1e-9


# ----------------------------------------------------------------------
# Hypothesis property test: dominance + feasibility on ANY instance.
# Guarded import so only this test skips when hypothesis is missing —
# a module-level importorskip would silently skip the whole suite.
# ----------------------------------------------------------------------
try:
    from hypothesis import given, settings, strategies as st
    _HAS_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised on minimal hosts
    _HAS_HYPOTHESIS = False


if _HAS_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(st.integers(3, 8), st.integers(3, 6), st.integers(4, 10),
           st.integers(0, 10_000))
    def test_xla_dominance_property(I, J, K, seed):
        inst = random_instance(I, J, K, seed=seed)
        sol_np = agh(inst, seed=0)
        sol_x = agh_xla(inst, seed=0)
        o_np, o_x = objective(inst, sol_np), objective(inst, sol_x)
        assert o_x <= o_np + _tol(o_np)
        assert is_feasible(inst, sol_x, enforce_zeta=False)
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_xla_dominance_property():
        pass
