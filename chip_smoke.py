#!/usr/bin/env python3
"""Bring-up smoke of the planning service on one TPU chip.

Drives the service's main path once, in one process, through its public
entry points, and checks every answer against the repo's own oracles:

1. ``plan``   — cold AGH plan of the ``fleet-scale`` scenario (100 query
   types x 80 models x 40 tiers, seed 42) on ``engine="xla"``.  Its
   objective must not exceed the numpy engine's on the same instance
   (within 1e-6 relative), and it must violate no hard constraint.  The
   numpy reference is AGH's sequential early-stop driver, the protocol
   the xla tier's dominance contract is stated against.
2. ``replan`` — `PlanSession(engine="xla").replan` under +-15% per-type
   demand drift, held to the same checks against a cold numpy plan of
   the drifted instance.
3. ``repair`` — `PlanSession.repair` under the scenario's fault schedule
   (an outage of the cold plan's busiest tier when the schedule is
   empty).  The plan must be feasible or report its degradation level.
4. ``risk``   — `risk_evaluate(..., S=20_000, engine="pdhg")` on the
   risk benchmark's deployment (GH on a (20,20,20) instance, seed 42;
   see `benchmarks/risk_scaling.py`).  The timed report's per-scenario
   objectives must match `risk_evaluate(..., engine="exact")` on all
   20,000 scenarios (three 8192-scenario chunks, anchors carried from
   one to the next) to rtol 1e-5, and the device must
   settle at least one scenario (anchor candidates or PDHG), so the exact
   fallback cannot hide it.
5. ``risk_fleet`` — the same checks for the cold plan of phase 1 at
   S=300.  At this size every scenario has its own optimal basis, so
   anchors rarely hit and most scenarios fall back to the exact solver;
   the phase bounds that cost and still requires device-settled work.

Every timed call is made once before, on the same shapes; that first
call is reported as set-up seconds (compiles, or persistent-cache
loads).  One JSON line per phase, then the verdict line
``{"ok": true, "device": {...}}``.  Exits non-zero, without the verdict
line, when JAX finds no TPU or when any check fails.

    python chip_smoke.py
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

OBJ_RTOL = 1e-6      # tests/test_engine_xla.py `_tol`
RISK_RTOL = 1e-5     # tests/test_risk.py `RTOL`
FEAS_TOL = 1e-4      # tests/test_engine_xla.py `_assert_feasible_scalar`
DRIFT_PM = 0.15      # benchmarks/allocator_scaling.py `DRIFT_PM`
XLA_COUNTERS = ("device_calls_phase2", "device_calls_screen",
                "screen_sources", "screen_bypassed", "screened_clean")
RISK_COUNTERS = ("n_anchor0", "n_harvest_exact", "n_pdhg",
                 "n_fallback_exact", "pdhg_iters_max", "n_anchors")


def _twice(fn):
    """(result, wall_s, setup_s): one warm-up call, then the timed call.

    Every callable here returns host numpy results, so the device work
    has been read back before the clock stops."""
    t0 = time.perf_counter()
    fn()
    setup = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0, setup


def _violations(inst, sol) -> dict:
    from repro.core import feasibility
    return {k: v for k, v in feasibility(inst, sol, enforce_zeta=False).items()
            if v > FEAS_TOL}


def _xla_counters(diag: dict) -> dict:
    return {k: diag.get(k, 0) for k in XLA_COUNTERS}


def _dominance(inst, res, ref) -> dict:
    """The xla tier's contract against the numpy oracle."""
    viol = _violations(inst, res.solution)
    slack = OBJ_RTOL * max(1.0, abs(ref.objective))
    return {"objective": res.objective, "numpy_objective": ref.objective,
            "excess": res.objective - ref.objective, "allowed": slack,
            "violations": viol,
            "ok": res.objective <= ref.objective + slack and not viol}


def phase_plan(inst) -> tuple[dict, object]:
    from repro import plan
    res, wall, setup = _twice(lambda: plan("agh", instance=inst,
                                           engine="xla"))
    t0 = time.perf_counter()
    ref = plan("agh", instance=inst, engine="numpy")
    numpy_wall = time.perf_counter() - t0
    oracle = _dominance(inst, res, ref)
    oracle["numpy_wall_s"] = numpy_wall
    return ({"phase": "plan", "ok": oracle["ok"], "wall_s": wall,
             "setup_s": setup, "oracle": oracle,
             "counters": _xla_counters(res.diagnostics)}, res)


def phase_replan(inst, cold) -> dict:
    from repro import PlanSession, plan
    lam = inst.lam * np.random.default_rng(7).uniform(
        1.0 - DRIFT_PM, 1.0 + DRIFT_PM, inst.I)
    drifted = inst.with_lam(lam)
    ses = PlanSession(engine="xla")

    def replan():
        ses.seed(inst, cold)
        return ses.replan(instance=drifted)

    res, wall, setup = _twice(replan)
    ref = plan("agh", instance=drifted, engine="numpy")
    oracle = _dominance(drifted, res, ref)
    oracle["warm_started"] = bool(res.diagnostics.get("warm_started"))
    oracle["ok"] = oracle["ok"] and oracle["warm_started"]
    return {"phase": "replan", "ok": oracle["ok"], "wall_s": wall,
            "setup_s": setup, "oracle": oracle,
            "counters": _xla_counters(res.diagnostics)}


def _fault(spec, inst, cold):
    """(schedule, window): the first faulted window of the scenario's own
    schedule, else an outage of the tier the cold plan uses most."""
    from repro.core import FaultSchedule, TierOutage
    sched = spec.fault_schedule(inst)
    t = next((t for t in range(sched.n_windows) if sched.active(t)), None)
    if t is not None:
        return sched, t
    busiest = int(np.argmax(cold.solution.y.sum(axis=0)))
    return FaultSchedule(n_windows=1,
                         events=(TierOutage(tier=busiest, t0=0, t1=1),)), 0


def phase_repair(spec, inst, cold) -> dict:
    from repro import PlanSession
    from repro.core import apply_faults
    sched, t = _fault(spec, inst, cold)
    ses = PlanSession(engine="xla")

    def repair():
        ses.seed(inst, cold)
        return ses.repair(schedule=sched, t=t)

    res, wall, setup = _twice(repair)
    viol = _violations(apply_faults(inst, sched, t), res.solution)
    rep = res.diagnostics["repair"]
    level = rep["degradation"]["level"]
    oracle = {"events": [repr(e) for e in sched.active(t)],
              "evicted_pairs": len(rep["evicted"]), "warm": rep["warm"],
              "objective": res.objective, "violations": viol,
              "degradation_level": level,
              "ok": not viol or level >= 1}
    return {"phase": "repair", "ok": oracle["ok"], "wall_s": wall,
            "setup_s": setup, "oracle": oracle,
            "counters": _xla_counters(res.diagnostics)}


def phase_risk(name: str, inst, deploy, S: int) -> dict:
    """Time `risk_evaluate(engine="pdhg")` over S scenarios and hold the
    timed report's own per-scenario costs to the exact engine's on the
    same scenario stream."""
    from repro.risk import risk_evaluate
    report, wall, setup = _twice(
        lambda: risk_evaluate(inst, deploy, S=S, engine="pdhg"))
    diag = report.diagnostics
    pd = report.stage2_costs
    ex = risk_evaluate(inst, deploy, S=S, engine="exact").stage2_costs
    rel = float(np.max(np.abs(pd - ex) / np.maximum(np.abs(ex), 1e-300)))
    settled = diag["n_anchor0"] + diag["n_pdhg"]
    oracle = {"S": S, "max_rel_diff": rel,
              "rtol": RISK_RTOL, "device_settled": settled,
              "expected_cost": report.expected_cost,
              "cvar_0.95": report.cvar["0.95"],
              "ok": bool(np.allclose(pd, ex, rtol=RISK_RTOL, atol=0.0))
              and settled > 0}
    return {"phase": name, "ok": oracle["ok"], "wall_s": wall,
            "setup_s": setup, "oracle": oracle,
            "counters": {k: diag.get(k, 0) for k in RISK_COUNTERS}}


def run(spec, risk_inst) -> bool:
    """Run the phases in order and print one JSON line each.  True iff
    every check passed."""
    from repro import plan
    inst = spec.build()
    line, cold = phase_plan(inst)
    lines = [line]
    print(json.dumps(line), flush=True)
    for make in (lambda: phase_replan(inst, cold),
                 lambda: phase_repair(spec, inst, cold),
                 lambda: phase_risk("risk", risk_inst,
                                    plan("gh", instance=risk_inst).solution,
                                    S=20_000),
                 lambda: phase_risk("risk_fleet", inst, cold.solution,
                                    S=300)):
        lines.append(make())
        print(json.dumps(lines[-1]), flush=True)
    return all(ln["ok"] for ln in lines)


def main() -> int:
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no TPU found ({e})", file=sys.stderr)
        return 2
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's default backend is "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 2
    try:
        from repro import scenario
        from repro.core import random_instance
        from repro.runtime import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    cache = use_compile_cache()
    print(f"chip_smoke: compile cache {cache}", file=sys.stderr)
    ok = run(scenario("fleet-scale"), random_instance(20, 20, 20, seed=42))
    if not ok:
        print("chip_smoke: a check failed (see the phase lines)",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
