"""Closed-loop serving: forecast-aware replanning vs fixed cadence vs static.

One `repro.serve()` run per controller mode over the same seeded diurnal
day of Poisson traffic (`core/trace.diurnal_multipliers("busy")`,
lognormal token-length noise, plan-aware weighted-random routing):

* ``forecast`` — the tentpole controller: EWMA arrival-rate forecast +
  drift/SLO-violation trigger (`serving.ReplanController`), warm
  `PlanSession.replan()` on firings only;
* ``fixed``    — blind cadence: replan every ``replan_every`` windows
  (PR 5's ``rolling(replan_every=)`` behavior, the baseline the paper's
  operating loop implies);
* ``static``   — never replan (the frozen-plan floor).

Every mode starts from the same cold AGH plan of the queueing-margin view
(`with_queueing_margin(inst, RHO_MAX)` — ~`1/(1-rho)` latency headroom so
p99, not mean, meets the SLO under simulated queueing + slowest-member
batch coupling), and every replan re-applies the same margin to its
forecast basis so a mid-run replan never sheds the headroom policy.

The acceptance claim this benchmark demonstrates at (100,80,40): the
forecast controller keeps worst-type p99 e2e within its SLO through the
diurnal cycle with strictly fewer replans than the fixed cadence at
equal-or-better attainment, and total planner wall time stays under 5% of
the simulated horizon.

Row identity for the CI regression gate encodes the mode into the size
string (``"(100,80,40)|forecast"``; `check_regression._row_key` is
``(size, engine)``).  Traffic, routing, and the simulator are seeded and
numpy-only, so attainment / replan counts / p99 ratios are deterministic
and exact-gated (``*_obj``); planner wall time is machine-dependent and
runtime-gated (``*_s``).

``--trajectory-out PATH`` appends this run's rows to the append-only
``BENCH_allocator.json`` artifact, same as `allocator_scaling`.
"""
from __future__ import annotations

import numpy as np

from repro.core import random_instance
from repro.core.queueing import with_queueing_margin
from repro.planner import PlanOptions, PlanSession
from repro.serving import ControllerSpec, TrafficSpec, serve

from .common import Timer, emit

SIZES = [(100, 80, 40)]                  # the acceptance fleet scale
QUICK_SIZES = [(24, 20, 10)]             # CI smoke
RHO_MAX = 0.65                           # queueing-margin utilization cap
HORIZON_S = 86400.0                      # one full diurnal day
QUICK_HORIZON_S = 7200.0
WINDOW_S = 300.0                         # 5-minute control windows
RATE_SCALE = 0.005                       # Poisson thinning of fleet rates
QUICK_RATE_SCALE = 0.02
TRACE = "busy"                           # core.trace diurnal day
MODES = ("forecast", "fixed", "static")
# Forecast-trigger knobs tuned for the diurnal trace: slower EWMA + a
# higher drift bar than the defaults, so the controller tracks the ramp
# with a handful of replans instead of firing every cooldown.
FORECAST_KW = dict(drift_threshold=0.5, cooldown=6, ewma_alpha=0.5)


def _controller(mode: str) -> ControllerSpec:
    kw = FORECAST_KW if mode == "forecast" else {}
    return ControllerSpec(mode=mode, rho_max=RHO_MAX, **kw)


def run(sizes=SIZES, horizon_s: float = HORIZON_S,
        rate_scale: float = RATE_SCALE, quick: bool = False) -> list[dict]:
    if quick:
        sizes, horizon_s, rate_scale = (QUICK_SIZES, QUICK_HORIZON_S,
                                        QUICK_RATE_SCALE)
    rows: list[dict] = []
    for (I, J, K) in sizes:
        inst = random_instance(I, J, K, seed=42)
        traffic = TrafficSpec(horizon_s=horizon_s, window_s=WINDOW_S,
                              rate_scale=rate_scale, trace=TRACE, seed=1)
        size = f"({I},{J},{K})"
        mode_rows: dict[str, dict] = {}
        for mode in MODES:
            # Fresh session per mode: serve() advances the session in
            # place (the incumbent after a run is the last replan's).
            sess = PlanSession(options=PlanOptions())
            with Timer() as t_plan:
                res = sess.plan(instance=with_queueing_margin(inst, RHO_MAX))
            sr = serve(res, instance=inst, session=sess, traffic=traffic,
                       controller=_controller(mode))
            p99_slo = float(np.nanmax(sr.per_type_e2e_p99 / inst.Delta))
            cal = sr.calibration()
            row = {
                "size": f"{size}|{mode}", "engine": "numpy",
                "initial_obj": round(res.objective, 4),
                "attain_obj": round(sr.attainment(), 6),
                "replans_obj": len(sr.replans),
                "served_obj": sr.n_served, "shed_obj": sr.n_shed,
                "p99_slo_ratio_obj": round(p99_slo, 4),
                "rental_per_h_obj": round(sr.mean_rental_per_h, 4),
                "calibration_med_obj": round(float(np.nanmedian(cal)), 4),
                "plan_wall_s": round(t_plan.dt, 4),
                "replan_wall_s": round(sr.planner_wall_s, 4),
                "planner_frac": round(
                    (t_plan.dt + sr.planner_wall_s) / horizon_s, 6),
            }
            rows.append(row)
            mode_rows[mode] = row
            emit(f"serve_closed_loop.{size}.{mode}",
                 sr.planner_wall_s * 1e6,
                 f"attain={row['attain_obj']:.4f};"
                 f"replans={row['replans_obj']};"
                 f"p99/slo={p99_slo:.3f};shed={sr.n_shed};"
                 f"pfrac={row['planner_frac']:.5f}")

        # Acceptance facts (informational in quick mode — the tiny smoke
        # instance is not the claim; the (100,80,40) day is).
        fc, fx = mode_rows["forecast"], mode_rows["fixed"]
        facts = {
            "fewer_replans": fc["replans_obj"] < fx["replans_obj"],
            "attain_ok": fc["attain_obj"] >= fx["attain_obj"] - 1e-9,
            "p99_within_slo": fc["p99_slo_ratio_obj"] <= 1.0,
            "planner_under_5pct": fc["planner_frac"] < 0.05,
        }
        emit(f"serve_closed_loop.{size}.acceptance", 0.0,
             ";".join(f"{k}={v}" for k, v in facts.items()))
        if not quick and not all(facts.values()):
            raise AssertionError(
                f"closed-loop acceptance failed at {size}: {facts}")
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small instance + short horizon (CI smoke)")
    ap.add_argument("--horizon", type=float, default=HORIZON_S,
                    help="simulated seconds (full mode)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="dump rows as a benchmarks.run-style JSON file "
                         "(consumed by check_regression)")
    ap.add_argument("--trajectory-out", default=None, metavar="PATH",
                    help="append this run's rows to the trajectory "
                         "artifact (e.g. BENCH_allocator.json)")
    args = ap.parse_args()
    out_rows = run(horizon_s=args.horizon, quick=args.quick)
    if args.json:
        import json

        from .common import JSON_SCHEMA_VERSION, ensure_outdir, git_sha
        ensure_outdir(args.json)
        with open(args.json, "w") as fh:
            json.dump({"schema_version": JSON_SCHEMA_VERSION,
                       "git_sha": git_sha(),
                       "sections": {"serve_closed_loop": out_rows}}, fh,
                      indent=2)
        print(f"# wrote {args.json}", flush=True)
    if args.trajectory_out:
        from .trajectory import append
        append(args.trajectory_out, out_rows, label="serve_closed_loop")
