"""Failure-injection replay: warm repair vs cold re-solve vs frozen static.

One diurnal rolling replay per (instance size, fault response) over a
seeded supply-fault schedule (`core/faults.py`): Poisson spot
revocations on the cheapest third of the tier catalog, a mid-replay
fleet-wide capacity shock, and a full outage of the busiest tier.  Every
supply change point triggers an event-driven re-solve; the three
responses differ only in how they react:

* ``repair``  — `PlanSession.repair` (warm `agh_repair`: evict, re-route,
  one incremental pass, graceful-degradation ladder);
* ``cold``    — a full cold AGH solve of the faulted instance per event;
* ``static``  — no reaction: the initial placement rides through the
  faults and loses the traffic its revoked pairs carried (the
  degradation floor the other two are measured against);
* ``nofault`` — the same replay with no fault schedule (the cost floor:
  ``cost_drift`` on the fault rows is total cost relative to this row).

Row identity for the CI regression gate (`check_regression._row_key` is
``(size, engine)``) encodes the response into the size string —
``"(100,80,40)|repair"`` — so the four rows of one size never collide.
``initial_obj`` is the deterministic cold solve of the unfaulted
instance (exact-gated); ``repair_wall_mean_s`` / ``repair_wall_max_s``
are the per-event re-solve latencies (runtime-gated 5x) — the
acceptance bar is sub-second warm repairs at (100,80,40).

``--trajectory-out PATH`` appends this run's rows to the append-only
``BENCH_allocator.json`` artifact, same as `allocator_scaling`.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core import (CapacityShock, FaultSchedule, TierOutage,
                        poisson_revocations, random_instance, rolling,
                        with_spot_tiers)
from repro.core.trace import diurnal_multipliers
from repro.planner import PlanOptions, PlanSession, plan

from .common import emit

SIZES = [(100, 80, 40)]                  # the acceptance fleet scale
QUICK_SIZES = [(24, 20, 10)]             # CI smoke
WINDOWS = 32                             # full replay length (45-min windows)
QUICK_WINDOWS = 12
REPLAN_EVERY = 8                         # scheduled replans between events
SPOT_REVOKE_RATE = 0.02                  # revocations/hour per spot tier
SPOT_FRACTION = 3                        # cheapest 1/3 of tiers on spot
ZETA = 0.5                               # binding unmet cap (ladder-visible)
CAP_HEADROOM = 1.5                       # nominal avail = 1.5x cold usage + 4
RESPONSES = ("static", "cold", "repair")


def _build_case(I: int, J: int, K: int, T: int, seed: int = 42):
    """Instance with nominal availability caps + spot tiers, the seeded
    fault schedule, the diurnal demand path, and the deterministic cold
    solve of the unfaulted instance (the exact-gated anchor)."""
    inst = random_instance(I, J, K, seed=seed)
    inst = dataclasses.replace(inst, zeta=np.full(I, ZETA))
    opts = PlanOptions()
    cold0 = plan("agh", instance=inst, options=opts)
    y_tier = cold0.solution.y.sum(axis=0)
    nominal = np.ceil(CAP_HEADROOM * y_tier) + 4
    capped = dataclasses.replace(inst, avail_gpus=nominal)
    spot_idx = np.argsort(inst.p_c)[: max(1, K // SPOT_FRACTION)]
    capped = with_spot_tiers(capped, spot_idx,
                             revoke_rate=SPOT_REVOKE_RATE)
    events = list(poisson_revocations(capped, T, seed=seed + 7, frac=0.6))
    dur = max(2, T // 8)
    busiest = int(np.argmax(y_tier))
    events += [
        CapacityShock(t0=T // 3, t1=T // 3 + dur, avail_frac=0.5),
        TierOutage(tier=busiest, t0=(2 * T) // 3, t1=(2 * T) // 3 + dur),
    ]
    sched = FaultSchedule(n_windows=T, events=tuple(events))
    mult = diurnal_multipliers("busy", seed=seed + 9, n_windows=T)
    lam_path = np.outer(mult, inst.lam)
    return capped, sched, lam_path, cold0, opts


def run(sizes=SIZES, T: int = WINDOWS, quick: bool = False) -> list[dict]:
    if quick:
        sizes, T = QUICK_SIZES, QUICK_WINDOWS
    rows: list[dict] = []
    for (I, J, K) in sizes:
        capped, sched, lam_path, cold0, opts = _build_case(I, J, K, T)
        size = f"({I},{J},{K})"

        def bare(inst, _opts=opts):
            return plan("agh", instance=inst, options=_opts).solution

        base = rolling(capped, lam_path, bare, replan_every=REPLAN_EVERY)
        rows.append({
            "size": f"{size}|nofault", "engine": "numpy",
            "initial_obj": round(cold0.objective, 4),
            "total_cost": round(base.total_cost, 4),
            "violation_rate": round(base.violation_rate, 6),
        })
        emit(f"failure_replay.{size}.nofault", 0.0,
             f"cost={base.total_cost:.2f};viol={base.violation_rate:.4f}")

        for response in RESPONSES:
            planner = (PlanSession(options=opts) if response == "repair"
                       else bare)
            r = rolling(capped, lam_path, planner,
                        replan_every=(None if response == "static"
                                      else REPLAN_EVERY),
                        faults=sched, fault_response=response)
            row: dict = {
                "size": f"{size}|{response}", "engine": "numpy",
                "initial_obj": round(cold0.objective, 4),
                "total_cost": round(r.total_cost, 4),
                "violation_rate": round(r.violation_rate, 6),
                "cost_drift": round(
                    r.total_cost / max(base.total_cost, 1e-9) - 1.0, 4),
                "fault_replans": r.fault_replans,
                "evictions": r.evictions,
            }
            if r.repair_wall_s:
                walls = np.asarray(r.repair_wall_s)
                row["repair_wall_mean_s"] = round(float(walls.mean()), 4)
                row["repair_wall_max_s"] = round(float(walls.max()), 4)
            if r.degradation_levels:
                row["deg_level_max"] = int(max(r.degradation_levels))
            rows.append(row)
            wall = float(np.mean(r.repair_wall_s)) if r.repair_wall_s else 0.0
            emit(f"failure_replay.{size}.{response}", wall * 1e6,
                 f"cost={r.total_cost:.2f};viol={r.violation_rate:.4f};"
                 f"drift={row['cost_drift']:+.3f};"
                 f"evict={r.evictions}")
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small instance + short replay (CI smoke)")
    ap.add_argument("--windows", type=int, default=WINDOWS,
                    help="replay length in windows (full mode)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="dump rows as a benchmarks.run-style JSON file "
                         "(consumed by check_regression)")
    ap.add_argument("--trajectory-out", default=None, metavar="PATH",
                    help="append this run's rows to the trajectory "
                         "artifact (e.g. BENCH_allocator.json)")
    args = ap.parse_args()
    out_rows = run(T=args.windows, quick=args.quick)
    if args.json:
        import json

        from .common import JSON_SCHEMA_VERSION, ensure_outdir, git_sha
        ensure_outdir(args.json)
        with open(args.json, "w") as fh:
            json.dump({"schema_version": JSON_SCHEMA_VERSION,
                       "git_sha": git_sha(),
                       "sections": {"failure_replay": out_rows}}, fh,
                      indent=2)
        print(f"# wrote {args.json}", flush=True)
    if args.trajectory_out:
        from .trajectory import append
        append(args.trajectory_out, out_rows, label="failure_replay")
