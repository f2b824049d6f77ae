"""Allocator scaling: registry-keyed rows for every engine generation.

Each row is one (instance size, engine) pair; solver columns are
sub-dicts keyed by the planner-registry name (plus a ``+variant`` suffix
for non-default engine modes), produced directly from
`PlanResult.summary()` — the CI regression gate
(`benchmarks/check_regression.py`) flattens and diffs them against the
committed baseline:

* ``gh``             — vectorized GH through the facade;
* ``agh``            — the incremental engine (default);
* ``agh+rescan``     — dirty-source tracking disabled (PR-3-style);
* ``agh+reference``  — the PR-1/PR-2 first-improvement probe loop;
  these two are `core.agh.agh` test-oracle modes, called directly;
* ``agh+warm``       — `PlanSession.replan` on a ±15% drifted demand
  vector, seeded from the undrifted incumbent, next to the cold AGH
  solve of the same drifted instance (``cold_*`` fields + ``speedup``);
* ``agh+xla``        — `--engine xla`: the jitted lane-batched tier.
  Every xla cell is solved twice: the first run pays jit tracing and is
  reported as ``compile_s`` (first minus second wall), the second run's
  steady-state timing is what the row and the regression gate see;
* flat ``GH_before_us`` / ``AGH_before_us`` — the frozen scalar seed
  path, kept at sizes where it finishes in seconds.

``--trajectory-out PATH`` appends this run's rows to the append-only
repo-root ``BENCH_allocator.json`` artifact (see
`benchmarks/trajectory.py`).  Emits one ``name,us_per_call`` line per
cell so perf regressions show up directly in CI logs.
"""
from __future__ import annotations

import numpy as np

from repro.core import random_instance
from repro.core._scalar_ref import agh_scalar, gh_scalar
from repro.core.solution import objective
from repro.planner import PlanOptions, PlanResult, PlanSession, plan

from .common import Timer, agh_mode, emit

SIZES = [(6, 6, 10), (10, 10, 10), (15, 15, 10), (20, 20, 20), (30, 30, 20),
         (40, 40, 30), (60, 60, 40)]
# Beyond-paper sizes: the PR-4 acceptance instance plus two fleet-scale
# points (the paper's Table 6 stops at (20,20,20)).
SIZES_XL = SIZES + [(100, 80, 40), (150, 120, 60), (200, 160, 80)]
QUICK_SIZES = [(6, 6, 10), (20, 20, 20)]
SCALAR_AGH_MAX = 10 * 10 * 10   # scalar AGH above this takes minutes
SCALAR_GH_MAX = 30 * 30 * 20    # scalar GH above this takes tens of seconds
REF_AGH_MAX = 100 * 80 * 40     # reference-mode AGH above this: minutes
DRIFT_PM = 0.15                 # warm-replan demo: ±15% per-type demand


def _cell(row: dict, size: str, key: str, inst,
          options=None) -> PlanResult:
    """One facade solve -> registry-keyed summary + CSV line.

    xla cells are solved twice: run 1 includes jit tracing (reported as
    ``compile_s``), run 2 is the steady-state row the gate diffs."""
    solver = key.split("+")[0]
    opts = options or PlanOptions()
    res = plan(solver, instance=inst, options=opts)
    cell = res.summary()
    if opts.engine == "xla":
        warm = plan(solver, instance=inst, options=opts)
        cell = warm.summary()
        cell["compile_s"] = round(max(0.0, res.wall_s - warm.wall_s), 4)
        res = warm
    row[key] = cell
    emit(f"allocator_scaling.{size}.{key}", res.wall_s * 1e6,
         f"obj={res.objective:.2f}")
    return res


def _oracle_cell(row: dict, size: str, key: str, inst,
                 local_search: str) -> None:
    """One `agh` test-oracle local-search mode -> summary + CSV line."""
    res = agh_mode(inst, local_search)
    row[key] = res.summary()
    emit(f"allocator_scaling.{size}.{key}", res.wall_s * 1e6,
         f"obj={res.objective:.2f}")


def _run_xla_row(row: dict, size: str, inst) -> None:
    res = _cell(row, size, "agh+xla", inst, PlanOptions(engine="xla"))
    emit(f"allocator_scaling.{size}.agh+xla.compile",
         row["agh+xla"]["compile_s"] * 1e6,
         f"steady_s={res.wall_s:.3f}")


def run(sizes=SIZES, scalar_agh_max: int = SCALAR_AGH_MAX,
        scalar_gh_max: int = SCALAR_GH_MAX,
        ref_agh_max: int = REF_AGH_MAX, warm_demo: bool = True,
        engine: str = "numpy") -> list[dict]:
    rows = []
    for (I, J, K) in sizes:
        inst = random_instance(I, J, K, seed=42)
        size = f"({I},{J},{K})"
        row: dict = dict(size=size, engine=engine)

        if engine == "xla":
            # The xla tier rides its own rows (same sizes, engine-keyed
            # so the gate never diffs them against numpy timings).
            _run_xla_row(row, size, inst)
            rows.append(row)
            continue

        if I * J * K <= scalar_gh_max:
            with Timer() as t:
                g_ref, _ = gh_scalar(inst)
            row["GH_before_us"] = t.us
            emit(f"allocator_scaling.{size}.GH.before", t.us,
                 f"obj={objective(inst, g_ref):.2f}")
        _cell(row, size, "gh", inst)

        if I * J * K <= scalar_agh_max:
            with Timer() as t:
                a_ref = agh_scalar(inst)
            row["AGH_before_us"] = t.us
            emit(f"allocator_scaling.{size}.AGH.before", t.us,
                 f"obj={objective(inst, a_ref):.2f}")
        if I * J * K <= ref_agh_max:
            _oracle_cell(row, size, "agh+reference", inst, "reference")
        _oracle_cell(row, size, "agh+rescan", inst, "batched-rescan")
        agh_res = _cell(row, size, "agh", inst)

        if warm_demo:
            # Warm-started replanning (ISSUE 5 acceptance): drift every
            # type's demand by ±15%, solve cold, then replan warm from the
            # undrifted incumbent.  The session is seeded with the `agh`
            # row's result (no duplicate cold solve).
            drift = np.random.default_rng(7).uniform(
                1.0 - DRIFT_PM, 1.0 + DRIFT_PM, inst.I)
            drifted = inst.with_lam(inst.lam * drift)
            cold = plan("agh", instance=drifted)
            ses = PlanSession()
            ses.seed(inst, agh_res)
            warm = ses.replan(instance=drifted)
            row["agh+warm"] = {
                **warm.summary(),
                "cold_objective": round(cold.objective, 4),
                "cold_wall_s": round(cold.wall_s, 4),
                "speedup": round(cold.wall_s / max(warm.wall_s, 1e-9), 2),
                "orderings": warm.diagnostics.get("orderings_evaluated"),
            }
            emit(f"allocator_scaling.{size}.agh+warm", warm.wall_s * 1e6,
                 f"obj={warm.objective:.2f};cold_obj={cold.objective:.2f};"
                 f"speedup={row['agh+warm']['speedup']:.2f}x")
        rows.append(row)
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smallest + acceptance size only (CI smoke)")
    ap.add_argument("--xl", action="store_true",
                    help="include the beyond-paper sizes up to (200,160,80)")
    ap.add_argument("--engine", default="numpy", choices=("numpy", "xla"),
                    help="allocator engine for the agh rows (xla adds "
                         "compile-vs-steady split; needs jax)")
    ap.add_argument("--trajectory-out", default=None, metavar="PATH",
                    help="append this run's rows to the trajectory "
                         "artifact (e.g. BENCH_allocator.json)")
    ap.add_argument("--scalar-agh-max", type=int, default=SCALAR_AGH_MAX,
                    help="largest I*J*K for which the scalar AGH is timed")
    args = ap.parse_args()
    out_rows = run(sizes=(QUICK_SIZES if args.quick else
                          (SIZES_XL if args.xl else SIZES)),
                   scalar_agh_max=args.scalar_agh_max,
                   engine=args.engine)
    if args.trajectory_out:
        from .trajectory import append
        append(args.trajectory_out, out_rows,
               label=f"allocator_scaling --engine {args.engine}")
