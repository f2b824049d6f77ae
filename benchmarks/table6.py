"""Table 6: runtime scaling with problem size (I, J, K).

Paper: DM exceeds 600 s at (15,15,10); GH < 1 s and AGH < 3 s everywhere
(>= 260x speedup at (20,20,20)).

Rows are registry-keyed (schema v3): each solver column is the
`PlanResult.summary()` sub-dict of one facade solve — ``gh``, ``agh``,
``agh+reference`` (the PR-2 first-improvement engine, capped at
`REF_AGH_MAX`), and ``milp`` (the exact DM; an anytime incumbent under
``dm_limit``, so the CI gate skips its columns).  The frozen scalar seed
GH is timed next to them as flat ``GH_before_s`` (capped at
`SCALAR_GH_MAX` — it takes tens of seconds beyond (30,30,20)).

DM column: `dm_max_size` bounds the largest I*J*K for which the exact
MILP is attempted — the unified default of 1000 runs DM through
(10,10,10) and skips it above (at (15,15,10) the paper already reports
>600 s; the CLI's ``--dm-max-size`` raises the bound for
full-replication runs, as does ``benchmarks.run --full``).  Skipped rows
show ``DM_s = skipped(>size)``.

``SIZES_EXT`` (CLI ``--ext``) pushes past the paper's largest instance:
(30,30,20) from PR 1, the PR-3 beyond-paper sizes (40,40,30), (60,60,40)
and (100,80,40), and the PR-4 fleet-scale points (150,120,60) and
(200,160,80)."""
from __future__ import annotations

from repro.core import random_instance
from repro.core._scalar_ref import gh_scalar
from repro.planner import PlanOptions, plan

from .common import Timer, agh_mode, emit

SIZES = [(4, 4, 5), (6, 6, 10), (10, 10, 10), (15, 15, 10), (20, 20, 20)]
SIZES_EXT = SIZES + [(30, 30, 20), (40, 40, 30), (60, 60, 40), (100, 80, 40),
                     (150, 120, 60), (200, 160, 80)]
DM_MAX_SIZE = 1000              # unified default: DM through (10,10,10)
SCALAR_GH_MAX = 30 * 30 * 20    # frozen scalar GH beyond this: minutes
REF_AGH_MAX = 100 * 80 * 40     # reference-mode AGH beyond this: minutes


def run(dm_limit: float = 600.0, dm_max_size: int = DM_MAX_SIZE,
        sizes=SIZES, include_before: bool = True) -> list[dict]:
    rows = []
    for (I, J, K) in sizes:
        inst = random_instance(I, J, K, seed=42)
        row: dict = dict(size=f"({I},{J},{K})")
        row["gh"] = plan("gh", instance=inst).summary()
        if include_before and I * J * K <= SCALAR_GH_MAX:
            with Timer() as t:
                gh_scalar(inst)
            row["GH_before_s"] = round(t.dt, 3)
        a = plan("agh", instance=inst)
        row["agh"] = a.summary()
        if include_before and I * J * K <= REF_AGH_MAX:
            row["agh+reference"] = agh_mode(inst, "reference").summary()
        if I * J * K <= dm_max_size:
            d = plan("milp", instance=inst,
                     options=PlanOptions(time_limit=dm_limit))
            row["milp"] = d.summary()
            solved = not d.diagnostics.get("timed_out", False)
            row["milp"]["status"] = d.diagnostics.get("status")
            if solved:
                row["AGH_gap_pct"] = round(
                    100 * (a.objective - d.objective)
                    / max(d.objective, 1e-9), 2)
        else:
            row["DM_s"] = f"skipped(>{dm_max_size})"
        rows.append(row)
        emit(f"table6.{row['size']}", row["agh"]["wall_s"] * 1e6,
             ";".join(f"{k}={v}" for k, v in row.items() if k != "size"))
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--dm-limit", type=float, default=600.0)
    ap.add_argument("--dm-max-size", type=int, default=DM_MAX_SIZE,
                    help="largest I*J*K for which the exact MILP is "
                         "attempted (default skips DM above (10,10,10))")
    ap.add_argument("--ext", action="store_true",
                    help="include the beyond-paper sizes up to (100,80,40)")
    args = ap.parse_args()
    run(dm_limit=args.dm_limit, dm_max_size=args.dm_max_size,
        sizes=SIZES_EXT if args.ext else SIZES)
