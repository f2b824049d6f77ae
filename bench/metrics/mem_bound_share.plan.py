"""mem_bound_share.plan: share of the M1-feasible (type, model, tier)
cells whose M1 winner takes more devices than the delay SLO alone asks
for, so per-device memory sets the device count (the planner's
`m1_mem_bound` over `m1_cells`, summed over the window's plans).  None
where the program keeps no such counter."""
from bench.lib.readers import diag_sum


def read(run):
    if not run.records or any("m1_cells" not in rec.answer["diagnostics"]
                              for rec in run.records):
        return None
    cells = diag_sum(run, "m1_cells")
    return 100.0 * diag_sum(run, "m1_mem_bound") / cells if cells else None
