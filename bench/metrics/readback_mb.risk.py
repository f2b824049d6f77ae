"""readback_mb.risk: megabytes the risk engine reads back from the device
per report (the solver's `readback_bytes` counter: candidate verdicts,
costs, primal and row values, PDHG iterates).  None where the program
keeps no such counter."""
from bench.lib.readers import diag_sum


def read(run):
    if not run.records or any("readback_bytes" not in rec.answer["diagnostics"]
                              for rec in run.records):
        return None
    return diag_sum(run, "readback_bytes") / len(run.records) / 1e6
