"""candidate_calls.risk: anchor candidate kernel calls per report (the
solver's `candidate_calls` counter), each one a dispatch, a wait and a
read-back on the host.  None where the program keeps no such counter."""
from bench.lib.readers import diag_sum


def read(run):
    if not run.records or any("candidate_calls" not in rec.answer["diagnostics"]
                              for rec in run.records):
        return None
    return diag_sum(run, "candidate_calls") / len(run.records)
