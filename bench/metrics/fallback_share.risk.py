"""fallback_share.risk: scenarios whose device work was thrown away and
that the host's exact solver settled, over all scenarios of the window's
reports (the risk driver's `n_fallback_exact`)."""
from bench.lib.readers import diag_sum


def read(run):
    if not run.records:
        return None
    scenarios = sum(rec.answer["S"] for rec in run.records)
    return 100.0 * diag_sum(run, "n_fallback_exact") / scenarios
