"""risk_scen_per_s: scenarios of the reports completed in the window
over the window's seconds."""


def read(run):
    if not run.records:
        return None
    return sum(rec.answer["S"] for rec in run.records) / run.window_s
