"""plan_s: the window's seconds over the plan or replan calls completed
in it, the operator's wait per answer."""


def read(run):
    return run.window_s / len(run.records) if run.records else None
