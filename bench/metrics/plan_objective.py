"""plan_objective: mean objective (8a) of the first `objective_calls`
plans of the window, in dollars over the 24-hour planning horizon,
priced by the benchmark's own reference from the returned plans."""
from bench.lib.instances import make_fields
from bench.lib.plan_ref import PlanModel


def read(run):
    k = int(run.traffic["objective_calls"])
    if len(run.records) < k:
        return None
    fields = make_fields(run.config)
    total = 0.0
    for rec in run.records[:k]:
        model = PlanModel(dict(fields, lam=rec.answer["lam"]))
        total += model.objective(rec.answer["result"].solution)
    return total / k
