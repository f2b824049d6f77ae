"""Tail-risk reports: `repro.risk.risk_evaluate` per call.

The deployment is built once in set-up, as the configuration's
``risk_deployment`` says (a GH plan, or a cold AGH plan on an engine).
Call *i* scores it over `S` scenarios of the traffic's scenario family,
drawn from the seed of the pool entry the call is sent.  Set-up runs
`warmup_calls` full reports of the warm-up stream, on the same shapes.

Checked after the window: for `check_reports` reports drawn from the
seed (all when fewer completed), `check_scenarios` scenarios each (all
when S is smaller), the report's Stage-2 cost against the plain
reference LP of that scenario (`bench.lib.stage2_ref`).  Every scenario
of a report is either settled on the device and verified there, or
solved exactly on the host; the sample covers both kinds.
"""
from __future__ import annotations

import numpy as np

from bench.lib.harness import Check, make_instance
from bench.lib.instances import InputPool, entry_seed
from bench.lib.stage2_ref import Stage2Reference, cost_gap, scenario_draws


class Cell:
    def __init__(self, config: dict, traffic: dict):
        from repro import plan
        self.config, self.traffic = config, traffic
        self.inst0, self.fields0 = make_instance(config)
        dep = config["risk_deployment"]
        self.deploy = plan(dep["solver"], instance=self.inst0,
                           engine=dep.get("engine")).solution
        self.inst0._xla_tensors = None    # the planner's device tensors
        for w in range(int(traffic["warmup_calls"])):
            self.report(entry_seed(int(traffic["pool_seed"]), w,
                                   InputPool.WARMUP))

    def report(self, seed: int):
        """The service call."""
        from repro.risk import risk_evaluate
        t = self.traffic
        return risk_evaluate(self.inst0, self.deploy, S=int(t["S"]),
                             engine=t["engine"], seed=seed,
                             chunk=int(t["chunk"]), **t["family"])

    def start(self, seed: int) -> None:
        self.pool = InputPool(int(self.traffic["pool_seed"]),
                              int(self.traffic["block"]), seed)

    def call(self, i: int) -> dict:
        entry = self.pool.index(i)
        seed = entry_seed(int(self.traffic["pool_seed"]), entry)
        rep = self.report(seed)
        return {"entry": entry, "seed": seed, "S": rep.S,
                "costs": rep.stage2_costs, "diagnostics": rep.diagnostics}

    def release(self) -> None:
        """Drop the program's objects, and with them its device state."""
        self.inst0 = None

    def check(self, records, seed: int, control: bool = False
              ) -> list[Check]:
        """Per-scenario costs against the reference LP; `control` puts the
        float32 reference's cost in the program's place."""
        t = self.traffic
        rng = np.random.default_rng([seed, 7])
        ref = Stage2Reference(self.fields0, self.deploy)
        low = Stage2Reference(self.fields0, self.deploy, np.float32)
        n_rep = min(int(t["check_reports"]), len(records))
        gaps = []
        for k in sorted(rng.choice(len(records), n_rep, replace=False)):
            a = records[k].answer
            S = a["S"]
            n = min(int(t["check_scenarios"]), S)
            keep = np.sort(rng.choice(S, n, replace=False))
            draws = scenario_draws(self.fields0, a["seed"], S, t["family"],
                                   keep)
            for row, s in enumerate(keep):
                scen = (draws["tau"][row], draws["e_base"][row],
                        draws["lam"][row])
                value = low.cost(*scen) if control else a["costs"][s]
                gaps.append(cost_gap(value, ref.cost(*scen)))
        return [Check("cost_gap", max(gaps), t["limits"]["cost_gap"])]
