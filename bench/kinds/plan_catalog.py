"""Cold plans of a catalog deployment whose model rows state their active
bytes (sparse experts): `bench/kinds/plan.py` on an instance built by
`bench.lib.catalog` from the configuration's published model configs.

The calls, the forecasts and the tapped device programs are the plan
kind's.  The checks are too, against the split references: delay and
load from the bytes a token reads (`B_act`), memory and storage from the
resident bytes (`B`) (`bench.lib.plan_ref_split`,
`bench.lib.xla_ref_split`).
"""
from __future__ import annotations

import numpy as np

from bench.kinds.plan import Cell as PlanCell
from bench.lib import xla_ref
from bench.lib.catalog import catalog_fields
from bench.lib.harness import Check
from bench.lib.instances import InputPool
from bench.lib.plan_ref import objective_gap
from bench.lib.plan_ref_split import SplitPlanModel
from bench.lib.xla_ref_split import SplitXlaTables
from bench.lib.xla_tap import KernelTap


class Cell(PlanCell):
    def __init__(self, config: dict, traffic: dict):
        from repro import PlanOptions
        from repro.core.instance import Instance
        self.config, self.traffic = config, traffic
        self.fields0 = catalog_fields(config)
        self.inst0 = Instance(**self.fields0)
        self.options = PlanOptions(engine=traffic["engine"])
        self.base = self.solve(self.inst0)
        for w in range(int(traffic["warmup_calls"])):
            self.solve(self.inst0.with_lam(self.forecast(w,
                                                         InputPool.WARMUP)))
        self.tap = KernelTap()

    def check(self, records, seed: int, control: bool = False
              ) -> list[Check]:
        """The plan kind's checks against the split references; `control`
        puts the float32 reference in the program's place."""
        gaps, viols = [], []
        lams = {}
        for rec in records:
            a = rec.answer
            lams[a["entry"]] = a["lam"]
            fields = dict(self.fields0, lam=a["lam"])
            sol = a["result"].solution
            ref = SplitPlanModel(fields)
            value = (SplitPlanModel(fields, np.float32).objective(sol)
                     if control else a["objective"])
            gaps.append(objective_gap(value, ref.objective(sol)))
            viols.append(max(ref.violations(sol).values()))
        keys, screens = self.check_device(lams, control)
        limits = self.traffic["limits"]
        return [Check("objective_gap", max(gaps), limits["objective_gap"]),
                Check("max_violation", max(viols),
                      self.config["guarantees"]["hard_constraint_tol"]),
                Check("keys_gap", keys, limits["keys_gap"]),
                Check("screen_mismatch", screens, limits["screen_mismatch"])]

    def check_device(self, lams: dict, control: bool) -> tuple[float, int]:
        """As the plan kind's, on the split reference's grids."""
        tables: dict = {}
        keys, screens = 0.0, 0
        for c in self.tap.calls:
            if c.tag not in lams:
                continue
            if c.tag not in tables:
                fields = dict(self.fields0, lam=lams[c.tag])
                tables[c.tag] = (SplitXlaTables(fields),
                                 SplitXlaTables(fields, np.float32)
                                 if control else None)
            ref, low = tables[c.tag]
            if c.program == "_phase2_keys_jit":
                out = (xla_ref.phase2_keys(low, c.args)[:2] if control
                       else c.out)
                keys = max(keys, xla_ref.keys_gap(
                    xla_ref.phase2_keys(ref, c.args), *out))
            else:
                out = xla_ref.screen(low, c.args) if control else c.out
                screens += xla_ref.screen_mismatch(ref, c.args, out)
        return keys, screens
