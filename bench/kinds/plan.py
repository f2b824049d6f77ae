"""Cold plans: `repro.plan(solver, instance=..., engine=...)` per call.

Call *i* plans the deployment under a fresh demand forecast: every
type's arrival rate scaled by its own U(1 - drift_pm, 1 + drift_pm)
factor, drawn for the pool entry the call is sent (see
`bench.lib.instances.InputPool`).  Set-up plans the base deployment once
and `warmup_calls` forecasts of the warm-up stream, on the same shapes.

Checked after the window, for every call: the plan's objective against
the plain reference's (`bench.lib.plan_ref`), and its hard constraints.
For `keys_calls` calls drawn from the seed among the first `min_calls`,
every output of the xla tier's device programs (Phase-2 keys, relocate
screen) against the plain reference of those programs
(`bench.lib.xla_ref`): the host driver keeps a plan feasible whatever
the device returns, so only this check sees a wrong device answer.
"""
from __future__ import annotations

import numpy as np

from bench.lib import xla_ref
from bench.lib.harness import Check, make_instance
from bench.lib.instances import InputPool, entry_rng
from bench.lib.plan_ref import PlanModel, objective_gap
from bench.lib.xla_tap import KernelTap


class Cell:
    def __init__(self, config: dict, traffic: dict):
        from repro import PlanOptions
        self.config, self.traffic = config, traffic
        self.inst0, self.fields0 = make_instance(config)
        self.options = PlanOptions(engine=traffic["engine"])
        self.base = self.solve(self.inst0)
        for w in range(int(traffic["warmup_calls"])):
            self.solve(self.inst0.with_lam(self.forecast(w,
                                                         InputPool.WARMUP)))
        self.tap = KernelTap()

    def forecast(self, entry: int, stream: int = InputPool.WINDOW):
        """Arrival rates of one pool entry."""
        pm = float(self.traffic["drift_pm"])
        rng = entry_rng(int(self.traffic["pool_seed"]), entry, stream)
        return self.fields0["lam"] * rng.uniform(1.0 - pm, 1.0 + pm,
                                                 self.inst0.I)

    def solve(self, inst):
        """The service call."""
        from repro import plan
        return plan(self.traffic["solver"], instance=inst,
                    options=self.options)

    def start(self, seed: int) -> None:
        t = self.traffic
        self.pool = InputPool(int(t["pool_seed"]), int(t["block"]), seed)
        rng = np.random.default_rng([seed, 11])
        self.keys_sample = set(rng.choice(
            int(t["min_calls"]), int(t["keys_calls"]), replace=False).tolist())
        self.tap.calls = []

    def call(self, i: int) -> dict:
        entry = self.pool.index(i)
        lam = self.forecast(entry)
        self.tap.tag = entry if i in self.keys_sample else None
        try:
            res = self.answer(lam)
        finally:
            self.tap.tag = None
        return {"entry": entry, "lam": lam, "result": res,
                "objective": float(res.objective),
                "diagnostics": res.diagnostics}

    def answer(self, lam):
        return self.solve(self.inst0.with_lam(lam))

    def release(self) -> None:
        """Drop the program's objects, and with them its device state."""
        self.tap.close()
        self.inst0 = None

    # -- the check --------------------------------------------------------
    def check(self, records, seed: int, control: bool = False
              ) -> list[Check]:
        """Reference checks of every call; `control` puts the float32
        reference in the program's place."""
        gaps, viols = [], []
        lams = {}
        for rec in records:
            a = rec.answer
            lams[a["entry"]] = a["lam"]
            fields = dict(self.fields0, lam=a["lam"])
            sol = a["result"].solution
            ref = PlanModel(fields)
            value = (PlanModel(fields, np.float32).objective(sol) if control
                     else a["objective"])
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
        """Widest Phase-2 key gap and number of screen verdicts the
        reference contradicts, over the tapped calls of completed plans."""
        tables: dict = {}
        keys, screens = 0.0, 0
        for c in self.tap.calls:
            if c.tag not in lams:
                continue
            if c.tag not in tables:
                fields = dict(self.fields0, lam=lams[c.tag])
                tables[c.tag] = (xla_ref.XlaTables(fields),
                                 xla_ref.XlaTables(fields, np.float32)
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
