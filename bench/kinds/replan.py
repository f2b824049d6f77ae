"""Warm replans: one `PlanSession`, carried from call to call.

Set-up seeds the session with the cold plan of the base deployment and
replans `warmup_calls` forecasts of the warm-up stream.  Call *i* then
replans to its pool entry's forecast (as in `bench/kinds/plan.py`),
warm-started from whatever the previous call left, as in rolling
operation.  The checks are the cold plans', the device programs' too.
"""
from __future__ import annotations

from bench.kinds.plan import Cell as PlanCell
from bench.lib.harness import make_instance
from bench.lib.instances import InputPool
from bench.lib.xla_tap import KernelTap


class Cell(PlanCell):
    def __init__(self, config: dict, traffic: dict):
        from repro import PlanSession
        self.config, self.traffic = config, traffic
        self.inst0, self.fields0 = make_instance(config)
        self.session = PlanSession(solver=traffic["solver"],
                                   engine=traffic["engine"])
        self.session.plan(instance=self.inst0)
        for w in range(int(traffic["warmup_calls"])):
            self.session.replan(instance=self.inst0.with_lam(
                self.forecast(w, InputPool.WARMUP)))
        self.after_warmup = self._state()
        self.tap = KernelTap()

    def _state(self) -> dict:
        s = self.session
        return {"lam": s.last_instance.lam, "result": s.last_result,
                "order": s.winning_order}

    def start(self, seed: int) -> None:
        super().start(seed)
        st = self.after_warmup
        self.session.seed(self.inst0.with_lam(st["lam"]), st["result"])
        self.session.winning_order = st["order"]

    def answer(self, lam):
        return self.session.replan(instance=self.inst0.with_lam(lam))

    def release(self) -> None:
        self.session = self.after_warmup = None
        super().release()
