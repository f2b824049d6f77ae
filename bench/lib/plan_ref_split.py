"""Plain reference for a plan whose model rows read fewer weights per token
than they hold (sparse experts): `bench.lib.plan_ref` with the two
per-token terms of the paper's model taken from `B_act` and every memory
and storage term from the resident `B`.

- delay (8i) and the resident-KV time T_res: d_comp = tau * B_act * nu / BW;
- compute load (8g): B_act * nu * r * lam / 1e3 GFLOP per unit share;
- weights per device (8f), model storage (8a, 8c, 8h): B.

Without `B_act` in the fields it is `plan_ref.PlanModel` exactly.  It
imports nothing of the program.
"""
from __future__ import annotations

import numpy as np

from .plan_ref import SECONDS_PER_HOUR, PlanModel


class SplitPlanModel(PlanModel):
    def __init__(self, fields: dict, dtype=np.float64):
        B = fields["B"]
        B_act = fields.get("B_act")
        B_act = B if B_act is None else B_act
        # The parent works out d_comp (and from it D and T_res) from the
        # fields' B: hand it the bytes a token reads, then put the
        # resident weights back for memory and storage.
        super().__init__(dict(fields, B=B_act), dtype)
        self.B_act = self.a["B"]
        self.a["B"] = np.asarray(B, dtype)

    def violations(self, sol) -> dict[str, float]:
        v = super().violations(sol)
        a, p = self.a, self.plan_arrays(sol)
        load = np.einsum("ijk,ijk->jk", p["x"], self.B_act[None, :, None]
                         * a["nu"][None, None, :]
                         * (self.r * a["lam"])[:, None, None] / 1e3)
        cap = self.eta * SECONDS_PER_HOUR * a["P_gpu"][None, :] * p["y"]
        v["compute"] = max(0.0, float(np.max(load - cap)))
        return v
