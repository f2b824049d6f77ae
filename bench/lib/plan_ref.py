"""Plain reference for a plan: its objective and its hard constraints.

Written from the paper's model (arXiv:2604.07472 §3, objective (8a) and
constraints (8b)-(8k)) over the raw instance fields alone: every derived
quantity (delays, resident KV, loads) is worked out here, so the check
shares no arithmetic with the planner it judges.  `dtype=np.float32`
computes the same in the precision below the one the planner states:
that is the control, which the objective comparison must reject.
"""
from __future__ import annotations

import numpy as np

KB_PER_GB = 1e6
SECONDS_PER_HOUR = 3600.0
NVLINK_GBPS = 750.0          # inter-stage activation link of the model
HOP_LATENCY_S = 5e-6         # fixed per-hop latency of that link
SSM_STATE_TOKENS = 64.0      # resident state of a model without KV cache


class PlanModel:
    """The instance's parameters and derived tensors, in one precision."""

    def __init__(self, fields: dict, dtype=np.float64):
        a = {k: np.asarray(v, dtype) for k, v in fields.items()
             if k in _ARRAY_FIELDS}
        self.dtype = dtype
        self.a = a
        n = np.array([n for n in fields["tp_degrees"]
                      for _ in fields["pp_depths"]], dtype)
        m = np.array([m for _ in fields["tp_degrees"]
                      for m in fields["pp_depths"]], dtype)
        self.nm = n * m                                          # [C]
        self.kv = np.asarray(fields.get("kv_applicable")
                             if fields.get("kv_applicable") is not None
                             else np.ones(len(a["B"]), bool), bool)
        tau = a["tau"]
        r = a["h"] + a["f"]
        self.r = r
        # Decode roofline delay per token at TP=1, and the inter-stage
        # activation transfer per token (paper §3.1 (6)-(7), §5.1).
        d_comp = (tau[:, None, None] * a["B"][None, :, None]
                  * a["nu"][None, None, :] / a["BW"][None, None, :])
        d_comm = (a["beta"] * dtype(8.0) / dtype(KB_PER_GB)
                  / dtype(NVLINK_GBPS) + dtype(HOP_LATENCY_S))   # [J]
        self.D = (d_comp[..., None] * r[:, None, None, None] / n
                  + m * d_comm[None, :, None, None]
                  * a["f"][:, None, None, None])                 # [I,J,K,C]
        self.t_res = (a["lam"][:, None, None] / dtype(SECONDS_PER_HOUR)
                      * a["f"][:, None, None] * d_comp)          # [I,J,K]
        self.e_bar = a["e_base"][:, :, None] * a["mu"][None, None, :]
        self.data_gb = a["theta"] / dtype(KB_PER_GB) * r * a["lam"]  # [I]
        self.T = dtype(fields.get("Delta_T", 24.0))
        self.eta = dtype(fields.get("eta", 0.9))
        self.p_s = dtype(fields["p_s"])
        self.delta = dtype(fields["delta"])
        self.C_s = dtype(fields["C_s"])

    def plan_arrays(self, sol) -> dict:
        return {k: np.asarray(getattr(sol, k), self.dtype)
                for k in ("x", "y", "q", "w", "z", "u")}

    def objective(self, sol) -> float:
        """Objective (8a): rental, model and data storage, delay and
        unmet penalties, in dollars over the planning horizon."""
        a, p = self.a, self.plan_arrays(sol)
        x = p["x"]
        rental = self.T * np.sum(a["p_c"][None, :] * p["y"])
        model_storage = self.T * self.p_s * np.sum(a["B"][None, :, None]
                                                   * p["z"])
        data_storage = self.T * self.p_s * np.sum(self.data_gb[:, None, None]
                                                  * x)
        delay = np.einsum("ijk,jkc,ijkc->i", x, p["w"], self.D)
        delay_pen = np.sum(a["rho"] * delay * self.dtype(1e3))
        unmet_pen = self.T * np.sum(a["phi"] * p["u"])
        total = (rental + model_storage + data_storage + delay_pen
                 + unmet_pen)
        return float(self.dtype(total))

    def violations(self, sol) -> dict[str, float]:
        """Largest violation of each hard-constraint family (8b)-(8k),
        in the constraint's own units; 0 where it holds."""
        a, p = self.a, self.plan_arrays(sol)
        x, y, q, w, z, u = (p[k] for k in ("x", "y", "q", "w", "z", "u"))
        pos = lambda v: max(0.0, float(np.max(v)))
        v = {"demand": float(np.max(np.abs(x.sum(axis=(1, 2)) + u - 1.0)))}
        spend = self.T * (np.sum(a["p_c"][None, :] * y)
                          + self.p_s * (np.sum(a["B"][None, :, None] * z)
                                        + np.sum(self.data_gb[:, None, None]
                                                 * x)))
        v["budget"] = pos(spend - self.delta)
        v["config"] = float(np.max(np.abs(w.sum(axis=2) - q)))
        nm_sel = np.einsum("jkc,c->jk", w, self.nm)
        v["gpus"] = float(np.max(np.abs(y - nm_sel)))
        # Per-device memory: weights plus resident KV (or SSM state) on
        # deployed pairs; any traffic routed to an undeployed pair.
        deployed = q > 0.5
        nm_d = np.maximum(nm_sel, 1.0)
        tokens = np.einsum("i,ijk,ijk->jk", self.r, self.t_res, x)
        gb_tok = a["beta"][:, None] / self.dtype(KB_PER_GB) / nm_d
        kv_gb = np.where(self.kv[:, None], gb_tok * tokens,
                         gb_tok * self.dtype(SSM_STATE_TOKENS))
        mem = (a["B"][:, None] * a["nu"][None, :] / nm_d + kv_gb
               - a["C_gpu"][None, :])
        ghost = x.sum(axis=0)
        v["memory"] = pos(np.where(deployed, mem, ghost))
        load = np.einsum("ijk,ijk->jk", x, a["B"][None, :, None]
                         * a["nu"][None, None, :]
                         * (self.r * a["lam"])[:, None, None] / 1e3)
        cap = self.eta * SECONDS_PER_HOUR * a["P_gpu"][None, :] * y
        v["compute"] = pos(load - cap)
        stor = (np.sum(a["B"][None, :, None] * z, axis=(1, 2))
                + np.sum(self.data_gb[:, None, None] * x, axis=(1, 2)))
        v["storage"] = pos(stor - self.C_s)
        delay = np.einsum("ijk,jkc,ijkc->i", x, w, self.D)
        v["delay"] = pos(delay - a["Delta"])
        v["error"] = pos(np.einsum("ijk,ijk->i", self.e_bar, x) - a["eps"])
        v["chain"] = max(pos(x - z), pos(z - q[None, :, :]))
        v["unmet_cap"] = pos(u - a["zeta"])
        v["bounds"] = max(pos(-x), pos(x - 1.0), pos(-u), pos(u - 1.0))
        return v


_ARRAY_FIELDS = frozenset({
    "lam", "h", "f", "theta", "B", "beta", "e_base", "C_gpu", "P_gpu",
    "p_c", "BW", "nu", "mu", "Delta", "eps", "rho", "phi", "zeta", "tau"})


def objective_gap(value: float, ref: float) -> float:
    """Relative gap of a reported objective from the reference's."""
    return abs(value - ref) / max(1.0, abs(ref))
