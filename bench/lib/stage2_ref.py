"""Plain reference for a risk report: each scenario's Stage-2 LP, solved
exactly.

The risk protocol (arXiv:2604.07472 §5.2, relaxed Stage 2): the
deployment (y, q, w, z) is frozen; per scenario, routing x over the
admitted (type, model, tier) triples and unmet shares u in [0, 1] are
re-optimized under the scenario's realized overhead tau, base error
e_base and arrival rate lam.  The scenario stream is the protocol's own:
per scenario, in this order, tau factors U(0, d_infl) per type, e_base
factors U(0, e_infl) per (type, model), lam factors U(-lam_pm, lam_pm)
per type, all from one generator seeded with the report's seed.

The LP is built here from the raw instance fields, in dense rows, and
solved by HiGHS through `scipy.optimize.linprog`; nothing of the planner
is imported.  `dtype=np.float32` builds the LP and prices the solution
in the precision below the float64 the risk engine states: the control.
"""
from __future__ import annotations

import numpy as np
from scipy import sparse
from scipy.optimize import linprog

from .plan_ref import (HOP_LATENCY_S, KB_PER_GB, NVLINK_GBPS,
                       SECONDS_PER_HOUR)


def scenario_draws(fields: dict, seed: int, S: int, family: dict,
                   keep: np.ndarray) -> dict[str, np.ndarray]:
    """tau, e_base and lam of scenarios `keep` (sorted indices < S)."""
    I, J = np.shape(fields["e_base"])
    per = 2 * I + I * J
    rng = np.random.default_rng(seed)
    out = {"tau": [], "e_base": [], "lam": []}
    step = 4096                        # scenarios drawn per block
    keep = np.asarray(keep)
    for s0 in range(0, S, step):
        n = min(step, S - s0)
        u = rng.random(n * per).reshape(n, per)
        sel = keep[(keep >= s0) & (keep < s0 + n)] - s0
        if not sel.size:
            continue
        u = u[sel]
        d, e, lp = family["d_infl"], family["e_infl"], family["lam_pm"]
        out["tau"].append(fields["tau"] * (1.0 + (0.0 + d * u[:, :I])))
        out["e_base"].append(fields["e_base"] * (
            1.0 + (0.0 + e * u[:, I:I + I * J].reshape(-1, I, J))))
        out["lam"].append(fields["lam"] * (
            1.0 + (-lp + (lp - -lp) * u[:, I + I * J:])))
    return {k: np.concatenate(v) for k, v in out.items()}


class Stage2Reference:
    """The relaxed Stage-2 LP of one frozen deployment."""

    def __init__(self, fields: dict, deploy, dtype=np.float64):
        self.dtype = dtype
        self.f = fields
        q = np.asarray(deploy.q) > 0.5
        z = np.asarray(deploy.z) > 0.5
        w = np.asarray(deploy.w)
        self.y = np.asarray(deploy.y, float)
        pj, pk = np.nonzero(q)
        configs = [(n, m) for n in fields["tp_degrees"]
                   for m in fields["pp_depths"]]
        cfg = w[pj, pk].argmax(axis=1)
        self.pairs = list(zip(pj.tolist(), pk.tolist(), strict=True))
        self.n_cfg = np.array([configs[c][0] for c in cfg], float)
        self.m_cfg = np.array([configs[c][1] for c in cfg], float)
        I = len(fields["lam"])
        # Admitted triples (i, pair p): z and q both set.
        ti, tp = np.nonzero(z[:, pj, pk]) if pj.size else (
            np.zeros(0, int), np.zeros(0, int))
        self.ti, self.tp = ti, tp
        self.tj, self.tk = pj[tp], pk[tp]
        self.I = I
        self.nx = ti.size
        self.model_gb = np.sum(np.asarray(fields["B"])[None, :, None] * z,
                               axis=(1, 2))

    def _lp(self, tau, e_base, lam):
        """(c, A_ub, b_ub, A_eq) of one scenario, in `self.dtype`."""
        dt = self.dtype
        f = {k: np.asarray(v, dt) if isinstance(v, np.ndarray) else v
             for k, v in self.f.items()}
        tau, e_base, lam = (np.asarray(v, dt) for v in (tau, e_base, lam))
        ti, tj, tk, tp = self.ti, self.tj, self.tk, self.tp
        I, nx = self.I, self.nx
        nm = np.asarray(self.n_cfg * self.m_cfg, dt)
        n_t = np.asarray(self.n_cfg, dt)[tp]
        m_t = np.asarray(self.m_cfg, dt)[tp]
        r = f["h"] + f["f"]
        d_comp = tau[ti] * f["B"][tj] * f["nu"][tk] / f["BW"][tk]
        d_comm = (f["beta"][tj] * dt(8.0) / dt(KB_PER_GB) / dt(NVLINK_GBPS)
                  + dt(HOP_LATENCY_S))
        D = d_comp * r[ti] / n_t + m_t * d_comm * f["f"][ti]
        data = f["theta"][ti] / dt(KB_PER_GB) * r[ti] * lam[ti]
        c = np.concatenate([
            dt(24.0) * dt(f["p_s"]) * data + f["rho"][ti] * dt(1e3) * D,
            dt(24.0) * f["phi"]])
        rows, cols, vals, rhs = [], [], [], []
        col = np.arange(nx)
        kv = np.asarray(self.f.get("kv_applicable")
                        if self.f.get("kv_applicable") is not None
                        else np.ones(len(f["B"]), bool), bool)

        def add(mask_rows, coef, b):
            # one row per distinct key in mask_rows (a [nx] key array,
            # -1 = not in any row)
            keys = np.unique(mask_rows[mask_rows >= 0])
            base = len(rhs)
            pos = {int(kk): base + a for a, kk in enumerate(keys)}
            sel = mask_rows >= 0
            rows.append(np.array([pos[int(kk)] for kk in mask_rows[sel]],
                                 int))
            cols.append(col[sel])
            vals.append(coef[sel])
            rhs.extend(b(int(kk)) for kk in keys)

        # Resident KV per device on each deployed KV-cache pair.
        t_res = lam[ti] / dt(SECONDS_PER_HOUR) * f["f"][ti] * d_comp
        kv_coef = f["beta"][tj] / dt(KB_PER_GB) / nm[tp] * r[ti] * t_res
        add(np.where(kv[tj], tp, -1), kv_coef,
            lambda p: (f["C_gpu"][self.pairs[p][1]]
                       - f["B"][self.pairs[p][0]] * f["nu"][self.pairs[p][1]]
                       / nm[p]))
        # Compute throughput per deployed pair.
        add(tp.copy(), f["B"][tj] * f["nu"][tk] * r[ti] * lam[ti] / dt(1e3),
            lambda p: (dt(self.f.get("eta", 0.9)) * dt(SECONDS_PER_HOUR)
                       * f["P_gpu"][self.pairs[p][1]]
                       * dt(self.y[self.pairs[p]])))
        # Storage, delay and error SLO per query type.
        add(ti.copy(), data, lambda i: dt(f["C_s"]) - dt(self.model_gb[i]))
        add(ti.copy(), D, lambda i: f["Delta"][i])
        add(ti.copy(), f["mu"][tk] * e_base[ti, tj], lambda i: f["eps"][i])
        m_ub = len(rhs)
        A_ub = sparse.csr_matrix(
            (np.concatenate(vals).astype(float),
             (np.concatenate(rows), np.concatenate(cols))),
            shape=(m_ub, nx + I))
        A_eq = sparse.csr_matrix(
            (np.ones(nx + I), (np.concatenate([ti, np.arange(I)]),
                               np.concatenate([col, nx + np.arange(I)]))),
            shape=(I, nx + I))
        return c, A_ub, np.asarray(rhs, float), A_eq

    def cost(self, tau, e_base, lam) -> float:
        """The scenario's optimal Stage-2 cost, priced in `self.dtype`."""
        c, A_ub, b_ub, A_eq = self._lp(tau, e_base, lam)
        res = linprog(c.astype(float), A_ub=A_ub, b_ub=b_ub, A_eq=A_eq,
                      b_eq=np.ones(self.I), bounds=(0.0, 1.0),
                      method="highs")
        if res.status != 0:
            raise RuntimeError(f"reference LP failed: {res.message}")
        xu = res.x.astype(self.dtype)
        xu[self.nx:] = np.clip(xu[self.nx:], 0.0, 1.0)
        return float(np.dot(c, xu))


def cost_gap(value: float, ref: float) -> float:
    """Relative gap of a reported scenario cost from the reference's."""
    return abs(value - ref) / max(1.0, abs(ref))
