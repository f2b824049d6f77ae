"""Plain reference for the xla tier's device programs on model rows that
read fewer weights per token than they hold (sparse experts).

`bench.lib.xla_ref`'s grids with M1's delay half, the routed-delay cost
and the screen's load per unit share from `B_act` (through
`plan_ref_split.SplitPlanModel`), and M1's memory half and the storage
costs from the resident `B`.  The programs' keys and verdicts are then
recomputed by `xla_ref.phase2_keys` and `xla_ref.screen` on these grids.
It imports nothing of the program.
"""
from __future__ import annotations

import numpy as np

from .plan_ref import SECONDS_PER_HOUR
from .plan_ref_split import SplitPlanModel
from .xla_ref import ERROR_FLOOR, XlaTables


class SplitXlaTables(XlaTables):
    """`xla_ref.XlaTables`, built from `SplitPlanModel`."""

    def __init__(self, fields: dict, dtype=np.float64):
        pm = SplitPlanModel(fields, dtype)
        a = pm.a
        I, J, K, C = pm.D.shape
        self.dtype = dtype
        self.T = pm.T
        self.JK = J * K
        # M1: the (n*m, delay, index)-smallest config whose resident
        # weights fit per device and whose delay meets the type's SLO.
        per_dev = (a["B"][:, None] * a["nu"][None, :])[:, :, None] / pm.nm
        fits = per_dev <= a["C_gpu"][None, :, None]               # [J,K,C]
        feas = fits[None] & (pm.D <= a["Delta"][:, None, None, None])
        nm_m = np.where(feas, pm.nm, np.inf)
        nm_min = nm_m.min(axis=3)
        tie = feas & (nm_m == nm_min[..., None])
        d_m = np.where(tie, pm.D, np.inf)
        first = tie & (d_m == d_m.min(axis=3)[..., None])
        ok = np.isfinite(nm_min)
        win = np.where(ok, first.argmax(axis=3), 0)
        delay = np.take_along_axis(pm.D, win[..., None], axis=3)[..., 0]
        flat = lambda g: g.reshape(I, J * K)
        self.m1_valid = flat(ok)
        self.m1_nm = flat(np.where(ok, nm_min, 0.0).astype(dtype))
        self.m1_delay = flat(delay)
        self.e_floor = flat(np.maximum(pm.e_bar, dtype(ERROR_FLOOR)))
        self.eps = a["eps"]
        self.Delta = a["Delta"]
        B_jk = np.repeat(a["B"], K)
        self.pc = np.tile(a["p_c"], J)
        self.storage = pm.p_s * (B_jk[None, :] + pm.data_gb[:, None])
        self.delay_cost = a["rho"][:, None] * self.m1_delay * dtype(1e3)
        self.rental = self.pc[None, :] * self.m1_nm
        self.model_storage = pm.p_s * B_jk
        self.comp_per_gpu = np.tile(pm.eta * dtype(SECONDS_PER_HOUR)
                                    * a["P_gpu"], J)
        self.load_per_x = flat(pm.B_act[None, :, None]
                               * a["nu"][None, None, :]
                               * (pm.r * a["lam"])[:, None, None]
                               / dtype(1e3))
