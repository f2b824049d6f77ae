"""Plain reference for the xla tier's two device programs.

The planner's xla tier computes two things on the device for its host
driver: the M2 ranking keys of GH Phase 2 (`kap0`, `kap1` per lane and
(model, tier) cell) and the relocate screen's verdict per source ("could
any destination improve?").  The host driver keeps every plan feasible
whatever the device returns, so a wrong key or verdict gives another
feasible plan: only a check of the device's own output sees it.

This module recomputes both from a call's inputs (the driver's state:
lane types, GPU counts, residuals, active-cell overrides, source rows)
and from grids it builds itself from the raw instance fields, by the
paper's mechanisms (arXiv:2604.07472 §4: M1, the TP-aware feasibility
selection; M2, the cost-per-effective-coverage ranking).  It imports
nothing of the program.  `dtype=np.float32` computes the same in the
precision below the planner's float64: the control.

Rounding can flip a threshold decision only where the reference's own
margin to the threshold is within `MARGIN` (relative); such cells and
sources are excused, every other disagreement counts.
"""
from __future__ import annotations

import numpy as np

from .plan_ref import SECONDS_PER_HOUR, PlanModel

MARGIN = 1e-10          # relative margin inside which rounding may flip
LIVE_MIN = 1e-9         # smallest routable share (x-bar) of a key
FULL_TOL = 1e-9         # x-bar this close to the residual absorbs it all
DELAY_FLOOR = 1e-12
ERROR_FLOOR = 1e-12
LPX_FLOOR = 1e-18


class XlaTables:
    """Flat [I, J*K] grids of one instance, built from its raw fields:
    the M1 winner per (type, model, tier) and the cost pieces of M2 and
    of the relocate screen's destination rows."""

    def __init__(self, fields: dict, dtype=np.float64):
        pm = PlanModel(fields, dtype)
        a = pm.a
        I, J, K, C = pm.D.shape
        self.dtype = dtype
        self.T = pm.T
        self.JK = J * K
        # M1: the (n*m, delay, index)-smallest config that fits per-device
        # memory and the type's delay SLO.
        per_dev = (a["B"][:, None] * a["nu"][None, :])[:, :, None] / pm.nm
        fits = per_dev <= a["C_gpu"][None, :, None]               # [J,K,C]
        if fields.get("avail_gpus") is not None:
            avail = np.asarray(fields["avail_gpus"], dtype)
            fits = fits & (pm.nm[None, None, :] <= avail[None, :, None])
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
        # M2 cost pieces: storage of model and data, routed-delay penalty.
        B_jk = np.repeat(a["B"], K)
        self.pc = np.tile(a["p_c"], J)
        self.storage = pm.p_s * (B_jk[None, :] + pm.data_gb[:, None])
        self.delay_cost = a["rho"][:, None] * self.m1_delay * dtype(1e3)
        # Screen destination rows: rental of a new pair at its M1 winner,
        # model storage, compute capacity per GPU, load per unit share.
        self.rental = self.pc[None, :] * self.m1_nm
        self.model_storage = pm.p_s * B_jk
        self.comp_per_gpu = np.tile(pm.eta * dtype(SECONDS_PER_HOUR)
                                    * a["P_gpu"], J)
        self.load_per_x = flat(a["B"][None, :, None] * a["nu"][None, None, :]
                               * (pm.r * a["lam"])[:, None, None]
                               / dtype(1e3))


def _cast(args: dict, dtype) -> dict:
    return {k: (v.astype(dtype) if np.issubdtype(v.dtype, np.floating)
                else v) for k, v in args.items()}


def _overlay(base: np.ndarray, cols: np.ndarray, vals: np.ndarray):
    """`base` with row r's columns `cols[r]` set to `vals[r]`; a column
    index equal to the width is padding and writes nothing."""
    out = np.concatenate([base, np.zeros((base.shape[0], 1), base.dtype)],
                         axis=1)
    out[np.arange(base.shape[0])[:, None], cols] = vals
    return out[:, :-1]


def phase2_keys(t: XlaTables, args: dict):
    """M2 keys of one lockstep step: (kap0, kap1, ambiguous).

    kap0 holds the cost per routable share of cells that absorb the
    lane's whole residual, kap1 of those that take part of it; inf where
    a cell is not a candidate.  `ambiguous` marks cells whose class a
    rounding of x-bar could flip."""
    g = _cast(args, t.dtype)
    i = g["i_idx"]
    inc = np.maximum(0.0, t.m1_nm[i] - g["y"])
    cost = t.T * (t.pc[None, :] * inc + t.storage[i]) + t.delay_cost[i]
    cost = _overlay(cost, g["act_jk"], g["act_cost"])
    d = _overlay(t.m1_delay[i], g["act_jk"], g["act_d"])
    valid = _overlay(t.m1_valid[i], g["act_jk"], g["act_valid"])
    rr = g["rr"][:, None]
    err_cap = (t.eps[i] - g["E"])[:, None] / t.e_floor[i]
    del_cap = (t.Delta[i] - g["D"])[:, None] / np.maximum(d, DELAY_FLOOR)
    xbar = np.minimum(np.minimum(rr, err_cap), del_cap)
    live = xbar > LIVE_MIN
    part = xbar < rr - FULL_TOL
    with np.errstate(divide="ignore", invalid="ignore"):
        kappa = np.where(live, cost / np.where(live, xbar, 1.0), np.inf)
    cand = valid & live
    kap0 = np.where(cand & ~part, kappa, np.inf)
    kap1 = np.where(cand & part, kappa, np.inf)
    ambiguous = valid & (
        (np.abs(xbar - LIVE_MIN) <= MARGIN * np.maximum(np.abs(xbar),
                                                        LIVE_MIN))
        | (np.abs(xbar - (rr - FULL_TOL))
           <= MARGIN * np.maximum(np.abs(xbar), np.abs(rr))))
    return kap0, kap1, ambiguous


def _classes(kap0, kap1):
    return np.where(np.isfinite(kap0), 0, np.where(np.isfinite(kap1), 1, 2))


def keys_gap(ref, kap0, kap1) -> float:
    """Widest relative gap between the device's keys and the reference's
    (`ref` from `phase2_keys` in float64).  A cell in another class than
    the reference puts it (a candidate missed or invented, or moved
    between kap0 and kap1) reads 1, unless its class is ambiguous."""
    r0, r1, amb = ref
    kap0 = np.asarray(kap0, np.float64)
    kap1 = np.asarray(kap1, np.float64)
    rc, dc = _classes(r0, r1), _classes(kap0, kap1)
    if np.any((rc != dc) & ~amb):
        return 1.0
    same = (rc == dc) & (rc < 2)
    if not same.any():
        return 0.0
    rk = np.where(rc == 0, r0, r1)[same]
    dk = np.where(dc == 0, kap0, kap1)[same]
    return float(np.max(np.abs(dk - rk) / np.abs(rk)))


def screen(t: XlaTables, args: dict, margin: float = 0.0) -> np.ndarray:
    """The relocate screen's verdict per source row: may any destination
    improve?  `margin` > 0 relaxes both thresholds by that relative
    amount, < 0 tightens them."""
    g = _cast(args, t.dtype)
    gi, s_g = g["g_i"], g["s_g"]
    d_sel = _overlay(t.m1_delay[gi], g["act_jk"], g["act_d"])
    ok = _overlay(t.m1_valid[gi], g["act_jk"], g["act_ok"])
    rent = _overlay(t.rental[gi], g["act_jk"], np.zeros_like(g["act_d"]))
    nm = _overlay(t.m1_nm[gi], g["act_jk"], g["act_nm"])
    dcost = t.T * (rent + np.where(g["z_lt"], 0.0,
                                   t.model_storage[None, :]))
    room = t.comp_per_gpu[None, :] * nm - g["load"][g["g_lane"]]
    ds = d_sel[s_g]
    delta = dcost[s_g] + g["dyn"][:, None] * ds
    bound = g["bound"][:, None]
    si = gi[s_g]
    ub = np.minimum(g["rr2"][:, None], g["err_num"][:, None] / t.e_floor[si])
    ub = np.minimum(ub, g["del_num"][:, None]
                    / np.maximum(ds, DELAY_FLOOR))
    lpx = t.load_per_x[si]
    has = lpx > LPX_FLOOR
    ub = np.where(has, np.minimum(ub, room[s_g] / np.where(has, lpx, 1.0)),
                  ub)
    fthr = g["fthr"][:, None]
    with np.errstate(invalid="ignore"):
        slack_b = margin * (np.abs(delta) + np.abs(bound))
        slack_f = margin * (np.abs(ub) + np.abs(fthr))
        cand = ok[s_g] & (delta < np.where(np.isfinite(bound),
                                           bound + slack_b, bound))
    cand[np.arange(len(s_g)), np.minimum(g["s_jk"], t.JK - 1)] &= (
        g["s_jk"] >= t.JK)
    alive = cand & (ub >= fthr - slack_f)
    return np.any(alive, axis=1)


def screen_mismatch(t: XlaTables, args: dict, verdict) -> int:
    """Sources whose device verdict the reference contradicts beyond
    rounding: pruned though alive under tightened thresholds, or kept
    though dead under relaxed ones."""
    verdict = np.asarray(verdict, bool)
    alive_lo = screen(t, args, -MARGIN)
    alive_hi = screen(t, args, MARGIN)
    return int(np.sum(verdict & ~alive_hi) + np.sum(~verdict & alive_lo))
