"""Batched first-order Stage-2 LP solver (tentpole of the risk subsystem).

All S scenarios of a `ScenarioBatch` are solved against one frozen
deployment as ONE stacked tensor program in jax (f64, scenario axis
leading), with the scipy/HiGHS path as the exact oracle.  Three phases,
cheapest first:

1. **Anchor-basis warm start.**  Each scenario's LP is a one-factor
   rescale of the base LP, so optimal bases cluster into a small set
   (~30 distinct bases cover tens of thousands of scenarios of the
   evaluation family).  An *anchor* is an optimal basis harvested from
   one exact solve: (active rows, basic columns, nonbasic-at-upper-bound
   columns), completed to a square basis through pivoted Gram-Schmidt
   when the vertex is degenerate.  Each unresolved scenario tries its
   untried anchors in descending hits (ties by anchor index), so within
   a chunk every scenario left after a pass picks the same next anchor
   and each pass makes one candidate call.  The k x k active systems
   B(s) z_B = rhs_eff(s)  and  B(s)^T y = -c_B(s)  are solved EXACTLY
   in closed form by exploiting how scenarios perturb the constraint
   matrix: equality rows are scenario-constant, kv/compute/storage rows
   are pure per-row rescales (every entry of row i carries the same
   lam/tau factor), and only active delay/error rows change shape — of
   which an optimal basis holds a bounded number (q capped by the
   largest `_SHAPE_CLASSES` entry; anchors pad to the smallest fitting
   class so nominal deployments keep tiny q).  Writing
   B(s) = D(s) B0 + U dR(s) with D(s) the diagonal of row factors and
   U the q unit columns of the changed rows, Woodbury gives
   B(s)^{-1} = (I - G0 M(s)^{-1} dR(s)) B0^{-1} D(s)^{-1} with
   G0 = B0^{-1} U precomputed per anchor and M(s) = I_q + dR(s) G0 a
   tiny q x q system solved by a statically unrolled LU.  Everything is
   gathers and small dgemms — B(s) is never materialized and no batched
   LAPACK is invoked (XLA lowers those to serial per-element loops on
   CPU, which would dominate wall time),
   then *verifies* each candidate with the PDHG convergence criteria
   proper (primal feasibility < `TOL_PF`, relative duality gap <
   `TOL_GAP`, duals clipped to sign-validity before the gap is formed).
   A passing candidate IS PDHG converged at iteration 0 — the stopping
   rule, not the proposer, is the correctness authority.  Scenarios that
   no anchor explains trigger an exact solve of one representative whose
   basis joins the anchor set (adaptive harvesting).

2. **PDHG iterations.**  Scenarios left over once the anchor set stops
   growing run restarted PDHG from the best candidate: Ruiz
   equilibration, diagonal (Pock-Chambolle) preconditioning, primal
   weight omega adapted at restarts, restart-to-average, and the same
   duality-gap stopping rule.

3. **Exact fallback.**  Scenarios that fail to converge within the
   iteration budget fall back to the exact oracle and are *counted* in
   the diagnostics — never silently dropped.

What crosses back to the host: each candidate call reads back one packed
array, [S, 4 + n_fam] f64 (verdict, cost, violation count, unmet total
and per-family utilization of every scenario of the group), which the
kernel reduces from the candidate's primal and row values itself
(`_ChunkArrays._stats` is the definition).  The iterates never leave the
device: each scenario's best miss candidate lives in one chunk-resident
device array, [Scb, 1 + n + m] (score, primal, dual), that every
candidate call updates in place through one gather, and PDHG gathers its
warm starts from it.

The LP solved here is the relaxed Stage-2 protocol (u <= 1, always
feasible), matching `Stage2System.solve(u_cap=ones)` — the risk
statistics want the realized cost of every scenario, not a strict-cap
feasibility verdict.  Per-scenario objectives agree with the oracle to
rtol 1e-5 (in practice ~1e-14); pinned in tests/test_risk.py.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np
from scipy import sparse

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402  (after the x64 switch, deliberately)

from ..core.instance import ScenarioBatch  # noqa: E402
from ..core.stage2 import Stage2System  # noqa: E402
from ..spans import span  # noqa: E402
from .solver_exact import ExactChunkSolver, _ChunkArrays  # noqa: E402

# PDHG convergence criteria — the single correctness authority for every
# non-exact scenario (anchor candidates must pass the SAME test).
TOL_PF = 1e-8       # max primal constraint violation (unscaled rows)
TOL_GAP = 1e-7      # relative duality gap |p-d| / (1+|p|+|d|)

_RUIZ_ITERS = 10
# Woodbury shape classes (q, eg): q = max scenario-varying (delay/error)
# rows per anchor basis, eg = max matrix entries in those rows x basic
# columns.  `_pack` pads each anchor to the SMALLEST fitting class, so
# nominal deployments (q <= 2 in practice) keep the small fast shapes
# while stressed deployments (15-16 active delay/error rows) still get
# kernel-representable anchors instead of degenerating to per-scenario
# exact solves.  One jit specialization per class actually used.
_SHAPE_CLASSES = ((8, 64), (24, 192))
_S_BUCKETS = (256, 1024, 4096, 8192)


def _bucket(S: int) -> int:
    for b in _S_BUCKETS:
        if S <= b:
            return b
    return int(2 ** np.ceil(np.log2(S)))


# ---------------------------------------------------------------------------
# Candidate kernel: propose the anchor's vertex/dual for every scenario in
# the batch and verify it with the PDHG stopping rule.  One compile per
# (S bucket); every anchor reuses it (all anchor tensors are padded to the
# system-wide static sizes).
# ---------------------------------------------------------------------------

def _lu_small(M):
    """No-pivot LU (compact storage) on [S, q, q] blocks, unrolled.

    M = I_q + dR G0 is diagonally dominated for in-cell scenarios and
    exactly the identity on padding slots, so pivoting is unnecessary;
    a scenario whose M is ill-conditioned produces a garbage candidate
    that the verification stage rejects (exactness is never assumed).
    q is read off the array shape (static under jit), so each shape
    class gets its own unrolled specialization.
    """
    Q = M.shape[1]
    for j in range(Q - 1):
        f = M[:, j + 1:, j] / M[:, j, j][:, None]
        M = M.at[:, j + 1:, j].set(f)
        M = M.at[:, j + 1:, j + 1:].add(
            -f[:, :, None] * M[:, j:j + 1, j + 1:])
    return M


def _solve_small(Mlu, r):
    """Solve M h = r from the compact LU ([S, q] right-hand sides)."""
    Q = Mlu.shape[1]
    h = r
    for j in range(1, Q):
        h = h.at[:, j].add(-jnp.sum(Mlu[:, j, :j] * h[:, :j], axis=1))
    for j in reversed(range(Q)):
        h = h.at[:, j].add(-jnp.sum(Mlu[:, j, j + 1:] * h[:, j + 1:],
                                    axis=1))
        h = h.at[:, j].mul(1.0 / Mlu[:, j, j])
    return h


def _solve_small_t(Mlu, r):
    """Solve M^T g = r from the same compact LU (M^T = U^T L^T)."""
    Q = Mlu.shape[1]
    a = r
    for j in range(Q):
        if j:
            a = a.at[:, j].add(-jnp.sum(Mlu[:, :j, j] * a[:, :j], axis=1))
        a = a.at[:, j].mul(1.0 / Mlu[:, j, j])
    for j in reversed(range(Q - 1)):
        a = a.at[:, j].add(-jnp.sum(Mlu[:, j + 1:, j] * a[:, j + 1:],
                                    axis=1))
    return a


@functools.partial(jax.jit, donate_argnames=("best",))
def _candidate_kernel(vals_all, c_all, idx, rhs0, is_eq, rows_a, cols_a,
                      ub, Rm, Rn, u_col, fam_mask, rhs_safe, best,
                      e_r, m_r, M_r, rhs_act,
                      scale_e, scale_m, scale_mask,
                      e_g, dv0, jpos_g, rowq_g, Hq, Hk, P_M, Hg,
                      bas_idx, bas_mask, nb_vec, act_idx, act_mask,
                      B0inv, G0):
    """One anchor's candidates for a group of chunk rows, verified and
    summarized.

    `idx` [S + Scb] int32 is one upload of two index vectors: `pad`, the
    group's chunk rows padded to the bucket S by repeating the first, then
    `inv`, each chunk row's position in the group, or S where it is not
    in it.  Returns ``(score, summary, best, z, y, rowsv)``.  The host
    reads back `summary` alone, [S, 4 + n_fam] f64 with columns ok, p,
    viols, unmet, util[0..n_fam): the cost and the statistics
    `_ChunkArrays._stats` defines, computed here from `z` and `rowsv`
    (`u_col` marks the unmet columns, `fam_mask` [m, n_fam] the rows of
    each family, `rhs_safe` the clamped right-hand sides).  `best`
    [Scb, 1 + n + m], donated, holds each chunk row's best miss candidate
    so far: its score (inf while there is none), z, then y.  `score`,
    `z`, `y` and `rowsv` are outputs for the tests only.
    """
    # All index-space reductions here are (gather, one-hot matmul) pairs
    # rather than `.at[].add` scatters: XLA CPU lowers batched scatters
    # to a serial per-index loop (~ms per call at S=8192), while the
    # equivalent [S, E] @ [E, K] dgemm is what the whole kernel budget
    # rides on.  Rm/Rn are the system-wide one-hot row/col maps; the
    # anchor tensors are padded to static sizes with zero-weight tails.
    # The group gather (pad -> rows of the chunk-resident tensors) lives
    # INSIDE the jit: done outside, each gather pays ~ms of trace and
    # dispatch overhead per call.
    pad, inv = idx[:-vals_all.shape[0]], idx[-vals_all.shape[0]:]
    vals = vals_all[pad]
    c = c_all[pad]
    S = pad.shape[0]
    m = rhs0.shape[0]

    # Woodbury pieces (see module docstring): row factors D(s) for the
    # pure-rescale rows, entry deltas dv of the q shape-changing rows.
    w_r = vals[:, e_r] * m_r[None, :]
    rhs_eff = rhs_act[None, :] - w_r @ M_r
    c_b = jnp.take_along_axis(c, bas_idx[None, :], axis=1) * bas_mask[None, :]
    dinv = 1.0 / (scale_mask[None, :] * vals[:, scale_e] * scale_m[None, :]
                  + (1.0 - scale_mask)[None, :])
    dv = vals[:, e_g] - dv0[None, :]
    Q = Hq.shape[1]
    Mlu = _lu_small(jnp.eye(Q, dtype=vals.dtype)[None, :, :]
                    + (dv @ P_M).reshape(S, Q, Q))

    # Primal:  B z_B = rhs_eff.
    t = (rhs_eff * dinv) @ B0inv.T
    h = _solve_small(Mlu, (dv * t[:, jpos_g]) @ Hq)
    z_b = t - h @ G0.T
    # Dual:  B^T y_act = -c_B.
    w0 = ((-c_b) @ B0inv) * dinv
    g = _solve_small_t(Mlu, w0 @ Hg)
    w = w0 - (((dv * g[:, rowq_g]) @ Hk) @ B0inv) * dinv

    z = (z_b * bas_mask[None, :]) @ jax.nn.one_hot(
        bas_idx, c.shape[1], dtype=vals.dtype) + nb_vec[None, :]
    z = jnp.clip(z, 0.0, ub[None, :])
    y = (w * act_mask[None, :]) @ jax.nn.one_hot(
        act_idx, m, dtype=vals.dtype)
    y = jnp.where(is_eq[None, :], y, jnp.maximum(y, 0.0))

    # Verification = the PDHG convergence criteria on the candidate.
    rowsv = (vals * z[:, cols_a]) @ Rm
    viol = jnp.where(is_eq[None, :], jnp.abs(rowsv - rhs0[None, :]),
                     jnp.maximum(rowsv - rhs0[None, :], 0.0))
    pf = jnp.max(viol, axis=1)
    p = jnp.sum(c * z, axis=1)
    rc = c + (vals * y[:, rows_a]) @ Rn
    d = -jnp.sum(rhs0[None, :] * y, axis=1) + jnp.sum(
        jnp.minimum(rc * ub[None, :], 0.0), axis=1)
    gap = jnp.abs(p - d) / (1.0 + jnp.abs(p) + jnp.abs(d))
    pf = jnp.where(jnp.isfinite(pf), pf, jnp.inf)
    gap = jnp.where(jnp.isfinite(gap), gap, jnp.inf)
    ok = (pf < TOL_PF) & (gap < TOL_GAP)
    score = jnp.maximum(pf, gap)

    # Summary: what the host keeps of each scenario.
    u = jnp.where(u_col[None, :], jnp.clip(z, 0.0, 1.0), 0.0)
    viols = jnp.sum(u > 0.01, axis=1).astype(vals.dtype)
    unmet = jnp.sum(u, axis=1)
    ratio = rowsv / rhs_safe[None, :]
    util = jnp.max(jnp.where(fam_mask[None, :, :], ratio[:, :, None],
                             -jnp.inf), axis=1)
    util = jnp.where(jnp.any(fam_mask, axis=0)[None, :], util, 0.0)
    summary = jnp.concatenate(
        [ok.astype(vals.dtype)[:, None], p[:, None], viols[:, None],
         unmet[:, None], util], axis=1)

    # Warm starts for PDHG: one gather from the group, never a scatter,
    # of each row's candidate packed as [score (inf on hits), z, y].
    cand = jnp.concatenate([jnp.where(ok, jnp.inf, score)[:, None], z, y],
                           axis=1)[jnp.clip(inv, 0, S - 1)]
    better = (inv < S) & (cand[:, 0] < best[:, 0])
    best = jnp.where(better[:, None], cand, best)
    return score, summary, best, z, y, rowsv


# ---------------------------------------------------------------------------
# PDHG kernels (phase 2): per-scenario Ruiz scaling + preconditioned
# restarted iterations, all S scenarios in lockstep.
# ---------------------------------------------------------------------------

@jax.jit
def _pdhg_setup(vals, c, rhs0, rows_a, cols_a, ub, best, pad):
    S, nnz = vals.shape
    m = rhs0.shape[0]
    n = c.shape[1]
    # Warm start: each row's best candidate (`_candidate_kernel`), zeros
    # where no candidate has a finite score.
    w = best[pad]
    have = jnp.isfinite(w[:, :1])
    z0 = jnp.where(have, w[:, 1:1 + n], 0.0)
    y0 = jnp.where(have, w[:, 1 + n:], 0.0)
    vs = vals
    dr = jnp.ones((S, m), dtype=vals.dtype)
    dc = jnp.ones((S, n), dtype=vals.dtype)
    for _ in range(_RUIZ_ITERS):
        av = jnp.abs(vs)
        rmax = jnp.zeros((S, m), dtype=vals.dtype).at[:, rows_a].max(av)
        cmax = jnp.zeros((S, n), dtype=vals.dtype).at[:, cols_a].max(av)
        er = 1.0 / jnp.sqrt(jnp.maximum(rmax, 1e-12))
        ec = 1.0 / jnp.sqrt(jnp.maximum(cmax, 1e-12))
        vs = vs * er[:, rows_a] * ec[:, cols_a]
        dr = dr * er
        dc = dc * ec
    cs = c * dc
    rhss = rhs0[None, :] * dr
    ubs = ub[None, :] / dc
    av = jnp.abs(vs)
    sig0 = 1.0 / jnp.maximum(
        jnp.zeros((S, m), dtype=vals.dtype).at[:, rows_a].add(av), 1e-12)
    tau0 = 1.0 / jnp.maximum(
        jnp.zeros((S, n), dtype=vals.dtype).at[:, cols_a].add(av), 1e-12)
    omega = jnp.maximum(
        jnp.linalg.norm(cs, axis=1)
        / jnp.maximum(jnp.linalg.norm(rhss, axis=1), 1.0), 1e-4)
    z = jnp.clip(z0 / dc, 0.0, ubs)
    y = y0 * dr
    return vs, cs, rhss, ubs, sig0, tau0, omega, dr, dc, z, y


def _pdhg_residuals(vs, cs, rhss, ubs, dr, is_eq, rows_a, cols_a, Rm, Rn,
                    z, y):
    p = jnp.sum(cs * z, axis=1)
    kz = (vs * z[:, cols_a]) @ Rm
    r0 = kz - rhss
    pf = jnp.max(jnp.where(is_eq[None, :], jnp.abs(r0),
                           jnp.maximum(r0, 0.0)) / dr, axis=1)
    yc = jnp.where(is_eq[None, :], y, jnp.maximum(y, 0.0))
    rc = cs + (vs * yc[:, rows_a]) @ Rn
    d = -jnp.sum(rhss * yc, axis=1) + jnp.sum(
        jnp.minimum(rc * ubs, 0.0), axis=1)
    gap = jnp.abs(p - d) / (1.0 + jnp.abs(p) + jnp.abs(d))
    return p, pf, gap


@jax.jit
def _pdhg_block(vs, cs, rhss, ubs, sig0, tau0, is_eq, rows_a, cols_a,
                Rm, Rn, dr, omega, z, y, z_r, y_r, n_inner):
    """`n_inner` PDHG iterations + one restart/adaptation step."""
    tau = tau0 / omega[:, None]
    sig = sig0 * omega[:, None]

    def body(_, state):
        z, y, zs, ys = state
        kty = (vs * y[:, rows_a]) @ Rn
        zn = jnp.clip(z - tau * (cs + kty), 0.0, ubs)
        arg = 2.0 * zn - z
        kz = (vs * arg[:, cols_a]) @ Rm
        t = y + sig * (kz - rhss)
        yn = jnp.where(is_eq[None, :], t, jnp.maximum(t, 0.0))
        return zn, yn, zs + zn, ys + yn

    z, y, zs, ys = jax.lax.fori_loop(
        0, n_inner, body, (z, y, jnp.zeros_like(z), jnp.zeros_like(y)))
    cnt = n_inner.astype(vs.dtype)
    za, ya = zs / cnt, ys / cnt

    p, pf, gap = _pdhg_residuals(vs, cs, rhss, ubs, dr, is_eq,
                                 rows_a, cols_a, Rm, Rn, z, y)
    pa, pfa, gapa = _pdhg_residuals(vs, cs, rhss, ubs, dr, is_eq,
                                    rows_a, cols_a, Rm, Rn, za, ya)
    take_avg = jnp.maximum(pfa, gapa) < jnp.maximum(pf, gap)
    z = jnp.where(take_avg[:, None], za, z)
    y = jnp.where(take_avg[:, None], ya, y)
    p = jnp.where(take_avg, pa, p)
    pf = jnp.where(take_avg, pfa, pf)
    gap = jnp.where(take_avg, gapa, gap)

    dz = jnp.linalg.norm(z - z_r, axis=1)
    dy = jnp.linalg.norm(y - y_r, axis=1)
    can = (dz > 1e-12) & (dy > 1e-12)
    omega_new = jnp.exp(0.5 * jnp.log(jnp.where(can, dy / dz, 1.0))
                        + 0.5 * jnp.log(omega))
    omega = jnp.where(can, omega_new, omega)
    return z, y, omega, p, pf, gap


# ---------------------------------------------------------------------------
# Host-side anchors.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Anchor:
    act: np.ndarray            # active rows
    bas: np.ndarray            # basic columns (sorted; keying only)
    nb_ub: np.ndarray          # nonbasic columns at upper bound
    pack: tuple                # padded device tensors for _candidate_kernel
    hits: int = 0

    @property
    def key(self) -> tuple:
        return (tuple(self.act.tolist()), tuple(self.bas.tolist()))


class BatchedStage2Solver(ExactChunkSolver):
    """Solve `ScenarioBatch`es against one `Stage2System`, batched.

    Anchors persist across `solve_scenarios` calls, so later chunks of a
    large S resolve almost entirely at iteration 0.  Thread-compatible
    with the relaxed Stage-2 protocol only (u_cap is pinned to ones).
    The exact oracle, the pattern plumbing, and the statistics recorder
    come from `ExactChunkSolver`; scenarios settled on the device have
    their statistics computed by `_candidate_kernel` to the definition of
    `_ChunkArrays._stats` (pinned in tests/test_risk.py).
    """

    def __init__(self, system: Stage2System, *, max_anchors: int = 32,
                 pdhg_max_iter: int = 20000, pdhg_check: int = 50):
        super().__init__(system)
        self.max_anchors = max_anchors
        self.pdhg_max_iter = pdhg_max_iter
        self.pdhg_check = pdhg_check
        self.anchors: list[_Anchor] = []
        self._anchor_keys: set[tuple] = set()
        self.diagnostics = {
            "n_anchor0": 0, "n_harvest_exact": 0, "n_pdhg": 0,
            "n_fallback_exact": 0, "pdhg_iters_max": 0, "n_scenarios": 0,
            "upload_bytes": 0, "readback_bytes": 0, "candidate_calls": 0,
            "candidate_rows": 0, "candidate_rows_padded": 0,
        }
        # Static device-side pattern tensors, shared by every kernel call.
        f64 = jnp.float64
        self._d_rhs0 = jnp.asarray(self.rhs0, dtype=f64)
        self._d_is_eq = jnp.asarray(self.is_eq, dtype=jnp.bool_)
        self._d_rows = jnp.asarray(self.rows, dtype=jnp.int64)
        self._d_cols = jnp.asarray(self.cols, dtype=jnp.int64)
        self._d_ub = jnp.asarray(self.ub, dtype=f64)
        # System-wide one-hot accumulation maps (see _candidate_kernel:
        # matmul accumulation beats XLA CPU's serial scatter lowering).
        E = self.nnz_all
        Rm = np.zeros((E, self.m))
        Rm[np.arange(E), self.rows] = 1.0
        Rn = np.zeros((E, self.n))
        Rn[np.arange(E), self.cols] = 1.0
        self._d_Rm = jnp.asarray(Rm, dtype=f64)
        self._d_Rn = jnp.asarray(Rn, dtype=f64)
        # What the candidate kernel's summary reads (`_ChunkArrays._stats`):
        # the unmet columns, each family's inequality rows, and the
        # right-hand sides clamped away from zero.
        u_col = np.arange(self.n) >= self.nx
        fam_mask = np.zeros((self.m, self.n_fam), dtype=bool)
        fam_mask[np.arange(self.m_ub), system.row_family] = True
        self._d_u_col = jnp.asarray(u_col, dtype=jnp.bool_)
        self._d_fam_mask = jnp.asarray(fam_mask, dtype=jnp.bool_)
        self._d_rhs_safe = jnp.asarray(np.maximum(self.rhs0, 1e-12),
                                       dtype=f64)

    def _harvest_anchor(self, res, vals: np.ndarray) -> bool:
        """Extract an optimal basis from a linprog result; True if new."""
        n, nx, m_ub, I = self.n, self.nx, self.m_ub, self.I
        z = res.x
        y_ineq = -res.ineqlin.marginals
        resid = res.ineqlin.residual
        act = np.concatenate([
            np.where((np.abs(resid) < 1e-7) | (y_ineq > 1e-9))[0],
            m_ub + np.arange(I)])
        if act.size > n:
            # More active rows than columns: a square basis over the
            # column space cannot exist; trim to the rows with the
            # largest |dual| plus the equality block.
            strong = np.argsort(-np.abs(y_ineq[act[:-I]]))[:n - I]
            act = np.concatenate([act[:-I][strong], m_ub + np.arange(I)])
        at_lb = np.abs(z) < 1e-8
        at_ub = np.abs(z - self.ub) < 1e-8
        inside = ~(at_lb | at_ub)
        order = np.concatenate([
            np.where(inside)[0], np.where(at_ub)[0],
            np.where(at_lb & (np.arange(n) < nx))[0],
            np.where(at_lb & (np.arange(n) >= nx))[0]])
        Ad = sparse.coo_matrix((vals, (self.rows, self.cols)),
                               shape=(self.m, self.n)).toarray()
        W = Ad[np.ix_(act, order)].copy()
        k = act.size
        chosen: list[int] = []
        left = list(range(W.shape[1]))
        for _ in range(k):
            norms = np.linalg.norm(W[:, left], axis=0)
            good = np.where(norms > 1e-8)[0]
            if not good.size:
                return False
            j = left[good[0]]
            chosen.append(j)
            v = W[:, j] / np.linalg.norm(W[:, j])
            W -= np.outer(v, v @ W)
            left.remove(j)
        bas = np.sort(order[np.array(chosen)])
        nonbas = np.setdiff1d(np.arange(n), bas)
        nb_ub = nonbas[at_ub[nonbas]]
        key = (tuple(act.tolist()), tuple(bas.tolist()))
        if key in self._anchor_keys:
            return False
        pack = self._pack(act, bas, nb_ub, vals, Ad)
        if pack is None:                    # over the Woodbury budget
            return False
        self._anchor_keys.add(key)
        self.anchors.append(
            _Anchor(act=act, bas=bas, nb_ub=nb_ub, pack=pack))
        return True

    def _pack(self, act: np.ndarray, bas: np.ndarray, nb_ub: np.ndarray,
              vals: np.ndarray, Ad: np.ndarray) -> tuple | None:
        """Build an anchor's padded device tensors for `_candidate_kernel`.

        `vals`/`Ad` are the SOURCE scenario's entry values / dense matrix
        — the basis block (identity tail) is inverted once here and the
        kernel reconstructs every scenario's solve from it via Woodbury.
        Returns None when the basis exceeds every `_SHAPE_CLASSES`
        budget (shape-changing rows / their entry count): such an
        anchor is rejected and its scenarios take the PDHG/exact path.
        Otherwise pads to the smallest fitting (q, eg) class — the
        kernel jit-specializes per class, so small-q anchors never pay
        big-q shapes.
        """
        K, E = self.n, self.nnz_all
        k = act.size
        row_pos = np.full(self.m, -1)
        row_pos[act] = np.arange(k)
        col_pos = np.full(self.n, -1)
        col_pos[bas] = np.arange(k)
        in_nb = np.zeros(self.n, dtype=bool)
        in_nb[nb_ub] = True

        sel_r = np.where((row_pos[self.rows] >= 0) & in_nb[self.cols])[0]
        e_r = np.zeros(E, dtype=np.int64)
        i_r = np.zeros(E, dtype=np.int64)
        m_r = np.zeros(E)
        e_r[:sel_r.size] = sel_r
        i_r[:sel_r.size] = row_pos[self.rows[sel_r]]
        # ub == 1 everywhere in the relaxed protocol, so the nb_ub
        # contribution to rhs_eff is just the coefficient itself.
        m_r[:sel_r.size] = self.ub[self.cols[sel_r]]
        M_r = np.zeros((E, K))
        M_r[np.arange(E), i_r] = np.where(m_r != 0.0, 1.0, 0.0)

        # Row classification: eq rows are scenario-constant, kv/compute/
        # storage rows rescale as a whole (one factor per row), delay/
        # error rows genuinely change shape -> Woodbury slots.
        fam = self.system.row_family
        scale_e = np.zeros(K, dtype=np.int64)
        scale_m = np.zeros(K)
        scale_mask = np.zeros(K)
        gen_pos: list[int] = []
        for p, r in enumerate(act):
            if r >= self.m_ub:
                continue                    # equality row: constant
            if fam[r] >= 3:
                gen_pos.append(p)           # delay/error: shape-changing
                continue
            ee = np.where(self.rows == r)[0]
            rep = ee[np.argmax(np.abs(vals[ee]))]
            if abs(vals[rep]) < 1e-12:      # degenerate rescale source
                gen_pos.append(p)
                continue
            scale_e[p] = rep
            scale_m[p] = 1.0 / vals[rep]
            scale_mask[p] = 1.0
        gen_rows = act[np.array(gen_pos, dtype=np.int64)]
        slot = {int(r): a for a, r in enumerate(gen_rows)}
        sel_g = np.where(np.isin(self.rows, gen_rows)
                         & (col_pos[self.cols] >= 0))[0]
        cls = next((c for c in _SHAPE_CLASSES
                    if len(gen_pos) <= c[0] and sel_g.size <= c[1]), None)
        if cls is None:
            return None
        Q, EG = cls

        P0 = np.eye(K)
        P0[:k, :k] = Ad[np.ix_(act, bas)]
        B0inv = np.linalg.inv(P0)
        G0 = np.zeros((K, Q))
        Hg = np.zeros((K, Q))
        for a, p in enumerate(gen_pos):
            G0[:, a] = B0inv[:, p]
            Hg[p, a] = 1.0
        e_g = np.zeros(EG, dtype=np.int64)
        dv0 = np.zeros(EG)
        jpos_g = np.zeros(EG, dtype=np.int64)
        rowq_g = np.zeros(EG, dtype=np.int64)
        Hq = np.zeros((EG, Q))
        Hk = np.zeros((EG, K))
        P_M = np.zeros((EG, Q * Q))
        for t, e in enumerate(sel_g):
            e_g[t] = e
            dv0[t] = vals[e]
            jp = col_pos[self.cols[e]]
            a = slot[int(self.rows[e])]
            jpos_g[t] = jp
            rowq_g[t] = a
            Hq[t, a] = 1.0
            Hk[t, jp] = 1.0
            P_M[t, a * Q:(a + 1) * Q] = G0[jp, :]

        rhs_act = np.zeros(K)
        rhs_act[:k] = self.rhs0[act]
        bas_idx = np.zeros(K, dtype=np.int64)
        bas_idx[:k] = bas
        bas_mask = np.zeros(K)
        bas_mask[:k] = 1.0
        nb_vec = np.zeros(self.n)
        nb_vec[nb_ub] = self.ub[nb_ub]
        act_idx = np.zeros(K, dtype=np.int64)
        act_idx[:k] = act
        act_mask = np.zeros(K)
        act_mask[:k] = 1.0
        f64, i64 = jnp.float64, jnp.int64
        return (jnp.asarray(e_r, dtype=i64), jnp.asarray(m_r, dtype=f64),
                jnp.asarray(M_r, dtype=f64), jnp.asarray(rhs_act, dtype=f64),
                jnp.asarray(scale_e, dtype=i64),
                jnp.asarray(scale_m, dtype=f64),
                jnp.asarray(scale_mask, dtype=f64),
                jnp.asarray(e_g, dtype=i64), jnp.asarray(dv0, dtype=f64),
                jnp.asarray(jpos_g, dtype=i64),
                jnp.asarray(rowq_g, dtype=i64),
                jnp.asarray(Hq, dtype=f64), jnp.asarray(Hk, dtype=f64),
                jnp.asarray(P_M, dtype=f64), jnp.asarray(Hg, dtype=f64),
                jnp.asarray(bas_idx, dtype=i64),
                jnp.asarray(bas_mask, dtype=f64),
                jnp.asarray(nb_vec, dtype=f64),
                jnp.asarray(act_idx, dtype=i64),
                jnp.asarray(act_mask, dtype=f64),
                jnp.asarray(B0inv, dtype=f64), jnp.asarray(G0, dtype=f64))

    def _upload(self, a: np.ndarray, dtype) -> jax.Array:
        """One per-call host-to-device copy, counted."""
        self.diagnostics["upload_bytes"] += a.nbytes
        return jnp.asarray(a, dtype=dtype)

    def _fetch(self, a: jax.Array) -> np.ndarray:
        """One device-to-host read-back, counted."""
        host = np.asarray(a)
        self.diagnostics["readback_bytes"] += host.nbytes
        return host

    # -- the batched solve ----------------------------------------------
    def solve_scenarios(self, batch: ScenarioBatch) -> _ChunkArrays:
        system = self.system
        S = batch.S
        with span("stage2.coefficients"):
            vals, c = system.coefficient_batch(batch)
        out = _ChunkArrays(S, self.n_fam)
        diag = self.diagnostics
        diag["n_scenarios"] += S

        if not self.anchors:
            with span("stage2.coefficients"):
                v0, c0 = system.coefficient_batch(ScenarioBatch(S=1))
            res0 = self._exact(v0[0], c0[0])
            with span("stage2.record"):
                self._harvest_anchor(res0, v0[0])

        # One chunk-wide device residency (padded to a bucket so chunk
        # length doesn't multiply kernel compiles); per-group rows are
        # gathered on device, inside the kernel's jit.
        with span("stage2.upload"):
            Scb = _bucket(S)
            vals_p = np.zeros((Scb, vals.shape[1]))
            vals_p[:S] = vals
            c_p = np.zeros((Scb, c.shape[1]))
            c_p[:S] = c
            d_vals_all = self._upload(vals_p, jnp.float64)
            d_c_all = self._upload(c_p, jnp.float64)

        unresolved = np.arange(S)
        tried = np.zeros((S, 0), dtype=bool)
        # Each scenario's best candidate so far, for PDHG's warm start:
        # chunk-resident, updated inside every candidate call.
        best = jnp.full((Scb, 1 + self.n + self.m), jnp.inf,
                        dtype=jnp.float64)

        while unresolved.size:
            A = len(self.anchors)
            still: list[np.ndarray] = []
            with span("stage2.assign"):
                if A == 0:
                    # No kernel-representable anchor yet (every harvested
                    # basis tripped every _SHAPE_CLASSES cap): skip the
                    # anchor pass — the harvest/PDHG tail below sees
                    # everything exhausted and keeps making progress one
                    # exact solve (or one PDHG batch) at a time.
                    exhausted_idx = unresolved
                    live = pick = np.zeros(0, dtype=np.int64)
                else:
                    if tried.shape[1] < A:
                        tried = np.concatenate(
                            [tried, np.zeros((S, A - tried.shape[1]), bool)],
                            axis=1)
                    # Anchor ordering (heuristic only — never affects
                    # correctness): the most-hit untried anchor, the
                    # lowest index among equals (argmax takes the first).
                    hits = np.array([a.hits for a in self.anchors])
                    t_u = tried[unresolved]
                    pick = np.argmax(np.where(t_u, -1, hits[None, :]),
                                     axis=1)
                    exhausted = t_u.all(axis=1)
                    exhausted_idx = unresolved[exhausted]
                    live = unresolved[~exhausted]
                    pick = pick[~exhausted]

            for a_id in np.unique(pick):
                grp = live[pick == a_id]
                tried[grp, a_id] = True
                anchor = self.anchors[a_id]
                # Gather the group's rows and pad to a compile bucket —
                # the kernel only ever does work proportional to the
                # scenarios actually trying this anchor.
                Sg = grp.size
                Sb = _bucket(Sg)
                diag["candidate_calls"] += 1
                diag["candidate_rows"] += Sg
                diag["candidate_rows_padded"] += Sb
                with span("stage2.candidate.dispatch"):
                    # [pad | inv] (see _candidate_kernel), one upload.
                    idx = np.full(Sb + Scb, Sb, dtype=np.int32)
                    idx[:Sb] = grp[0]
                    idx[:Sg] = grp
                    idx[Sb + grp] = np.arange(Sg)
                    d_idx = self._upload(idx, jnp.int32)
                    _, summary, best, *_ = _candidate_kernel(
                        d_vals_all, d_c_all, d_idx,
                        self._d_rhs0, self._d_is_eq,
                        self._d_rows, self._d_cols, self._d_ub,
                        self._d_Rm, self._d_Rn, self._d_u_col,
                        self._d_fam_mask, self._d_rhs_safe, best,
                        *anchor.pack)
                    # Queued behind the program: the wait adds no round
                    # trip of its own before the fetch.
                    summary.copy_to_host_async()
                with span("stage2.candidate.wait"):
                    summary.block_until_ready()
                # One array per call: verdict, cost and statistics of
                # each scenario; misses keep their iterates on the device.
                with span("stage2.candidate.fetch"):
                    summ = self._fetch(summary)[:Sg]
                with span("stage2.record"):
                    ok_np = summ[:, 0] != 0.0
                    hit = grp[ok_np]
                    if hit.size:
                        anchor.hits += int(hit.size)
                        diag["n_anchor0"] += int(hit.size)
                        sh = summ[ok_np]
                        out.costs[hit] = sh[:, 1]
                        out.record_stats(hit, sh[:, 2], sh[:, 3], sh[:, 4:])
                    if not ok_np.all():
                        still.append(grp[~ok_np])

            leftovers = (np.concatenate(still) if still
                         else np.zeros(0, dtype=np.int64))
            if exhausted_idx.size:
                if len(self.anchors) < self.max_anchors:
                    # Harvest: exact-solve one representative; its basis
                    # joins the anchor set, the others retry against it.
                    s = int(exhausted_idx[0])
                    res = self._exact(vals[s], c[s])
                    diag["n_harvest_exact"] += 1
                    with span("stage2.record"):
                        self._record_exact(s, vals[s], c[s], res, out)
                        self._harvest_anchor(res, vals[s])
                    unresolved = np.concatenate(
                        [leftovers, exhausted_idx[1:]])
                    continue
                # Anchor space exhausted: hand the rest to PDHG.
                unresolved = np.zeros(0, dtype=np.int64)
                pdhg_idx = np.concatenate([leftovers, exhausted_idx])
                self._run_pdhg(pdhg_idx, vals, c, best, out)
                return out
            unresolved = leftovers

        return out

    def _run_pdhg(self, idx: np.ndarray, vals: np.ndarray, c: np.ndarray,
                  best: jax.Array, out: _ChunkArrays) -> None:
        """Phase 2 (restarted PDHG) + phase 3 (exact fallback).

        `best` holds the chunk's best candidates on the device
        (`_candidate_kernel`); the warm starts are gathered from it there.
        """
        diag = self.diagnostics
        if not idx.size:
            return
        with span("stage2.pdhg"):
            Sp = idx.size
            Sb = _bucket(Sp)
            pad = np.concatenate([idx, np.repeat(idx[:1], Sb - Sp)])
            f64 = jnp.float64
            d_vals = self._upload(vals[pad], f64)
            d_c = self._upload(c[pad], f64)
            d_pad = self._upload(pad, jnp.int64)
            (vs, cs, rhss, ubs, sig0, tau0, omega, dr, dc, z,
             y) = _pdhg_setup(d_vals, d_c, self._d_rhs0, self._d_rows,
                              self._d_cols, self._d_ub, best, d_pad)
            z_r, y_r = z, y
            n_inner = jnp.asarray(self.pdhg_check, dtype=jnp.int64)
            done = np.zeros(Sb, dtype=bool)
            p_done = np.zeros(Sb)
            z_done = np.zeros((Sb, self.n))
            it = 0
            while it < self.pdhg_max_iter:
                z, y, omega, p, pf, gap = _pdhg_block(
                    vs, cs, rhss, ubs, sig0, tau0, self._d_is_eq,
                    self._d_rows, self._d_cols, self._d_Rm, self._d_Rn,
                    dr, omega, z, y, z_r, y_r, n_inner)
                z_r, y_r = z, y
                it += self.pdhg_check
                conv = (pf < TOL_PF) & (gap < TOL_GAP)
                conv.copy_to_host_async()
                with span("stage2.pdhg.wait"):
                    conv.block_until_ready()
                with span("stage2.pdhg.fetch"):
                    ok = self._fetch(conv)
                new = ok & ~done
                if new.any():
                    with span("stage2.pdhg.fetch"):
                        p_np = self._fetch(p)
                        z_phys = self._fetch(z * dc)
                    p_done[new] = p_np[new]
                    z_done[new] = z_phys[new]
                    done |= new
                if done[:Sp].all():
                    break
            diag["pdhg_iters_max"] = max(diag["pdhg_iters_max"], it)
            conv = np.where(done[:Sp])[0]
            if conv.size:
                diag["n_pdhg"] += int(conv.size)
                sel = idx[conv]
                out.costs[sel] = p_done[conv]
                for j, s in zip(conv, sel, strict=True):
                    out.record_z(int(s), vals[s], z_done[j], self)
        fail = np.where(~done[:Sp])[0]
        for j in fail:
            s = int(idx[j])
            res = self._exact(vals[s], c[s])
            diag["n_fallback_exact"] += 1
            with span("stage2.record"):
                self._record_exact(s, vals[s], c[s], res, out)
