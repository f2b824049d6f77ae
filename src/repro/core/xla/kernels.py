"""Jitted XLA kernels of the allocator engine, with host-side padding.

Two device programs cover the engine's hot numeric loops:

* `phase2_keys` — the batched M2 ranking keys of GH Phase 2 for every
  multi-start lane at once (`rank_keys_all` over a lane axis): one call
  per lockstep step computes the (pi, kappa) argmin-walk inputs of all
  orderings, each lane at its own current type.  Active cells arrive as
  host-computed override values (exact numpy arithmetic) scattered over
  the resident M1 grids.

* `screen_sources` — the relocate screen: for a stacked batch of
  (lane, source-cell) rows, reproduce `score_moves_batch`'s improvement
  filter and cap-upper-bound prefilter against each lane's sweep-start
  state and reduce to one boolean per source ("could any destination
  improve?").  Sources that fail are provably non-improving (the caller
  adds slack to the thresholds so XLA fusion ulps can never flip a
  verdict from pass to fail); sources that pass get the exact numpy
  scan.

Shapes are padded to a small set of bucket sizes so jit retraces stay
bounded: scatter indices are padded with the one-past-the-end column
trick (a dummy column is appended, written, then sliced off), and padded
sources carry ``bound = -inf`` so they can never report alive.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ...spans import span
from .tensors import XlaInstanceTensors


def _bucket(n: int, steps: tuple[int, ...], cap: int) -> int:
    """Smallest padded size >= n from `steps` (clamped to `cap`)."""
    for s in steps:
        s = min(s, cap)
        if n <= s:
            return s
    return cap


def _pad2(rows: list[np.ndarray], n_rows: int, n_cols: int, fill,
          dtype) -> np.ndarray:
    out = np.full((n_rows, n_cols), fill, dtype=dtype)
    for r, a in enumerate(rows):
        out[r, : a.shape[0]] = a
    return out


def _count(counters: dict | None, key: str, n: int) -> None:
    if counters is not None:
        counters[key] = counters.get(key, 0) + n


# ---------------------------------------------------------------------------
# Phase-2 ranking keys (rank_keys_all over a lane axis)
# ---------------------------------------------------------------------------

@jax.jit
def _phase2_keys_jit(m1_nm, psb_data, rho_d, m1_delay, m1_valid, ebf,
                     pc_flat, eps, Delta, Delta_T, i_idx, y, rr, E, D,
                     act_jk, act_cost, act_d, act_valid):
    R, JK = y.shape
    rows = jnp.arange(R, dtype=jnp.int64)[:, None]

    def scat(base, vals):
        p = jnp.concatenate([base, jnp.zeros((R, 1), base.dtype)], axis=1)
        return p.at[rows, act_jk].set(vals)[:, :JK]

    # Cost/delay/validity grids: M1 rows gathered at each lane's current
    # type, the lane's active cells overridden with the host's exact
    # per-cell values (post-M3 configs, pair-config delays).
    inc = jnp.maximum(0.0, m1_nm[i_idx] - y)
    cost = (Delta_T * (pc_flat[None, :] * inc + psb_data[i_idx])
            + rho_d[i_idx])
    d = scat(m1_delay[i_idx], act_d)
    valid = scat(m1_valid[i_idx], act_valid)
    cost = scat(cost, act_cost)
    # x-bar = min(r_rem, error headroom, delay headroom); keys as in
    # rank_keys_all: pi=0 iff the pair absorbs the full residual.
    err_cap = (eps[i_idx] - E)[:, None] / ebf[i_idx]
    del_cap = (Delta[i_idx] - D)[:, None] / jnp.maximum(d, 1e-12)
    xbar = jnp.minimum(jnp.minimum(rr[:, None], err_cap), del_cap)
    live = xbar > 1e-9
    valid = valid & live
    pi = xbar < rr[:, None] - 1e-9
    kappa = jnp.where(live, cost / jnp.where(live, xbar, 1.0), jnp.inf)
    kap0 = jnp.where(valid & ~pi, kappa, jnp.inf)
    kap1 = jnp.where(valid & pi, kappa, jnp.inf)
    return kap0, kap1


_ACT_STEPS = (64, 512, 4096)


def phase2_keys(tx: XlaInstanceTensors, items: list[tuple],
                counters: dict | None = None
                ) -> tuple[np.ndarray, np.ndarray]:
    """Ranking keys for one lockstep step over a chunk of lanes.

    ``items`` holds one tuple per lane:
    ``(i, y_flat, rr, E, D, act_jk, act_cost, act_d, act_valid)`` —
    the lane's current type, its flat GPU-count grid, the type-local
    scalars, and the active-cell override vectors.  Returns writable
    numpy ``(kap0, kap1)`` of shape [len(items), J*K] ready for
    `_phase2_walk`'s destructive visited-masking.
    """
    JK = tx.JK
    R = len(items)
    with span("xla.phase2.pack"):
        a_max = max((it[5].shape[0] for it in items), default=0)
        A = _bucket(max(a_max, 1), _ACT_STEPS, JK)
        i_idx = np.fromiter((it[0] for it in items), np.int64, R)
        y = np.stack([it[1] for it in items])
        rr = np.fromiter((it[2] for it in items), np.float64, R)
        E = np.fromiter((it[3] for it in items), np.float64, R)
        D = np.fromiter((it[4] for it in items), np.float64, R)
        act_jk = _pad2([it[5] for it in items], R, A, JK, np.int64)
        act_cost = _pad2([it[6] for it in items], R, A, 0.0, np.float64)
        act_d = _pad2([it[7] for it in items], R, A, 0.0, np.float64)
        act_valid = _pad2([it[8] for it in items], R, A, False, bool)
    per_call = (i_idx, y, rr, E, D, act_jk, act_cost, act_d, act_valid)
    with span("xla.phase2.dispatch"):
        kap0, kap1 = _phase2_keys_jit(
            tx.m1_nm, tx.psb_data, tx.rho_d, tx.m1_delay, tx.m1_valid,
            tx.ebf, tx.pc_flat, tx.eps, tx.Delta, tx.Delta_T, *per_call)
        # Queue the copies behind the program, so that the explicit wait
        # adds no round trip of its own before the fetch.
        kap0.copy_to_host_async()
        kap1.copy_to_host_async()
    with span("xla.phase2.wait"):
        jax.block_until_ready((kap0, kap1))
    with span("xla.phase2.fetch"):
        kap0, kap1 = np.array(kap0), np.array(kap1)
    _count(counters, "device_calls_phase2", 1)
    _count(counters, "upload_bytes", sum(a.nbytes for a in per_call))
    _count(counters, "readback_bytes", kap0.nbytes + kap1.nbytes)
    return kap0, kap1


# ---------------------------------------------------------------------------
# Relocate screen (score_moves_batch's filters, any-destination reduce)
# ---------------------------------------------------------------------------

@jax.jit
def _screen_jit(m1_delay, m1_valid, m1_rental, m1_nm, ebf, lpx, psB_flat,
                comp_flat, Delta_T, g_i, g_lane, z_lt, act_jk, act_nm,
                act_d, act_ok, load, s_g, s_jk, dyn, bound, rr2, err_num,
                del_num, fthr):
    G, JK = z_lt.shape
    S = s_g.shape[0]
    gr = jnp.arange(G, dtype=jnp.int64)[:, None]

    def scat(base, vals):
        p = jnp.concatenate([base, jnp.zeros((G, 1), base.dtype)], axis=1)
        return p.at[gr, act_jk].set(vals)[:, :JK]

    # Destination rows per (lane, type) group — the DestCache row
    # construction: M1 grids with each lane's active cells overridden
    # (pair config delay/validity, zero incremental rental, pair GPU
    # count), plus the type's admission-dependent static cost.
    d_sel = scat(m1_delay[g_i], act_d)
    okr = scat(m1_valid[g_i], act_ok)
    rent = scat(m1_rental[g_i], jnp.zeros_like(act_d))
    nmd = scat(m1_nm[g_i], act_nm)
    dcost = Delta_T * (rent + jnp.where(z_lt, 0.0, psB_flat[None, :]))
    comp = comp_flat[None, :] * nmd - load[g_lane]
    # Per-source improvement filter + cap upper bound, reduced to one
    # "any destination alive" bit.
    ds = d_sel[s_g]
    delta = dcost[s_g] + dyn[:, None] * ds
    cand = okr[s_g] & (delta < bound[:, None])
    candp = jnp.concatenate([cand, jnp.zeros((S, 1), bool)], axis=1)
    cand = candp.at[jnp.arange(S, dtype=jnp.int64), s_jk].set(False)[:, :JK]
    si = g_i[s_g]
    ub = jnp.minimum(rr2[:, None], err_num[:, None] / ebf[si])
    ub = jnp.minimum(ub, del_num[:, None] / jnp.maximum(ds, 1e-12))
    lpx_s = lpx[si]
    gcap = comp[s_g] / jnp.where(lpx_s > 1e-18, lpx_s, 1.0)
    ub = jnp.where(lpx_s > 1e-18, jnp.minimum(ub, gcap), ub)
    alive = cand & (ub >= fthr[:, None])
    return jnp.any(alive, axis=1)


# Geometric bucket ladders: each distinct (S, G, A) triple costs one jit
# trace, so steps double — retraces stay O(log) while padding waste is
# bounded at 2x (the coarse ladders this replaced padded the common
# ~700-source screen call to 4096 rows, 5x wasted device work).
_SRC_STEPS = (128, 256, 512, 1024, 2048, 4096)
_GRP_STEPS = (64, 128, 256, 512, 1024, 2048, 4096)
_SCREEN_ACT_STEPS = (128, 512, 2048, 8192)


def screen_sources(tx: XlaInstanceTensors, groups: list[tuple],
                   srcs: list[tuple], load: np.ndarray,
                   counters: dict | None = None) -> np.ndarray:
    """One padded screen call; see the module docstring.

    ``groups[g] = (lane_idx, type, z_lt_flat, act_jk, act_nm, act_d,
    act_ok)`` — one row per (lane, type) with the lane's active-cell
    overrides; ``srcs[s] = (g, s_jk, dyn, bound, rr2, err_num, del_num,
    fthr)``; ``load`` is the [n_lanes, J*K] stacked per-lane compute
    load (padded to the solve's full lane count so the compiled shape is
    per-solve constant).  Returns a bool verdict per real source
    (True = may improve, run the exact scan).
    """
    nS = len(srcs)
    with span("xla.screen.pack"):
        per_call = _screen_inputs(tx.JK, groups, srcs, load)
    with span("xla.screen.dispatch"):
        alive = _screen_jit(tx.m1_delay, tx.m1_valid, tx.m1_rental,
                            tx.m1_nm, tx.ebf, tx.lpx, tx.psB_flat,
                            tx.comp_flat, tx.Delta_T, *per_call)
        real = alive[:nS]
        real.copy_to_host_async()
    with span("xla.screen.wait"):
        real.block_until_ready()
    with span("xla.screen.fetch"):
        verdicts = np.array(real)
    _count(counters, "device_calls_screen", 1)
    _count(counters, "screen_sources", nS)
    _count(counters, "screen_rows_padded", int(alive.shape[0]))
    _count(counters, "upload_bytes", sum(a.nbytes for a in per_call))
    _count(counters, "readback_bytes", verdicts.nbytes)
    return verdicts


def _screen_inputs(JK: int, groups: list[tuple], srcs: list[tuple],
                   load: np.ndarray) -> tuple[np.ndarray, ...]:
    """The screen program's per-call arguments, padded to their
    buckets (see `screen_sources`)."""
    nG, nS = len(groups), len(srcs)
    a_max = max((g[3].shape[0] for g in groups), default=0)
    A = _bucket(max(a_max, 1), _SCREEN_ACT_STEPS, JK)
    # Capped at the ladder's top, not at the count itself: a cap of n
    # would make every count below the top its own shape and compile.
    G = _bucket(nG, _GRP_STEPS, max(nG, _GRP_STEPS[-1]))
    S = _bucket(nS, _SRC_STEPS, max(nS, _SRC_STEPS[-1]))
    g_i = np.zeros(G, np.int64)
    g_lane = np.zeros(G, np.int64)
    z_lt = np.zeros((G, JK), bool)
    act_jk = np.full((G, A), JK, np.int64)
    act_nm = np.zeros((G, A), np.float64)
    act_d = np.zeros((G, A), np.float64)
    act_ok = np.zeros((G, A), bool)
    for g, (lane, ty, z_row, a_jk, a_nm, a_d, a_ok) in enumerate(groups):
        g_i[g] = ty
        g_lane[g] = lane
        z_lt[g] = z_row
        n = a_jk.shape[0]
        act_jk[g, :n] = a_jk
        act_nm[g, :n] = a_nm
        act_d[g, :n] = a_d
        act_ok[g, :n] = a_ok
    s_g = np.zeros(S, np.int64)
    s_jk = np.full(S, JK, np.int64)
    dyn = np.zeros(S, np.float64)
    bound = np.full(S, -np.inf)
    rr2 = np.zeros(S, np.float64)
    err_num = np.zeros(S, np.float64)
    del_num = np.zeros(S, np.float64)
    fthr = np.zeros(S, np.float64)
    for s, (g, jk, dy, bd, r2, en, dn, ft) in enumerate(srcs):
        s_g[s], s_jk[s] = g, jk
        dyn[s], bound[s], rr2[s] = dy, bd, r2
        err_num[s], del_num[s], fthr[s] = en, dn, ft
    return (g_i, g_lane, z_lt, act_jk, act_nm, act_d, act_ok, load, s_g,
            s_jk, dyn, bound, rr2, err_num, del_num, fthr)
