"""Batched local-search engine: scored move matrices vs scalar probing.

Three contracts pin the PR-3 engine:

* `score_moves_batch` agrees with sequential `_try_move`-style probing —
  the same destinations are admissible, with the same commit caps and the
  same post-move objectives at 1e-9 — checked here deterministically on
  the fixed instance suite and property-based (random instances) in
  tests/test_score_moves_property.py;
* batched relocate+consolidate never ends at a worse objective than the
  reference first-improvement path from the same construction state, and
  the full batched AGH never returns a worse objective than
  `local_search="reference"`;
* every solution the batched engine returns passes the full constraint
  system (`is_feasible`).
"""
import numpy as np
import pytest

from repro.core import agh, default_instance, gh, objective, random_instance
from repro.core.agh import (_consolidate, _consolidate_batched, _orderings,
                            _rank_inactive_targets, _relocate,
                            _relocate_batched)
from repro.core.gh import _phase1, greedy_heuristic
from repro.core.mechanisms import (State, commit, max_commit,
                                   remove_assignment, score_moves_batch,
                                   state_objective, state_snapshot,
                                   undo_all)
from repro.core.solution import is_feasible


def probe_all_destinations(state: State, i: int, j: int, k: int):
    """Sequential `_try_move`-style probe of every destination: returns
    (frac, {(j2,k2): (admissible, cap, obj_after)}) with the state left
    exactly as found.  This is the scalar oracle the scored matrix must
    reproduce."""
    inst = state.inst
    undo: list = []
    frac = remove_assignment(state, i, j, k, undo=undo)
    out = {}
    for j2 in range(inst.J):
        for k2 in range(inst.K):
            if (j2, k2) == (j, k):
                continue
            if state.q[j2, k2] > 0.5:
                c = int(state.cfg[j2, k2])
                if inst.D_cfg[i, j2, k2, c] > inst.Delta[i]:
                    out[(j2, k2)] = (False, None, None)
                    continue
            else:
                c = int(inst.cfg_m1[i, j2, k2])
                if c < 0:
                    out[(j2, k2)] = (False, None, None)
                    continue
            cap = max_commit(state, i, j2, k2, c)
            if cap < frac - 1e-9:
                out[(j2, k2)] = (False, cap, None)
                continue
            u2: list = []
            commit(state, i, j2, k2, c, frac, undo=u2)
            out[(j2, k2)] = (True, cap, state_objective(state))
            undo_all(state, u2)
    undo_all(state, undo)
    return frac, out


def assert_scores_match_probing(state: State, i: int, j: int, k: int):
    """Shared oracle comparison (also driven by the hypothesis suite)."""
    before = state_snapshot(state)
    frac, probed = probe_all_destinations(state, i, j, k)
    ms = score_moves_batch(state, i, j, k)
    assert abs(ms.frac - frac) <= 1e-12
    for (j2, k2), (adm, cap, obj) in probed.items():
        assert bool(ms.admissible[j2, k2]) == adm, (i, j, k, j2, k2)
        if cap is not None:
            assert abs(ms.caps[j2, k2] - cap) <= 1e-9 * max(1.0, cap), \
                (i, j, k, j2, k2)
        if adm:
            assert abs(ms.obj_after[j2, k2] - obj) \
                <= 1e-9 * max(1.0, abs(obj)), (i, j, k, j2, k2)
    # the scan must leave the state untouched
    for a, b in zip(before, state_snapshot(state), strict=True):
        if isinstance(a, (set, float)):
            assert a == b
        else:
            assert np.array_equal(a, b)


def sources_of(state: State):
    return [(int(i), int(f) // state.inst.K, int(f) % state.inst.K)
            for i in range(state.inst.I)
            for f in np.flatnonzero((state.x[i] > 1e-9).ravel())]


def _ls_instances():
    return [
        ("default", default_instance()),
        ("random-6-6-10", random_instance(6, 6, 10, seed=1)),
        ("random-8-5-6", random_instance(8, 5, 6, seed=2)),
        ("random-10-10-10", random_instance(10, 10, 10, seed=3)),
        ("stressed-1.15", default_instance().stressed(1.15)),
        ("tight-budget", random_instance(6, 6, 10, seed=4, budget=40.0)),
        ("random-15-15-10", random_instance(15, 15, 10, seed=7)),
    ]


@pytest.mark.parametrize("name,inst", _ls_instances())
def test_score_moves_batch_matches_probing_on_suite(name, inst):
    _, state = greedy_heuristic(inst)
    srcs = sources_of(state)
    assert srcs, name
    for (i, j, k) in srcs[:10]:
        assert_scores_match_probing(state, i, j, k)


@pytest.mark.parametrize("name,inst", _ls_instances()[:4])
def test_score_moves_batch_improve_below_filter_on_suite(name, inst):
    """The lazy `improve_below` path (including its scalar-caps branch for
    few surviving candidates) is exactly the full scan filtered by the
    improvement bound."""
    _, state = greedy_heuristic(inst)
    obj = state_objective(state)
    for (i, j, k) in sources_of(state)[:8]:
        full = score_moves_batch(state, i, j, k)
        lazy = score_moves_batch(state, i, j, k, improve_below=obj - 1e-9)
        want = full.admissible & (full.obj_after < obj - 1e-9)
        assert np.array_equal(lazy.admissible, want), name
        assert np.allclose(lazy.obj_after[want], full.obj_after[want],
                           atol=0, rtol=1e-12)


@pytest.mark.parametrize("name,inst", _ls_instances())
def test_batched_relocate_never_worse_per_ordering(name, inst):
    """From every multi-start construction state, the batched engine ends
    at an objective <= the reference first-improvement path's (it scores
    the full destination grid, a superset of the reference shortlist, and
    applies the best admissible move)."""
    st0 = State.fresh(inst)
    _phase1(st0)
    p1 = state_snapshot(st0)
    ranked = _rank_inactive_targets(inst)
    rng = np.random.default_rng(0)
    for n, order in enumerate(_orderings(inst, 3, rng)):
        _, stb = greedy_heuristic(inst, order=order, phase1_snapshot=p1)
        _relocate_batched(stb, 3, False)
        _consolidate_batched(stb, False)
        _, str_ = greedy_heuristic(inst, order=order, phase1_snapshot=p1)
        _relocate(str_, 3, ranked, False)
        _consolidate(str_, False)
        ob, orf = state_objective(stb), state_objective(str_)
        assert ob <= orf + 1e-9, (name, n, ob, orf)


@pytest.mark.parametrize("name,inst", _ls_instances())
def test_batched_agh_never_worse_and_feasible(name, inst):
    sol_b = agh(inst, validate=True)
    sol_r = agh(inst, local_search="reference")
    assert is_feasible(inst, sol_b, enforce_zeta=False), name
    ob, orf = objective(inst, sol_b), objective(inst, sol_r)
    assert ob <= orf + 1e-9, (name, ob, orf)
    # and never worse than plain GH
    assert ob <= objective(inst, gh(inst)) + 1e-9, name
