"""repro.risk — batched Stage-2 solver + CVaR evaluation contracts.

The acceptance spine: the pdhg engine (anchor-basis Woodbury warm starts
+ restarted PDHG + counted exact fallback) must reproduce the exact
HiGHS oracle per scenario to rtol 1e-5, nominal AND stressed — the
stressed case pins the wide `_SHAPE_CLASSES` tier (15-16 active
delay/error rows per basis), which degenerated to per-scenario exact
solves before the shape classes existed.

Also pinned here: the scenario-stream chunking bit-identity that
`risk_evaluate` leans on (`perturbed_chunks` == one-shot
`perturbed_batch`), the `coefficient_batch` == `_coefficients` row
identity both engines consume, CVaR against a hand-computed value, the
report JSON round trip, the planner `risk=` hook, and the invariant-lint
scopes covering `src/repro/risk/`.
"""
import inspect

import numpy as np
import pytest

from repro.core import agh, gh, random_instance
from repro.core.stage2 import Stage2System
from repro.risk import RiskReport, rank_deployments, risk_evaluate
from repro.risk.api import PROTOCOL
from repro.risk.metrics import var_cvar
from repro.risk.solver_exact import ExactChunkSolver, _ChunkArrays

jax = pytest.importorskip("jax")
from repro.risk.solver import BatchedStage2Solver  # noqa: E402

RTOL = 1e-5    # the pdhg-vs-oracle acceptance contract


@pytest.fixture(scope="module")
def inst():
    return random_instance(10, 8, 8, seed=7)


@pytest.fixture(scope="module")
def deploy(inst):
    return gh(inst)


def _batch(inst, S, seed=None):
    rng = np.random.default_rng(PROTOCOL["seed"] if seed is None else seed)
    return inst.perturbed_batch(rng, S, d_infl=PROTOCOL["d_infl"],
                                e_infl=PROTOCOL["e_infl"],
                                lam_pm=PROTOCOL["lam_pm"])


# -- pdhg engine vs the exact oracle ------------------------------------

def test_pdhg_matches_oracle_per_scenario(inst, deploy):
    S = 300
    batch = _batch(inst, S)
    system = Stage2System(inst, deploy)
    out_pd = BatchedStage2Solver(system).solve_scenarios(batch)
    out_ex = ExactChunkSolver(system).solve_scenarios(batch)
    np.testing.assert_allclose(out_pd.costs, out_ex.costs, rtol=RTOL)


@pytest.fixture(scope="module")
def stressed():
    """The 1.5x-stressed (20,20,20) instance and its AGH deployment."""
    big = random_instance(20, 20, 20, seed=42)
    return big.stressed(1.5), agh(big)


def test_pdhg_matches_oracle_stressed_wide_bases(stressed):
    """1.5x stress activates 15-16 delay/error rows per optimal basis —
    only representable through the wide (q, eg) shape class.  Before the
    shape classes every anchor was rejected and the whole batch fell to
    per-scenario exact solves; anchors > 0 pins the fix."""
    sinst, dep = stressed
    S = 160
    batch = _batch(sinst, S)
    system = Stage2System(sinst, dep)
    solver = BatchedStage2Solver(system)
    out_pd = solver.solve_scenarios(batch)
    out_ex = ExactChunkSolver(system).solve_scenarios(batch)
    np.testing.assert_allclose(out_pd.costs, out_ex.costs, rtol=RTOL)
    assert len(solver.anchors) > 0
    assert solver.diagnostics["n_anchor0"] > 0


def _logged_solve(system, batch, solver=None, **kw):
    """Solve `batch` (with `solver` when given, so its anchors carry
    over), keeping host copies of every candidate call's inputs (by name)
    and outputs, the anchor it tried and the assignment pass it made."""
    from repro.risk import solver as solver_mod
    real = solver_mod._candidate_kernel
    real_span = solver_mod.span
    params = list(inspect.signature(real).parameters)
    calls = []
    passes = 0

    def counted(name):
        nonlocal passes
        passes += name == "stage2.assign"
        return real_span(name)

    def logged(*args):
        named = {k: np.asarray(a) for k, a in zip(params, args)
                 if k in ("idx", "Hq")}
        out = real(*args)
        call = dict(named, **{k: np.asarray(v) for k, v in zip(
            ("score", "summary", "best", "z", "y", "rowsv"), out)})
        call["inv"] = call["idx"][call["score"].size:]
        call["anchor"] = next(j for j, a in enumerate(solver.anchors)
                              if a.pack[-1] is args[-1])
        call["pass"] = passes
        calls.append(call)
        return out

    if solver is None:
        solver = BatchedStage2Solver(system, **kw)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(solver_mod, "_candidate_kernel", logged)
        mp.setattr(solver_mod, "span", counted)
        out = solver.solve_scenarios(batch)
    return solver, out, calls


@pytest.fixture(scope="module")
def forced(inst, deploy):
    """max_anchors=0 freezes the anchor set at the seed anchor, forcing
    every miss through restarted PDHG (phase 2)."""
    S = 120
    batch = _batch(inst, S, seed=5)
    solver, out, calls = _logged_solve(Stage2System(inst, deploy), batch,
                                       max_anchors=0)
    return batch, solver, out, calls


def test_forced_pdhg_path_and_diagnostics_accounting(inst, deploy, forced):
    """max_anchors=0 freezes the anchor set at the seed anchor, forcing
    every miss through restarted PDHG (phase 2) — and every scenario must
    be accounted for in exactly one diagnostics bucket."""
    S = 120
    batch, solver, out, _ = forced
    d = solver.diagnostics
    assert d["n_scenarios"] == S
    assert (d["n_anchor0"] + d["n_harvest_exact"] + d["n_pdhg"]
            + d["n_fallback_exact"]) == S
    assert d["n_pdhg"] + d["n_fallback_exact"] > 0
    out_ex = ExactChunkSolver(Stage2System(inst, deploy)) \
        .solve_scenarios(batch)
    np.testing.assert_allclose(out.costs, out_ex.costs, rtol=RTOL)


def test_warm_starts_are_each_miss_best_candidate(forced):
    """The device-resident warm starts PDHG begins from are, per miss, the
    lowest-scoring candidate over the anchors it tried (the first of
    equal scores), recomputed here on the host from each call's
    candidates.  From them PDHG settles 118 scenarios, the budget of
    20,000 iterations is reached, and 2 fall back to the exact oracle."""
    _, solver, _, calls = forced
    Scb = calls[0]["inv"].size
    best_score = np.full(Scb, np.inf)
    best_z = np.zeros((Scb, solver.n))
    best_y = np.zeros((Scb, solver.m))
    for call in calls:
        rows = np.where(call["inv"] < call["score"].size)[0]
        j = call["inv"][rows]
        ok = call["summary"][j, 0] != 0.0
        sc = call["score"][j]
        upd = ~ok & (sc < best_score[rows])
        best_score[rows[upd]] = sc[upd]
        best_z[rows[upd]] = call["z"][j[upd]]
        best_y[rows[upd]] = call["y"][j[upd]]
    assert np.isfinite(best_score).sum() > 0
    # What `_pdhg_setup` makes of the device array: zeros where no
    # candidate has a finite score.
    best = calls[-1]["best"]
    have = np.isfinite(best[:, :1])
    assert np.array_equal(best[:, 0], best_score)
    assert np.array_equal(np.where(have, best[:, 1:1 + solver.n], 0.0),
                          best_z)
    assert np.array_equal(np.where(have, best[:, 1 + solver.n:], 0.0),
                          best_y)
    d = solver.diagnostics
    assert (d["n_pdhg"], d["n_fallback_exact"], d["pdhg_iters_max"]) == \
        (118, 2, 20000)


@pytest.fixture(scope="module")
def second_chunk():
    """Two chunks on the unstressed (20,20,20) instance's GH plan: the
    first harvests the full anchor set (`max_anchors`, 32), the second
    starts with it.  Returns the solver, each anchor's hits and the
    solver's diagnostics as the second chunk starts, and each chunk's
    logged calls."""
    big = random_instance(20, 20, 20, seed=42)
    system = Stage2System(big, gh(big))
    solver, _, calls1 = _logged_solve(system, _batch(big, 600, seed=21))
    assert len(solver.anchors) == solver.max_anchors
    hits0 = [a.hits for a in solver.anchors]
    diag0 = dict(solver.diagnostics)
    _, _, calls2 = _logged_solve(system, _batch(big, 300, seed=22),
                                 solver=solver)
    return solver, hits0, diag0, calls1, calls2


def test_full_anchor_set_makes_one_call_per_pass(second_chunk):
    """Every scenario left after a pass has tried the same anchors, so
    each assignment pass of the second chunk makes exactly one candidate
    call, with the whole chunk first and then exactly the previous
    call's misses, until every scenario is settled on an anchor."""
    solver, _, diag0, _, calls = second_chunk
    assert [c["pass"] for c in calls] == list(range(1, len(calls) + 1))
    group = np.arange(300)
    for call in calls:
        rows = np.where(call["inv"] < call["score"].size)[0]
        assert np.array_equal(rows, group)
        group = rows[call["summary"][call["inv"][rows], 0] == 0.0]
    assert group.size == 0
    d = solver.diagnostics
    assert d["n_anchor0"] - diag0["n_anchor0"] == 300
    assert d["n_harvest_exact"] == diag0["n_harvest_exact"]


def test_anchors_are_tried_in_descending_hits(second_chunk):
    """From the first pass, the untried anchor with the most hits comes
    next, the lower index among equals: an anchor's hits change only
    once it has been tried, so the second chunk walks the order its
    hits had when the chunk started."""
    _, hits0, _, _, calls = second_chunk
    order = sorted(range(len(hits0)), key=lambda j: (-hits0[j], j))
    assert len(set(hits0)) < len(hits0)       # ties occur
    assert [c["anchor"] for c in calls] == order[:len(calls)]


def test_candidate_calls_counts_kernel_calls(second_chunk):
    """`candidate_calls` counts `_candidate_kernel` calls, and
    `candidate_rows` the chunk rows they tried."""
    solver, _, diag0, calls1, calls2 = second_chunk
    assert diag0["candidate_calls"] == len(calls1)
    d = solver.diagnostics
    assert d["candidate_calls"] == len(calls1) + len(calls2)
    rows2 = sum(int((c["inv"] < c["score"].size).sum()) for c in calls2)
    assert d["candidate_rows"] - diag0["candidate_rows"] == rows2


@pytest.mark.parametrize("case,q", [("nominal", 8), ("stressed", 24)])
def test_candidate_statistics_match_host_formula(request, case, q):
    """The summary each candidate call returns holds the statistics
    `_ChunkArrays._stats` computes from the same call's `z` and row
    values: violation counts exactly, unmet and utilization to 1e-12."""
    if case == "nominal":
        inst = request.getfixturevalue("inst")
        dep = request.getfixturevalue("deploy")
        batch = _batch(inst, 300)
    else:
        inst, dep = request.getfixturevalue("stressed")
        batch = _batch(inst, 160)
    solver, _, calls = _logged_solve(Stage2System(inst, dep), batch)
    mine = [c for c in calls if c["Hq"].shape[1] == q]
    assert mine
    for call in mine:
        Sb = call["z"].shape[0]
        host = _ChunkArrays(Sb, solver.n_fam)
        host._stats(np.arange(Sb), call["z"], call["rowsv"], solver)
        summ = call["summary"]
        assert summ.shape == (Sb, 4 + solver.n_fam)
        assert np.array_equal(summ[:, 2], host.viols)
        np.testing.assert_allclose(summ[:, 3], host.unmet, rtol=1e-12,
                                   atol=0)
        np.testing.assert_allclose(summ[:, 4:], host.util, rtol=1e-12,
                                   atol=0)


# -- metrics ------------------------------------------------------------

def test_cvar_hand_computed():
    """Rockafellar-Uryasev on costs 0..99 at alpha=0.9: VaR = 89.1 (the
    interpolated 0.9-quantile), tail excess sum_{c=90..99}(c - 89.1) = 54
    => CVaR = 89.1 + 0.54/0.1 = 94.5."""
    costs = np.arange(100, dtype=float)
    var, cvar = var_cvar(costs, 0.90)
    assert var == pytest.approx(89.1)
    assert cvar == pytest.approx(94.5)
    # Coherence: CVaR dominates VaR dominates the mean, monotone in alpha.
    assert cvar >= var >= costs.mean()
    assert var_cvar(costs, 0.95)[1] >= cvar


# -- report / api -------------------------------------------------------

def test_risk_report_json_round_trip(inst, deploy):
    r = risk_evaluate(inst, deploy, S=64, engine="exact")
    r2 = RiskReport.from_json(r.to_json())
    assert r2.to_dict() == r.to_dict()
    s = r.summary()
    assert s["expected_cost"] == r.expected_cost
    assert s["cvar_0.95"] == r.cvar["0.95"]


def test_report_stage2_costs_match_oracle_across_chunks(inst, deploy):
    """The report's per-scenario costs are the ones its statistics were
    folded from: a multi-chunk pdhg run (anchors carried from chunk to
    chunk) agrees per scenario with a shorter exact run, whose stream is
    a prefix of the longer one."""
    pd = risk_evaluate(inst, deploy, S=96, engine="pdhg", chunk=32)
    ex = risk_evaluate(inst, deploy, S=40, engine="exact", chunk=40)
    assert pd.stage2_costs.shape == (96,)
    np.testing.assert_allclose(pd.stage2_costs[:40], ex.stage2_costs,
                               rtol=RTOL)
    ex_long = risk_evaluate(inst, deploy, S=96, engine="exact", chunk=32)
    assert np.array_equal(ex_long.stage2_costs[:40], ex.stage2_costs)
    assert "stage2_costs" not in pd.to_dict()


def test_risk_evaluate_chunking_invariant(inst, deploy):
    """Chunk size is an implementation detail: same S, different chunk
    => bit-identical statistics (scenario stream + per-scenario solves
    are both chunk-invariant)."""
    r1 = risk_evaluate(inst, deploy, S=96, engine="exact", chunk=96)
    r2 = risk_evaluate(inst, deploy, S=96, engine="exact", chunk=32)
    assert r1.expected_cost == r2.expected_cost
    assert r1.cvar == r2.cvar
    assert r1.viol_quantiles == r2.viol_quantiles


def test_risk_evaluate_rejects_unknown_engine(inst, deploy):
    with pytest.raises(ValueError, match="unknown engine"):
        risk_evaluate(inst, deploy, S=8, engine="simplex")


def test_rank_deployments_stress_orderings(inst, deploy):
    plans = {"gh": deploy, "agh": agh(inst)}
    rk = rank_deployments(inst, plans, S=48, engine="exact", stress=1.5)
    assert sorted(rk["ranking_expected"]) == sorted(plans)
    assert sorted(rk["ranking_cvar"]) == sorted(plans)
    assert rk["agree"] == (rk["ranking_expected"] == rk["ranking_cvar"])
    assert set(rk["summaries"]) == set(plans)
    reports = rk["reports"]
    e = [reports[k].expected_cost for k in rk["ranking_expected"]]
    assert e == sorted(e)
    cv = [reports[k].cvar["0.95"] for k in rk["ranking_cvar"]]
    assert cv == sorted(cv)


def test_planner_risk_hook(inst):
    from repro.planner import PlanOptions, plan
    res = plan("gh", instance=inst,
               options=PlanOptions(risk={"S": 32, "engine": "exact"}))
    row = res.diagnostics["risk"]
    assert row["S"] == 32 and row["engine"] == "exact"
    assert row["expected_cost"] > 0
    base = plan("gh", instance=inst)
    assert "risk" not in base.diagnostics
    assert base.objective == res.objective


# -- chunking / coefficient bit-identities ------------------------------

def test_perturbed_chunks_bit_identical_to_one_shot(inst):
    """Satellite (c): chunked scenario generation == one-shot at large S,
    bit for bit, including across chunk boundaries."""
    S, chunk = 10_000, 4096
    kw = dict(d_infl=PROTOCOL["d_infl"], e_infl=PROTOCOL["e_infl"],
              lam_pm=PROTOCOL["lam_pm"])
    one = inst.perturbed_batch(np.random.default_rng(9), S, **kw)
    parts = list(inst.perturbed_chunks(np.random.default_rng(9), S,
                                       chunk=chunk, **kw))
    assert [p.S for p in parts] == [4096, 4096, 1808]
    for field in ("tau", "e_base", "lam"):
        cat = np.concatenate([getattr(p, field) for p in parts])
        assert np.array_equal(cat, getattr(one, field))
    # The row right AFTER a chunk boundary is the one-shot row `chunk`.
    assert np.array_equal(parts[1].tau[0], one.tau[chunk])
    assert np.array_equal(parts[1].e_base[0], one.e_base[chunk])
    assert np.array_equal(parts[1].lam[0], one.lam[chunk])


def test_coefficient_batch_bit_identical_to_scalar(inst, deploy):
    batch = _batch(inst, 16, seed=11)
    system = Stage2System(inst, deploy)
    vals, c = system.coefficient_batch(batch)
    for s in range(batch.S):
        v1, c1 = system._coefficients(batch.tau[s], batch.e_base[s],
                                      batch.lam[s])
        assert np.array_equal(vals[s, :system.nnz], v1)
        assert np.array_equal(c[s], c1)
    # The equality tail is the constant 1.0 in every scenario.
    assert np.array_equal(vals[:, system.nnz:],
                          np.ones((batch.S, system.nnz_all - system.nnz)))


# -- satellites: lint scopes --------------------------------------------

def test_lint_scopes_cover_risk_subsystem():
    """src/repro/risk/ is an f64 LP tier like the xla engine: the dtype
    narrowing ban and the jit-purity checker must both scope it."""
    from repro.analysis.lint.checkers.dtype import DtypeChecker
    from repro.analysis.lint.checkers.jit_purity import JitPurityChecker
    assert "repro/risk/" in DtypeChecker.scope
    assert "repro/risk/" in JitPurityChecker.scope
    assert "repro/risk/" in DtypeChecker._NARROW_SCOPE
