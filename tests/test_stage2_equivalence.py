"""Equivalence of the vectorized Stage-2 engine and the frozen seed path.

`Stage2System` assembles the scenario LP from precomputed per-triple factor
arrays on a fixed sparsity pattern; `_scalar_ref.stage2_lp_ref` freezes the
seed's per-call dict-of-tuples assembly.  Both must agree on every instance
× deployment × cap combination: same capped-feasibility verdict, same
routing objective (the LP optimum is unique even when the vertex is not),
and the batched / looped / fanned-out evaluation protocols must agree on
violation rates and expected costs because they draw bit-identical
scenarios.
"""
import numpy as np
import pytest

from repro.core import (agh, default_instance, evaluate, gh, hf,
                        random_instance)
from repro.core._scalar_ref import stage2_lp_ref
from repro.core.stage2 import Stage2System, stage2_cost, stage2_lp


def _cases():
    d = default_instance()
    r = random_instance(8, 5, 6, seed=2)
    t = random_instance(6, 6, 10, seed=4, budget=40.0)
    return [
        ("default+GH", d, gh(d)),
        ("default+AGH", d, agh(d)),
        ("random-8-5-6+GH", r, gh(r)),
        ("tight-budget+HF", t, hf(t)),
    ]


CASES = _cases()


@pytest.mark.parametrize("name,inst,deploy", CASES,
                         ids=[c[0] for c in CASES])
def test_stage2_lp_matches_reference(name, inst, deploy):
    """Base + perturbed scenarios, default and strict caps, both admission
    modes: identical capped flags, objectives within 1e-9."""
    rng = np.random.default_rng(11)
    scens = [inst, *(inst.perturbed(rng, d_infl=0.15, e_infl=0.10)
                     for _ in range(3))]
    strict = np.full(inst.I, 0.02)
    for si, scen in enumerate(scens):
        for cap, any_dep in [(None, False), (strict, False),
                             (np.ones(inst.I), True)]:
            got, ok_got = stage2_lp(scen, deploy, u_cap=cap,
                                    allow_any_deployed=any_dep)
            want, ok_want = stage2_lp_ref(scen, deploy, u_cap=cap,
                                          allow_any_deployed=any_dep)
            label = (name, si, "strict" if cap is not None else "zeta",
                     any_dep)
            assert ok_got == ok_want, label
            c_got, c_want = stage2_cost(scen, got), stage2_cost(scen, want)
            assert abs(c_got - c_want) <= 1e-9 * max(1.0, abs(c_want)), \
                (label, c_got, c_want)
            assert np.allclose(got.u, want.u, atol=1e-7), label
            # Deployment untouched, demand balance holds.
            assert np.array_equal(got.y, deploy.y), label
            assert np.allclose(got.x.sum(axis=(1, 2)) + got.u, 1.0,
                               atol=1e-6), label


def test_stage2_system_reuse_matches_one_shot():
    """One system solving many scenarios == one stage2_lp call per scenario
    (the pattern-reuse refresh leaves no stale coefficients behind)."""
    inst = default_instance()
    deploy = gh(inst)
    system = Stage2System(inst, deploy)
    rng = np.random.default_rng(3)
    scens = [inst.perturbed(rng) for _ in range(4)]
    for scen in scens:
        r = system.solve(tau=scen.tau, e_base=scen.e_base, lam=scen.lam)
        sol, ok = stage2_lp(scen, deploy)
        assert ok == r.capped_ok
        want = stage2_cost(scen, sol)
        assert abs(r.cost - want) <= 1e-9 * max(1.0, abs(want))


PROTOCOL = dict(d_infl=0.15, e_infl=0.10, lam_pm=0.20)
FAMILIES = {"protocol": PROTOCOL,
            "lam_pm=0": dict(PROTOCOL, lam_pm=0.0),
            "d_infl=0": dict(PROTOCOL, d_infl=0.0)}
SUB = 7       # scenarios per generator sub-block in the cases below


def _draw_cases():
    cases = [pytest.param("default", 5, "protocol", None,
                          id="default-S5-materialize")]
    for S in (1, SUB - 1, SUB, SUB + 1, SUB * 5 // 2):
        for fam in FAMILIES:
            cases.append(pytest.param("8x5", S, fam, SUB,
                                      id=f"8x5-S{S}-{fam}"))
    return cases


@pytest.mark.parametrize("which,S,fam,sub", _draw_cases())
def test_perturbed_batch_matches_sequential_draws(which, S, fam, sub,
                                                  monkeypatch):
    """Batched sampling must replay the sequential RNG stream bitwise,
    across sub-block boundaries (I != J catches a transposed reshape), and
    leave the generator where S sequential draws leave it."""
    import repro.core.instance as instance_mod
    inst = (default_instance() if which == "default"
            else random_instance(8, 5, 6, seed=2))
    if sub is not None:
        per = 2 * inst.I + inst.I * inst.J
        monkeypatch.setattr(instance_mod, "_DRAW_BLOCK_BYTES", 8 * per * sub)
    kw = FAMILIES[fam]
    rng_b = np.random.default_rng(42)
    batch = inst.perturbed_batch(rng_b, S, **kw)
    rng = np.random.default_rng(42)
    for s in range(S):
        scen = inst.perturbed(rng, **kw)
        assert np.array_equal(batch.tau[s], scen.tau), s
        assert np.array_equal(batch.e_base[s], scen.e_base), s
        assert np.array_equal(batch.lam[s], scen.lam), s
        if sub is None:
            mat = batch.materialize(inst, s)
            assert np.array_equal(mat.lam, scen.lam)
            assert np.array_equal(mat.D_cfg, scen.D_cfg)
    assert np.array_equal(rng_b.random(4), rng.random(4))


def test_perturbed_batch_transient_is_one_sub_block():
    """The draw's peak allocation beyond its three outputs is the scratch
    block of uniforms, not an [S, 2I + I·J] array of them."""
    import tracemalloc

    from repro.core.instance import _DRAW_BLOCK_BYTES
    inst = random_instance(20, 20, 20, seed=42)
    S, per = 4096, 2 * 20 + 20 * 20
    block = min(S, _DRAW_BLOCK_BYTES // (8 * per)) * per * 8
    assert block < S * per * 8 // 2      # the test can tell the two apart
    rng = np.random.default_rng(3)
    tracemalloc.start()
    try:
        base = tracemalloc.get_traced_memory()[0]
        batch = inst.perturbed_batch(rng, S, **PROTOCOL)
        peak = tracemalloc.get_traced_memory()[1] - base
    finally:
        tracemalloc.stop()
    outputs = batch.tau.nbytes + batch.e_base.nbytes + batch.lam.nbytes
    assert peak - outputs < block + (256 << 10), (peak, outputs, block)


def test_evaluate_batched_matches_loop():
    """Identical violation rate, expected cost within 1e-6 (acceptance)."""
    inst = default_instance()
    deploy = gh(inst)
    rb = evaluate(inst, deploy, S=30, seed=9)
    rl = evaluate(inst, deploy, S=30, seed=9, batched=False)
    assert rb.violation_rate == rl.violation_rate
    assert abs(rb.expected_cost - rl.expected_cost) < 1e-6
    assert np.allclose(rb.per_scenario_cost, rl.per_scenario_cost, atol=1e-6)


def test_evaluate_batched_matches_seed_protocol():
    """Agreement with the seed protocol reconstructed verbatim: sequential
    perturbed() + stage2_lp_ref per scenario."""
    inst = default_instance()
    deploy = gh(inst)
    S = 10
    res = evaluate(inst, deploy, S=S, seed=5)
    rng = np.random.default_rng(5)
    costs = np.zeros(S)
    viol = 0
    for s in range(S):
        scen = inst.perturbed(rng, d_infl=0.15, e_infl=0.10, lam_pm=0.20)
        sol, _ = stage2_lp_ref(scen, deploy)
        costs[s] = stage2_cost(scen, sol)
        viol += int(np.sum(sol.u > 0.01))
    assert res.violation_rate == viol / (S * inst.I)
    assert np.allclose(res.per_scenario_cost, costs, atol=1e-6)


def test_evaluate_strict_cap_paths_agree():
    """The strict-cap → relaxed-fallback branch agrees across paths too."""
    inst = default_instance()
    deploy = gh(inst)
    cap = np.full(inst.I, 0.02)
    rb = evaluate(inst, deploy, S=20, seed=2, u_cap=cap)
    rl = evaluate(inst, deploy, S=20, seed=2, u_cap=cap, batched=False)
    assert rb.violation_rate == rl.violation_rate
    assert np.allclose(rb.per_scenario_cost, rl.per_scenario_cost, atol=1e-6)


def test_ssm_models_match_reference():
    """kv_applicable=False models get no (8f) row (constant recurrent
    state, not per-token KV) — the factored assembly must mirror the seed's
    skip, including for deployments that actually use such a model."""
    inst = default_instance()
    deploy0 = gh(inst)
    used = np.flatnonzero(deploy0.q.sum(axis=1) > 0.5)
    assert used.size > 0
    inst.kv_applicable = np.ones(inst.J, dtype=bool)
    inst.kv_applicable[used[0]] = False       # one deployed model is SSM
    inst.__post_init__()
    deploy = gh(inst)
    rng = np.random.default_rng(17)
    for scen in (inst, inst.perturbed(rng, d_infl=0.15, e_infl=0.10)):
        got, ok_got = stage2_lp(scen, deploy)
        want, ok_want = stage2_lp_ref(scen, deploy)
        assert ok_got == ok_want
        c_got, c_want = stage2_cost(scen, got), stage2_cost(scen, want)
        assert abs(c_got - c_want) <= 1e-9 * max(1.0, abs(c_want))
        assert np.allclose(got.u, want.u, atol=1e-7)


def test_empty_deployment_full_unmet():
    """A deployment that can route nothing: u = 1 everywhere, not a crash."""
    from repro.core import Solution
    inst = default_instance()
    empty = Solution.empty(inst)
    sol, ok = stage2_lp(inst, empty, u_cap=np.full(inst.I, 0.02))
    assert not ok
    assert np.allclose(sol.u, 1.0)
    assert sol.x.sum() == 0.0
