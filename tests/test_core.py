"""CoCaR core: LP solver equivalence, rounding guarantees (Lemmas 1–2 as
statistical tests), repair feasibility — including hypothesis property tests
over random JDCR instances."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import lp as LP
from repro.core.cocar import cocar_window
from repro.core.jdcr import check_feasible
from repro.core.rounding import repair, round_solution
from repro.mec.scenario import MECConfig, Scenario


def small_instance(seed=0, n_users=60, n_bs=3, n_models=4):
    cfg = MECConfig(n_bs=n_bs, n_users=n_users, n_models=n_models, seed=seed)
    sc = Scenario(cfg)
    return sc.instance(0, sc.empty_cache())


def warm_instance(seed=0, n_users=60, n_bs=3, n_models=4):
    cfg = MECConfig(n_bs=n_bs, n_users=n_users, n_models=n_models, seed=seed)
    sc = Scenario(cfg)
    inst = sc.instance(0, sc.empty_cache())
    x, A, _ = cocar_window(inst, seed=seed)
    return sc.instance(1, x)


def test_lp_scipy_feasible_fractional():
    inst = small_instance()
    x, A, obj = LP.solve_lp_scipy(inst)
    assert obj > 0
    res = check_feasible(inst, x, A, atol=1e-6)
    assert res["ok"], res


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_pdhg_matches_scipy(seed):
    """Property: the JAX PDHG solver reaches the HiGHS optimum."""
    inst = small_instance(seed=seed, n_users=40)
    _, _, obj_ref = LP.solve_lp_scipy(inst)
    res = LP.solve_lp_pdhg(inst, iters=3000)
    assert res.obj >= obj_ref * 0.97 - 1e-6
    assert res.obj <= obj_ref * 1.03 + 0.5          # near-feasible overshoot


def test_rounding_expectation_matches_lp():
    """Lemma 2: E[rounded objective] == LP objective (statistical)."""
    inst = warm_instance()
    x_f, A_f, obj = LP.solve_lp_scipy(inst)
    vals = []
    for s in range(200):
        _, A_i = round_solution(inst, x_f, A_f, s)
        vals.append(inst.objective(A_i))
    mean = np.mean(vals)
    se = np.std(vals) / np.sqrt(len(vals))
    assert abs(mean - obj) < max(5 * se, 0.05 * obj), (mean, obj, se)


def test_rounding_one_submodel_per_type():
    """Constraint (1) holds for every rounded draw by construction."""
    inst = small_instance()
    x_f, A_f, _ = LP.solve_lp_scipy(inst)
    for s in range(20):
        x_i, A_i = round_solution(inst, x_f, A_f, s)
        assert np.allclose(x_i.sum(-1), 1.0)
        assert np.all(A_i <= x_i[:, inst.m_u, 1:] + 1e-9)   # (14)


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_repair_always_feasible(seed):
    """Property: repair output satisfies every constraint of P1."""
    inst = small_instance(seed=seed % 17, n_users=50)
    x_f, A_f, _ = LP.solve_lp_scipy(inst)
    x_i, A_i = round_solution(inst, x_f, A_f, seed)
    x, A = repair(inst, x_i, A_i)
    res = check_feasible(inst, x, A, atol=1e-6)
    assert res["ok"], res


def test_cocar_beats_random_and_greedy():
    from repro.core import baselines as BL
    from repro.mec import metrics as MET
    inst = warm_instance(n_users=120)
    x, A, _ = cocar_window(inst, seed=0)
    m_c = MET.window_metrics(inst, x, A)
    for fn in (lambda: BL.greedy(inst), lambda: BL.random_policy(inst, 0)):
        xb, Ab = fn()
        m_b = MET.window_metrics(inst, xb, Ab)
        assert m_c["avg_precision"] >= m_b["avg_precision"]


def test_cocar_near_lr_bound():
    """At paper-like scale (concentration regime, P† >> 4ln|H|) CoCaR lands
    near the LR bound — the paper reports a 7.5% gap at full scale."""
    inst = warm_instance(n_users=200, n_bs=5, n_models=8)
    _, _, obj = LP.solve_lp_scipy(inst)
    best = 0.0
    for s in range(3):
        x, A, _ = cocar_window(inst, seed=s)
        from repro.mec import metrics as MET
        best = max(best, MET.window_metrics(inst, x, A)["precision_sum"])
    assert best >= 0.75 * obj, (best, obj)


def test_approximation_ratio_theorem1():
    """Thm 1: rounded objective ≥ (1-δ)² P† w.h.p. when P† ≥ 4 ln|H|."""
    inst = warm_instance(n_users=200)
    x_f, A_f, obj = LP.solve_lp_scipy(inst)
    n_sub = inst.M * inst.H
    delta = np.sqrt(4 * np.log(n_sub) / obj)
    if delta >= 1:
        pytest.skip("P+ too small for the theorem's regime")
    bound = (1 - delta) ** 2 * obj
    ok = 0
    for s in range(20):
        _, A_i = round_solution(inst, x_f, A_f, s)
        if inst.objective(A_i) >= bound:
            ok += 1
    assert ok >= 18, f"bound {bound:.2f} met only {ok}/20 times"
