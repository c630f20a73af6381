"""Batched PDHG + vectorized rounding: the one-dispatch path must agree
with the per-instance oracles (scipy objectives, the benchmark's
independent f64 PDHG, scalar rounding stats), and padding stays inert."""
import harness
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import lp as LP
from repro.core.cocar import cocar_windows_batched
from repro.core.jdcr import check_feasible
from repro.core.rounding import round_solution, round_solution_batch
from repro.mec.scenario import (MECConfig, Scenario, config_grid,
                                stack_instances)


def make_instance(seed=0, n_users=40, n_bs=3, n_models=4):
    cfg = MECConfig(n_bs=n_bs, n_users=n_users, n_models=n_models, seed=seed)
    sc = Scenario(cfg)
    return sc.instance(0, sc.empty_cache())


HETERO = [(0, 40, 3), (1, 50, 4), (2, 35, 3), (3, 30, 2)]


def test_config_grid_cross_product():
    base = MECConfig(n_users=50)
    cfgs = config_grid(base, {"n_bs": (4, 6), "zipf": (0.4, 0.8),
                              "mem_capacity_mb": (300.0, 500.0),
                              "ddl_s": (0.25, 0.35)})
    assert len(cfgs) == 16
    assert len({(c.n_bs, c.zipf, c.mem_capacity_mb, c.ddl_s)
                for c in cfgs}) == 16
    # untouched fields come from the base
    assert all(c.n_users == 50 for c in cfgs)


def test_stack_instances_pads_and_unstacks():
    insts = [make_instance(seed=s, n_users=u, n_bs=n) for s, u, n in HETERO]
    stk = stack_instances(insts)
    N_max = max(i.N for i in insts)
    U_max = max(i.U for i in insts)
    assert stk.data.T.shape == (len(insts), N_max, U_max, insts[0].H)
    # padded BSs have no memory, padded users no precision
    for i, inst in enumerate(insts):
        assert np.all(stk.data.R[i, inst.N:] == 0)
        assert np.all(stk.data.prec_u[i, inst.U:] == 0)
    x = np.zeros((len(insts), N_max, insts[0].M, insts[0].H + 1))
    A = np.zeros((len(insts), N_max, U_max, insts[0].H))
    for (xi, Ai), inst in zip(stk.unstack(x, A), insts):
        assert xi.shape == (inst.N, inst.M, inst.H + 1)
        assert Ai.shape == (inst.N, inst.U, inst.H)


def test_stack_rejects_heterogeneous_catalogs():
    a = make_instance(n_models=4)
    b = make_instance(n_models=5)
    with pytest.raises(ValueError):
        stack_instances([a, b])


def test_batched_pdhg_matches_scipy_per_instance():
    """Every element of a padded heterogeneous stack must reach its own
    HiGHS optimum, exactly like the scalar PDHG path does — and the
    reported objs must match the unstacked solutions (padding holds no
    routing mass)."""
    insts = [make_instance(seed=s, n_users=u, n_bs=n) for s, u, n in HETERO]
    stk = stack_instances(insts)
    res = LP.solve_lp_pdhg_batched(stk.data, iters=3000)
    for i, (inst, (x_f, A_f)) in enumerate(zip(insts,
                                               stk.unstack(res.x, res.A))):
        _, _, obj_ref = LP.solve_lp_scipy(inst)
        obj = inst.objective(A_f)
        assert obj >= obj_ref * 0.97 - 1e-6
        assert obj <= obj_ref * 1.03 + 0.5        # near-feasible overshoot
        assert abs(res.objs[i] - obj) < 1e-4


def test_batched_elements_equal_solo_solves():
    """Padding is inert by construction: each element of a heterogeneous
    stack must reproduce the solo scalar solve of its own instance."""
    insts = [make_instance(seed=s, n_users=u, n_bs=n) for s, u, n in HETERO]
    stk = stack_instances(insts)
    res = LP.solve_lp_pdhg_batched(stk.data, iters=1000)
    for inst, (x_f, A_f) in zip(insts, stk.unstack(res.x, res.A)):
        solo = LP.solve_lp_pdhg(inst, iters=1000)
        np.testing.assert_allclose(x_f, solo.x, atol=1e-4)
        np.testing.assert_allclose(A_f, solo.A, atol=1e-4)


def test_batched_matches_scalar_pdhg():
    """Batch-of-one must be bit-comparable to the scalar jit path."""
    inst = make_instance()
    stk = stack_instances([inst])
    res_b = LP.solve_lp_pdhg_batched(stk.data, iters=1500)
    res_s = LP.solve_lp_pdhg(inst, iters=1500)
    np.testing.assert_allclose(res_b.x[0], res_s.x, atol=1e-5)
    np.testing.assert_allclose(res_b.A[0], res_s.A, atol=1e-5)


@pytest.mark.parametrize("diagnostics", [False, True])
def test_reference_kernel_lowers_without_dot_general(diagnostics):
    """The reference LP kernel, vmapped over a window axis as
    ``_pipeline_kernel`` runs it at Sec. VII-A sizes (N=5, U=600, M=8,
    H=3), lowers with no ``dot_general``: the TPU has no float64 matrix
    unit, so XLA would emulate each one as nested loops over f32 and
    bf16 pieces inside every PDHG iteration."""
    import functools

    import jax

    with jax.enable_x64(True):
        data = LP.pdhg_data(make_instance(n_users=600, n_bs=5, n_models=8))
        spec = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct((1,) + np.shape(a), np.float64),
            data)
        fn = jax.vmap(functools.partial(
            LP._pdhg_kernel, iters=4000, diagnostics=diagnostics))
        text = jax.jit(fn).lower(spec).as_text()
    assert "while" in text
    assert "dot_general" not in text


@pytest.mark.parametrize("shape", [
    # (seed, users, BSs, models, iterations, padded BSs)
    (2, 60, 3, 4, 400, 0),
    (0, 600, 5, 8, 4000, 0),            # Sec. VII-A, the benchmark's size
    (1, 200, 3, 8, 1000, 2),
], ids=["small", "sec7a", "padded_bs"])
def test_reference_kernel_matches_independent_f64(shape):
    """``_pdhg_kernel`` against the benchmark's NumPy f64 PDHG
    (``chipbench/reference/offline.py::solve_lp``, which imports nothing
    of the program), fed the instance's own arrays: the same iterate to
    1e-10.  With padded base stations, the padded solve's real rows match
    the unpadded reference solve and the padded rows hold no mass."""
    import jax

    from chipbench.reference.offline import solve_lp

    seed, n_users, n_bs, n_models, iters, pad_bs = shape
    inst = make_instance(seed=seed, n_users=n_users, n_bs=n_bs,
                         n_models=n_models)
    stacked = stack_instances([inst], pad_to=(n_bs + pad_bs, n_users))
    data = type(stacked.data)(*(v[0] for v in stacked.data))
    with jax.enable_x64(True):
        x, A = (np.asarray(v) for v in LP._pdhg_kernel(data, iters))
    x_r, A_r = solve_lp(harness.reference_window(inst), iters)
    assert float(np.abs(x[:n_bs] - x_r).max()) < 1e-10
    assert float(np.abs(A[:n_bs] - A_r).max()) < 1e-10
    assert (A[n_bs:] == 0.0).all()


@pytest.mark.parametrize("diagnostics", [False, True],
                         ids=["diag_off", "diag_on"])
@settings(max_examples=8, deadline=None)
@given(n_users=st.integers(8, 20), n_bs=st.integers(2, 4),
       pad_bs=st.integers(1, 3), pad_users=st.integers(1, 8),
       seed=st.integers(0, 3))
def test_reference_padding_is_exactly_inert(diagnostics, n_users, n_bs,
                                            pad_bs, pad_users, seed):
    """Padded base-station rows AND padded user columns of the reference
    kernel's A stay exactly 0.0 (zero step sizes in tau_A, zero
    objective and one-hot rows), with the diagnostics tap off or on, and
    the primal stays finite in [0, 1]."""
    import jax

    inst = make_instance(seed=seed, n_users=n_users, n_bs=n_bs)
    stacked = stack_instances([inst], pad_to=(n_bs + pad_bs,
                                              n_users + pad_users))
    data = type(stacked.data)(*(v[0] for v in stacked.data))
    with jax.enable_x64(True):
        out = LP._pdhg_kernel(data, 48, diagnostics=diagnostics,
                              diag_stride=16)
        x, A = np.asarray(out[0]), np.asarray(out[1])
    assert (A[inst.N:] == 0.0).all()
    assert (A[:, inst.U:] == 0.0).all()
    assert np.isfinite(x).all() and (x >= 0).all() and (x <= 1).all()
    assert np.isfinite(A).all()


def test_round_solution_batch_shapes_and_marginals():
    """Batched trials are iid draws of Alg. 1: caching rows stay one-hot
    and the empirical E[objective] over trials matches the LP objective
    (Lemma 2) just like looping round_solution does."""
    inst = make_instance(n_users=60)
    x_f, A_f, obj = LP.solve_lp_scipy(inst)
    T = 256
    xs, As = round_solution_batch(inst, x_f, A_f, key=0, n_trials=T)
    assert xs.shape == (T, inst.N, inst.M, inst.H + 1)
    assert As.shape == (T, inst.N, inst.U, inst.H)
    assert np.allclose(xs.sum(-1), 1.0)
    vals = [inst.objective(A) for A in As]
    se = np.std(vals) / np.sqrt(T)
    assert abs(np.mean(vals) - obj) < max(5 * se, 0.05 * obj)
    # scalar wrapper is the T=1 special case
    x1, A1 = round_solution(inst, x_f, A_f, key=0)
    assert x1.shape == (inst.N, inst.M, inst.H + 1)
    assert A1.shape == (inst.N, inst.U, inst.H)


def test_batched_rounding_matches_scalar_statistically():
    """Vectorized best_of draws and the scalar loop agree on the rounding
    distribution under a fixed overall budget of draws."""
    inst = make_instance(n_users=50)
    x_f, A_f, _ = LP.solve_lp_scipy(inst)
    _, As = round_solution_batch(inst, x_f, A_f, key=7, n_trials=200)
    batch_vals = np.array([inst.objective(A) for A in As])
    scalar_vals = np.array([inst.objective(
        round_solution(inst, x_f, A_f, key=1000 + s)[1]) for s in range(200)])
    pooled = np.sqrt(batch_vals.var() / 200 + scalar_vals.var() / 200)
    assert abs(batch_vals.mean() - scalar_vals.mean()) < 5 * pooled


def test_cocar_windows_batched_end_to_end():
    insts = [make_instance(seed=s, n_users=u, n_bs=n) for s, u, n in HETERO]
    outs = cocar_windows_batched(insts, seed=0, pdhg_iters=2000, best_of=4)
    assert len(outs) == len(insts)
    for inst, (x, A, info) in zip(insts, outs):
        assert check_feasible(inst, x, A)["ok"]
        assert info["lp_obj"] > 0


def test_sweep_grid_one_dispatch():
    """The default 16-variant sweep solves through a single vmapped
    dispatch and returns one metrics row per variant."""
    from repro.experiments.sweep import DEFAULT_AXES, run_sweep
    rows = run_sweep(base=MECConfig(n_users=30), pdhg_iters=800, best_of=2)
    n_variants = int(np.prod([len(v) for v in DEFAULT_AXES.values()]))
    assert len(rows) == n_variants >= 16
    for row in rows:
        assert set(DEFAULT_AXES) <= set(row)
        assert 0.0 <= row["hit_rate"] <= 1.0
        assert row["lp_obj"] > 0
