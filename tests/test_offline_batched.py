"""The fused offline pipeline (LP → round → repair → metrics, one device
dispatch) vs the NumPy reference: decision-identical equivalence on whole
grids, repair edge cases asserted on BOTH paths, and the deterministic
reduction (`tree_sum`) invariants the equivalence rides on; and the
rounding-margin certificate against an independent f64 LP, with the
uniform-consumption locality the grid executor's slicing relies on."""
import harness
import numpy as np
import pytest
from harness import make_instance, tiny_instance
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import cocar as CC
from repro.core import lp as LP
from repro.core.jdcr import check_feasible, objective_sel, tree_sum
from repro.core.rounding import repair, repair_device, round_from_uniforms
from repro.mec import metrics as MET
from repro.mec.scenario import MECConfig, stack_instances


def both_repairs(inst, x, A):
    """Run the NumPy reference and the device kernel on the same rounded
    input; assert they make identical decisions, then return them."""
    import jax

    xh, Ah = repair(inst, np.array(x), np.array(A))
    data = LP.pdhg_data(inst)
    with jax.enable_x64(True):
        xd, Ad = repair_device(data, np.array(x), np.array(A))
    xd, Ad = np.asarray(xd), np.asarray(Ad)
    assert np.array_equal(xh, xd), (xh, xd)
    assert np.array_equal(Ah, Ad), (Ah, Ad)
    # post-repair, metric-time enforcement must be an identity (the fused
    # pipeline computes metrics without re-running enforce)
    assert np.array_equal(MET.enforce(inst, xh, Ah), Ah)
    assert check_feasible(inst, xh, Ah)["ok"]
    return xh, Ah


# ---------------------------------------------------------------------------
# tree_sum: the deterministic reduction equivalence rides on
# ---------------------------------------------------------------------------

def test_tree_sum_matches_numpy_and_is_padding_invariant():
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    for n in (1, 2, 3, 7, 17, 64, 150):
        v = rng.standard_normal((5, n))
        ref = tree_sum(v, -1)
        np.testing.assert_allclose(ref, v.sum(-1), rtol=1e-12)
        # appending zeros must not change a single bit
        padded = np.concatenate([v, np.zeros((5, 37))], axis=-1)
        assert np.array_equal(tree_sum(padded, -1), ref)
        # the jnp path folds the same adds -> bit-identical to numpy
        with jax.enable_x64(True):
            dev = np.asarray(tree_sum(jnp.asarray(v), -1))
        assert np.array_equal(dev, ref)


def test_round_from_uniforms_np_jnp_identical():
    import jax
    import jax.numpy as jnp

    inst = make_instance(n_users=30)
    x_f, A_f, _ = LP.solve_lp_scipy(inst)
    onehot = np.zeros((inst.U, inst.M))
    onehot[np.arange(inst.U), inst.m_u] = 1.0
    from repro.core.rounding import draw_rounding_uniforms
    u_cat, u_phi = draw_rounding_uniforms(3, 4, inst.N, inst.M, inst.U,
                                          inst.H)
    xh, Ah = round_from_uniforms(np.asarray(x_f), np.asarray(A_f), onehot,
                                 u_cat, u_phi)
    with jax.enable_x64(True):
        xd, Ad = round_from_uniforms(jnp.asarray(x_f), jnp.asarray(A_f),
                                     jnp.asarray(onehot),
                                     jnp.asarray(u_cat),
                                     jnp.asarray(u_phi))
    assert np.array_equal(xh, np.asarray(xd))
    assert np.array_equal(Ah, np.asarray(Ad))


# ---------------------------------------------------------------------------
# repair edge cases, identical on both paths
# ---------------------------------------------------------------------------

def _route(inst, entries):
    """A (N, U, H) routing matrix with 1.0 at each (n, u, h) entry."""
    A = np.zeros((inst.N, inst.U, inst.H))
    for n, u, h in entries:
        A[n, u, h] = 1.0
    return A


def _cache(inst, levels):
    """A one-hot x from per-(n, m) cached levels."""
    x = np.zeros((inst.N, inst.M, inst.H + 1))
    for (n, m), h in levels.items():
        x[n, m, h] = 1.0
    return x


def test_memory_overflow_downgrade_to_smaller_submodel():
    """Slack fits the next-smaller submodel: the evicted model downgrades
    (h2 -> h1) and its users follow to the downgraded route."""
    inst = tiny_instance(R=32.0)                # 40 used, slack fits h1
    x = _cache(inst, {(0, 0): 2, (0, 1): 2})
    A = _route(inst, [(0, 0, 1), (0, 1, 1)])    # both users at h2
    xh, Ah = both_repairs(inst, x, A)
    # model 1 has the smaller routed precision -> downgraded to h1
    assert np.argmax(xh[0, 1]) == 1
    assert np.argmax(xh[0, 0]) == 2
    assert Ah[0, 1, 0] == 1.0 and Ah[0, 1, 1] == 0.0   # user moved h2->h1
    assert Ah[0, 0, 1] == 1.0                          # untouched


def test_memory_overflow_evicts_to_h0():
    """No smaller submodel fits: evict to h0 and drop the orphaned user."""
    inst = tiny_instance(R=25.0)                # slack 5 < h1 size 10
    x = _cache(inst, {(0, 0): 2, (0, 1): 2})
    A = _route(inst, [(0, 0, 1), (0, 1, 1)])
    xh, Ah = both_repairs(inst, x, A)
    assert np.argmax(xh[0, 1]) == 0             # evicted outright
    assert Ah[0, 1].sum() == 0.0                # its user goes to the cloud
    assert Ah[0, 0, 1] == 1.0


def test_downgrade_chain_over_multiple_evictions():
    """Tight memory forces a chain: one model steps down, then the other,
    until the budget fits — the bounded while_loop must reach the same
    fixpoint as the reference's open-ended loop."""
    inst = tiny_instance(R=21.0, m_u=(0, 1), prec2=(0.9, 0.8))
    x = _cache(inst, {(0, 0): 2, (0, 1): 2})    # 40 used vs R=21
    A = _route(inst, [(0, 0, 1), (0, 1, 1)])
    xh, Ah = both_repairs(inst, x, A)
    used = float(np.sum(xh[0] * inst.sizes))
    assert used <= 21.0 + 1e-9


def test_dedupe_exact_precision_tie_keeps_smallest_bs():
    """Two routes to the SAME submodel level at different BSs are an exact
    precision tie — both engines must keep the smaller (n, h)."""
    inst = tiny_instance(n_bs=2, m_u=(0,), R=100.0)
    x = _cache(inst, {(0, 0): 2, (1, 0): 2, (0, 1): 0, (1, 1): 0})
    A = _route(inst, [(0, 0, 1), (1, 0, 1)])    # duplicate routes, tied
    xh, Ah = both_repairs(inst, x, A)
    assert Ah[0, 0, 1] == 1.0 and Ah[1, 0, 1] == 0.0


def test_users_infeasible_at_every_bs_stay_unserved():
    """A deadline below every achievable latency: the kick-out stage drops
    the routes and the re-route stage must NOT bring them back."""
    inst = tiny_instance(ddl=1e-6, R=100.0)
    x = _cache(inst, {(0, 0): 2, (0, 1): 2})
    A = _route(inst, [(0, 0, 1), (0, 1, 1)])
    xh, Ah = both_repairs(inst, x, A)
    assert Ah.sum() == 0.0
    m = MET.window_metrics(inst, xh, Ah)
    assert m["hits"] == 0 and m["hit_rate"] == 0.0


def test_reroute_recovers_unserved_user_at_feasible_bs():
    """A user whose rounded route was dropped gets re-routed to a cached
    feasible replica (the routing-only step beyond Sec. V-D)."""
    inst = tiny_instance(n_bs=2, m_u=(0,), R=100.0)
    x = _cache(inst, {(0, 0): 0, (1, 0): 2, (0, 1): 0, (1, 1): 0})
    A = _route(inst, [])                        # unserved after rounding
    xh, Ah = both_repairs(inst, x, A)
    assert Ah[1, 0, 1] == 1.0                   # picked up at BS 1, h2


# ---------------------------------------------------------------------------
# the fused pipeline end to end
# ---------------------------------------------------------------------------

HETERO = [(0, 40, 3), (1, 50, 4), (2, 35, 3)]
#: the paper's Sec. VII-A window: N=5, U=600, M=8
SEC7A = [(0, 600, 5)]


def _device_vs_reference(n_seeds, best_of, iters=500, spec=HETERO,
                         n_models=4):
    insts = [make_instance(seed=s, n_users=u, n_bs=n, n_models=n_models)
             for s, u, n in spec]
    stacked = stack_instances(insts)
    u_cat, u_phi = CC.offline_uniforms(stacked, 7, n_seeds, best_of)
    dev = CC.offline_pipeline_device(stacked, u_cat, u_phi,
                                     pdhg_iters=iters, n_seeds=n_seeds)
    host = CC.offline_pipeline_host(stacked, dev["x_frac"], dev["A_frac"],
                                    u_cat, u_phi, n_seeds=n_seeds)
    devu = CC._unstack_device(stacked, dev, n_seeds)
    return insts, devu, host


@pytest.mark.parametrize("spec,n_models", [(HETERO, 4), (SEC7A, 8)],
                         ids=["hetero", "sec7a"])
def test_device_pipeline_matches_reference_on_hetero_grid(spec, n_models):
    """Identical cache/routing decisions on a padded heterogeneous stack
    and on one Sec. VII-A window, objectives and window metrics within
    1e-9, all outputs feasible."""
    insts, devu, host = _device_vs_reference(n_seeds=2, best_of=4,
                                             spec=spec, n_models=n_models)
    for inst, per_dev, per_host in zip(insts, devu, host):
        for (xd, Ad, idv), (xh, Ah, ih) in zip(per_dev, per_host):
            assert np.array_equal(xd, xh)
            assert np.array_equal(Ad, Ah)
            assert check_feasible(inst, xd, Ad)["ok"]
            assert abs(idv["obj"] - ih["obj"]) < 1e-9
            assert abs(idv["lp_obj"] - ih["lp_obj"]) < 1e-9
            for k, v in ih["metrics"].items():
                assert abs(idv["metrics"][k] - v) < 1e-9, k


def test_best_of_trial_argmax_agreement():
    """The device argmax over trials must pick the same winner as the host
    strictly-greater loop — per (window, seed), with bit-equal per-trial
    objectives (ties included)."""
    _, devu, host = _device_vs_reference(n_seeds=3, best_of=8)
    for per_dev, per_host in zip(devu, host):
        for (_, _, idv), (_, _, ih) in zip(per_dev, per_host):
            assert idv["best_t"] == ih["best_t"]
            assert np.array_equal(idv["trial_objs"],
                                  np.asarray(ih["trial_objs"]))


def test_check_feasible_device_on_pipeline_outputs():
    """The jnp feasibility residuals, evaluated on the padded pipeline
    outputs, must report every repaired window as feasible."""
    import jax

    from repro.core.jdcr import check_feasible_device

    insts = [make_instance(seed=s, n_users=u, n_bs=n) for s, u, n in HETERO]
    stacked = stack_instances(insts)
    u_cat, u_phi = CC.offline_uniforms(stacked, 1, 2, 2)
    dev = CC.offline_pipeline_device(stacked, u_cat, u_phi,
                                     pdhg_iters=400, n_seeds=2)
    for i in range(len(stacked)):
        data_i = type(stacked.data)(*(v[i] for v in stacked.data))
        for s in range(2):
            with jax.enable_x64(True):
                res = check_feasible_device(data_i, dev["x"][i, s],
                                            dev["A"][i, s])
            for k, v in res.items():
                assert float(v) <= 1e-6, (k, float(v))


def test_objective_sel_matches_objective():
    inst = make_instance(n_users=30)
    x_f, A_f, _ = LP.solve_lp_scipy(inst)
    from repro.core.rounding import round_solution
    x, A = round_solution(inst, x_f, A_f, key=0)
    x, A = repair(inst, x, A)
    prec_u = inst.prec[inst.m_u, 1:]
    assert abs(objective_sel(prec_u, A) - inst.objective(A)) < 1e-9


def test_sweep_seeds_axis():
    """run_sweep(n_seeds=2) emits one row per (variant, rounding seed)."""
    from repro.experiments.sweep import run_sweep
    rows = run_sweep(base=MECConfig(n_users=20),
                     axes={"zipf": (0.4, 0.8)}, pdhg_iters=300,
                     best_of=2, n_seeds=2)
    assert len(rows) == 4
    assert {r["rounding_seed"] for r in rows} == {0, 1}
    for r in rows:
        assert 0.0 <= r["hit_rate"] <= 1.0


def test_cocar_grid_host_backend_matches_shapes():
    """The host composition returns ``cocar_grid``'s result structure (it
    is the same algorithm, looped on the host against its own LP solve)
    and its decisions."""
    insts = [make_instance(seed=s, n_users=u, n_bs=n)
             for s, u, n in HETERO[:2]]
    kw = dict(seed=0, pdhg_iters=300, best_of=2, n_seeds=2)
    grid = harness.offline_grid_host(insts, **kw)
    harness.assert_same_offline(CC.cocar_grid(insts, **kw), grid)
    assert len(grid) == 2 and len(grid[0]) == 2
    for inst, per_seed in zip(insts, grid):
        for x, A, info in per_seed:
            assert x.shape == (inst.N, inst.M, inst.H + 1)
            assert check_feasible(inst, x, A)["ok"]
            assert info["lp_obj"] > 0


# ---------------------------------------------------------------------------
# the rounding-margin certificate against an independent f64 LP
# ---------------------------------------------------------------------------

def test_rounding_margin_certifies_decision_identity():
    """The device pipeline's fractional solution must sit far closer to
    the benchmark's independent NumPy f64 PDHG
    (``chipbench/reference/offline.py::solve_lp``) than any uniform sits
    to its rounding threshold — decisions then *cannot* differ from the
    reference's, rather than merely not differing on this draw."""
    from chipbench.reference.offline import solve_lp

    iters, n_seeds, best_of = 300, 2, 3
    insts, stacked = harness.padded_stack(HETERO)
    u_cat, u_phi = CC.offline_uniforms(stacked, 7, n_seeds, best_of)
    dev = CC.offline_pipeline_device(stacked, u_cat, u_phi,
                                     pdhg_iters=iters, n_seeds=n_seeds)
    for i, inst in enumerate(insts):
        N, U = inst.N, inst.U
        x_r, A_r = solve_lp(harness.reference_window(inst), iters)
        x_d, A_d = dev["x_frac"][i, :N], dev["A_frac"][i, :N, :U]
        gap = max(float(np.abs(x_d - x_r).max()),
                  float(np.abs(A_d - A_r).max()))
        oh = inst.onehot_mu()
        uc, up = u_cat[i, :, :N], u_phi[i, :, :N, :U]
        m = harness.decision_margin(x_r, A_r, oh, uc, up)
        assert m["min"] > 0
        assert gap < m["min"] / 10.0, (i, gap, m)
        cert = harness.threshold_shift_certificate(x_r, A_r, x_d, A_d, oh,
                                                   uc, up)
        assert cert["certified"], (i, cert)
        assert cert["headroom"] > 10.0, (i, cert)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(2, 4), m=st.integers(2, 4), u=st.integers(3, 8),
       h=st.integers(1, 3), t=st.integers(2, 4), row=st.integers(0, 3),
       trial=st.integers(0, 3), seed=st.integers(0, 100))
def test_rounding_uniform_consumption_is_local(n, m, u, h, t, row, trial,
                                               seed):
    """Alg. 1 rounding consumes uniforms positionally: perturbing the
    uniforms of one trial / one BS row changes no other trial's or row's
    decisions.  This locality is what lets the scale executor draw
    uniforms once at the global max shape and slice them per bucket."""
    row, trial = row % n, trial % t
    rng = np.random.default_rng(seed)
    x_frac = rng.random((n, m, h + 1))
    A_frac = rng.random((n, u, h))
    m_u = rng.integers(0, m, size=u)
    onehot = np.zeros((u, m))
    onehot[np.arange(u), m_u] = 1.0
    u_cat = rng.random((t, n, m))
    u_phi = rng.random((t, n, u, h))
    x0, A0 = round_from_uniforms(x_frac, A_frac, onehot, u_cat, u_phi)

    # perturb every uniform of one trial: other trials bit-unchanged
    u_cat2, u_phi2 = u_cat.copy(), u_phi.copy()
    u_cat2[trial] = rng.random((n, m))
    u_phi2[trial] = rng.random((n, u, h))
    x1, A1 = round_from_uniforms(x_frac, A_frac, onehot, u_cat2, u_phi2)
    others = [tt for tt in range(t) if tt != trial]
    harness.assert_decisions_identical(x0[others], A0[others],
                                       x1[others], A1[others],
                                       msg="(trial locality)")

    # perturb one BS row's uniforms: other rows bit-unchanged
    u_cat3, u_phi3 = u_cat.copy(), u_phi.copy()
    u_cat3[:, row] = rng.random((t, m))
    u_phi3[:, row] = rng.random((t, u, h))
    x2, A2 = round_from_uniforms(x_frac, A_frac, onehot, u_cat3, u_phi3)
    keep = [nn for nn in range(n) if nn != row]
    harness.assert_decisions_identical(x0[:, keep], A0[:, keep],
                                       x2[:, keep], A2[:, keep],
                                       msg="(row locality)")
