"""The Sec. VII-B baseline zoo, dual-engine: every policy's device kernel
vs its NumPy oracle — decision-identical on shared uniforms/params — plus
the edge cases (no feasible BS, routing precision ties, GatMARL rollout
determinism) and the fused policy grid end to end."""
import numpy as np

from repro.core import baselines as BL
from repro.core import cocar as CC
from repro.core import lp as LP
from repro.mec import metrics as MET
from harness import make_instance, tiny_instance

from repro.mec.scenario import MECConfig, stack_instances


def _x64():
    import jax
    return jax.enable_x64(True)


def _dev(fn, inst, *args):
    """Run a device baseline kernel on one unpadded instance."""
    data = LP.pdhg_data(inst)
    with _x64():
        x, A = fn(data, *args)
    return np.asarray(x), np.asarray(A)


# ---------------------------------------------------------------------------
# per-policy dual-engine agreement on random instances
# ---------------------------------------------------------------------------

def test_greedy_device_matches_host():
    for seed in range(3):
        inst = make_instance(seed=seed, n_users=30, n_bs=4)
        xh, Ah = BL.greedy(inst)
        xd, Ad = _dev(BL.greedy_device, inst)
        assert np.array_equal(xh, xd)
        assert np.array_equal(Ah, Ad)


def test_random_device_matches_host_on_shared_uniforms():
    inst = make_instance(seed=1, n_users=30, n_bs=4)
    u_perm, u_h, u_route = BL.draw_baseline_uniforms(
        5, inst.N, inst.M, inst.U, n_seeds=4)
    for s in range(4):
        xh, Ah = BL.random_from_uniforms(inst, u_perm[s], u_h[s],
                                         u_route[s])
        xd, Ad = _dev(BL.random_device, inst, u_perm[s], u_h[s], u_route[s])
        assert np.array_equal(xh, xd)
        assert np.array_equal(Ah, Ad)


def test_gat_rollout_deterministic_and_dual_engine():
    """Fixed seed: training is cached, two rollouts are bit-identical, and
    the vmappable device rollout reproduces the host decisions."""
    inst = make_instance(seed=2, n_users=25, n_bs=3)
    params = BL.gat_policy(inst, seed=0, episodes=6)
    x1, A1 = BL.gat_rollout_host(inst, params)
    x2, A2 = BL.gat_rollout_host(inst, params)
    assert np.array_equal(x1, x2) and np.array_equal(A1, A2)
    feats = BL.gat_features(inst)
    adj = BL.gat_adj(inst)
    xd, Ad = _dev(BL.gat_rollout_device, inst, params, feats, adj)
    assert np.array_equal(x1, xd)
    assert np.array_equal(A1, Ad)


# ---------------------------------------------------------------------------
# edge cases, identical on both engines
# ---------------------------------------------------------------------------

def test_route_best_exact_precision_tie_keeps_smallest_bs():
    """Two BSs cache the user's model at the same level — an exact
    precision tie; both engines must route to the smaller BS index."""
    inst = tiny_instance(n_bs=2, m_u=(0, 0), R=100.0)
    x = np.zeros((2, 2, 3))
    x[:, :, 0] = 1.0
    for n in range(2):                       # both BSs cache model 0 at h2
        x[n, 0] = [0, 0, 1]
    Ah = BL._route_best(inst, x)
    lvl = np.argmax(x, axis=-1)
    data = LP.pdhg_data(inst)
    with _x64():
        import jax.numpy as jnp
        Ad = np.asarray(BL._route_best_device(data, jnp.asarray(lvl)))
    assert np.array_equal(Ah, Ad)
    assert Ah[0, 0, 1] == 1.0 and Ah[1, 0, 1] == 0.0


def test_user_with_no_feasible_bs_stays_unserved_both_engines():
    """The requested model is cached nowhere: Greedy's home routing and
    the best-precision router both leave the user unserved (A row all
    zero), on both engines, and metrics count the miss identically."""
    # R fits only model 0's full submodel (size 20); model 1 never cached
    inst = tiny_instance(n_bs=1, m_u=(0, 1), R=20.0)
    xh, Ah = BL.greedy(inst)
    xd, Ad = _dev(BL.greedy_device, inst)
    assert np.array_equal(xh, xd) and np.array_equal(Ah, Ad)
    assert Ah[:, 1, :].sum() == 0.0          # user 1 unserved
    mh = MET.window_metrics(inst, xh, Ah)
    data = LP.pdhg_data(inst)
    with _x64():
        md = MET.window_metrics_device(
            data, xd, MET.enforce_device(data, xd, Ad))
    assert mh["hits"] == int(md["hits"]) == 1
    assert abs(mh["avg_qoe"] - float(md["avg_qoe"])) < 1e-9


def test_enforce_device_matches_host_on_noisy_routes():
    """Duplicate routes + routes at uncached submodels + latency
    violations: the execution-time enforcement must kick out the same
    routes on both engines."""
    inst = make_instance(seed=3, n_users=25, n_bs=3)
    xg, _ = BL.greedy(inst)
    # route EVERY user everywhere its model is cached (dupes galore)
    x_sel = xg[:, inst.m_u, 1:]
    A = (x_sel > 0).astype(np.float64)
    Ah = MET.enforce(inst, xg, A)
    data = LP.pdhg_data(inst)
    with _x64():
        Ad = np.asarray(MET.enforce_device(data, xg, A))
    assert np.array_equal(Ah, Ad)
    assert (Ah.sum(axis=(0, 2)) <= 1.0 + 1e-12).all()


# ---------------------------------------------------------------------------
# the fused policy grid end to end
# ---------------------------------------------------------------------------

HETERO = [(0, 22, 3), (1, 28, 4)]


def test_policy_grid_device_matches_host_per_policy():
    """All five policies on a padded heterogeneous stack: identical
    cache/routing decisions per (window, seed, policy), metrics within
    1e-9."""
    insts = [make_instance(seed=s, n_users=u, n_bs=n) for s, u, n in HETERO]
    stacked = stack_instances(insts)
    n_seeds = 2
    uniforms = CC.policy_uniforms(stacked, 3, n_seeds, best_of=2)
    gat = CC.gat_grid_policies(stacked, 0, episodes=5)
    dev = CC.policy_grid_device(stacked, pdhg_iters=250, best_of=2,
                                n_seeds=n_seeds, uniforms=uniforms, gat=gat)
    host = CC.policy_grid_host(stacked, uniforms, gat,
                               dev["cocar_frac"]["x"],
                               dev["cocar_frac"]["A"],
                               dev["spr3_frac"], n_seeds=n_seeds)
    for p in CC.OFFLINE_POLICIES:
        for i, inst in enumerate(insts):
            for s in range(n_seeds):
                xh, Ah, mh = host[p][i][s]
                assert np.array_equal(dev[p]["x"][i, s, :inst.N], xh), p
                assert np.array_equal(
                    dev[p]["A"][i, s, :inst.N, :inst.U], Ah), p
                for k, v in mh.items():
                    assert abs(float(dev[p]["metrics"][k][i, s]) - v) \
                        < 1e-9, (p, k)


def test_improvement_ratio_summary():
    means = {"cocar": [0.6, 0.66], "greedy": [0.3, 0.36],
             "random": [0.1, 0.2], "spr3": [0.2, 0.2],
             "gatmarl": [0.15, 0.15]}
    out = CC.improvement_ratio(means)
    assert out["best_baseline"] == "greedy"
    assert abs(out["ratio"] - 0.63 / 0.33) < 1e-12


def test_run_policy_sweep_rows_and_summary():
    from repro.experiments.sweep import run_policy_sweep
    rows, summary = run_policy_sweep(
        base=MECConfig(n_users=18), axes={"zipf": (0.4, 0.8)},
        pdhg_iters=150, best_of=2, n_seeds=1, episodes=4)
    assert len(rows) == 2 * len(CC.OFFLINE_POLICIES)
    assert {r["policy"] for r in rows} == set(CC.OFFLINE_POLICIES)
    for r in rows:
        assert 0.0 <= r["hit_rate"] <= 1.0
        assert r["avg_qoe"] <= r["avg_precision"] + 1e-12
    assert summary["ratio"] > 0
    assert summary["best_baseline"] in CC.OFFLINE_POLICIES


def test_spr3_relaxation_consistency():
    """The device relaxation must transform the pytree exactly as the
    host relaxes the instance (sizes/precision/budgets)."""
    inst = make_instance(seed=4, n_users=20, n_bs=3)
    relaxed = BL.spr3_relaxed(inst)
    data = LP.pdhg_data(inst)
    with _x64():
        rdata = BL.spr3_relax_device(data)
        rdata = type(rdata)(*(np.asarray(v) for v in rdata))
    ref = LP.pdhg_data(relaxed)
    np.testing.assert_array_equal(rdata.sizes, ref.sizes)
    np.testing.assert_array_equal(rdata.prec, ref.prec)
    np.testing.assert_array_equal(rdata.prec_u, ref.prec_u)
    np.testing.assert_array_equal(rdata.s_u, ref.s_u)


def test_qoe_bounds_in_window_metrics():
    """QoE is precision discounted by latency slack: 0 ≤ qoe ≤ precision,
    and a window with no served users reports zero."""
    inst = make_instance(seed=5, n_users=20, n_bs=3)
    sc_x, sc_A = BL.greedy(inst)
    m = MET.window_metrics(inst, sc_x, sc_A)
    assert 0.0 <= m["avg_qoe"] <= m["avg_precision"] + 1e-12
    empty_A = np.zeros_like(sc_A)
    m0 = MET.window_metrics(inst, sc_x, empty_A)
    assert m0["avg_qoe"] == 0.0 and m0["hits"] == 0
