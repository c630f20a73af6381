"""The fused mixed-precision PDHG backend vs the reference kernel: the
bit-exact conformance contract.

Three layers, all riding on tests/harness.py:

  * kernel layer — the Pallas engine (interpret mode on CPU) against its
    lax.scan realization: same step math, f32 state agreement to
    FMA-contraction noise, and the pure-f64 fused path within
    op-reordering distance of ``LP._pdhg_kernel``;
  * pipeline layer — ``lp_backend="pallas"`` through the offline and
    policy grids and the sharded executor makes *bit-identical*
    decisions (cache/routing arrays, winning trials) to
    ``lp_backend="reference"``;
  * certificate layer — the rounding-margin certificate: the fused
    fractional gap stays orders of magnitude below every uniform's
    distance to its rounding threshold, so decision identity is implied,
    not coincidental.

Plus the hypothesis property tests (padding inertness of the fused
kernel, uniform-consumption locality of Alg. 1 rounding) backing the
executor's slice-per-bucket RNG scheme.
"""
import harness
import numpy as np
import pytest
from harness import assert_same_offline, decision_margin, make_instance

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import cocar as CC
from repro.core import lp as LP
from repro.core.rounding import draw_rounding_uniforms, round_from_uniforms
from repro.kernels import pdhg_fused as PF
from repro.mec.scenario import stack_instances
from repro.scale import GridSpec, run_grid

HETERO = [(0, 40, 3), (1, 50, 4), (2, 35, 3)]
ITERS, S, BO = 300, 2, 3


def _x64():
    import jax
    return jax.enable_x64(True)


def _data(inst):
    return LP.pdhg_data(inst)


# ---------------------------------------------------------------------------
# kernel layer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [
    # (seed, users, BSs, models, iterations, padded BSs)
    (2, 60, 3, 4, 400, 0),
    (0, 600, 5, 8, 4000, 0),            # Sec. VII-A, the benchmark's size
    (1, 200, 3, 8, 1000, 2),
], ids=["small", "sec7a", "padded_bs"])
def test_fused_f64_matches_reference_closely(shape):
    """With polish == iters the fused path is the reference algorithm
    with reordered ops — pure f64, gap at accumulated-roundoff scale.
    Padded base stations hold exactly 0 routing mass in both."""
    seed, n_users, n_bs, n_models, iters, pad_bs = shape
    with _x64():
        inst = make_instance(seed=seed, n_users=n_users, n_bs=n_bs,
                             n_models=n_models)
        data = _data(inst)
        if pad_bs:
            stacked = stack_instances([inst], pad_to=(n_bs + pad_bs,
                                                      n_users))
            data = type(stacked.data)(*(v[0] for v in stacked.data))
        x_r, A_r = (np.asarray(v) for v in LP._pdhg_kernel(data, iters))
        x_f, A_f = (np.asarray(v) for v in
                    PF.pdhg_fused(data, iters, polish=iters, engine="scan"))
    assert float(np.abs(x_f - x_r).max()) < 1e-10
    assert float(np.abs(A_f - A_r).max()) < 1e-10
    assert (A_r[n_bs:] == 0.0).all() and (A_f[n_bs:] == 0.0).all()


def test_mixed_precision_gap_small_and_finite():
    with _x64():
        inst = make_instance(seed=3, n_users=60)
        gap = PF.fused_vs_reference_gap(_data(inst), 600)
    assert 0.0 <= gap < 1e-3


@pytest.mark.slow_compile
def test_pallas_interpret_matches_scan_engine():
    """The conformance gate for the kernel itself: both engines execute
    the identical fused step.  XLA contracts mul+add chains into FMAs
    differently for the scan body (compiled standalone) and the unrolled
    kernel block, so the f32 sweep carries f32-ulp noise (~1e-7) between
    engines — and shared uniforms round both to identical decisions,
    which is the contract that matters.  The f64 polish runs on the scan
    engine under either choice (Mosaic has no float64), so a pure-f64
    solve is the same program on both."""
    with _x64():
        inst = make_instance(seed=4, n_users=30)
        data = _data(inst)
        # pure f64: one program, bit-identical
        x_s64, A_s64 = PF.pdhg_fused(data, 40, polish=40, engine="scan")
        x_p64, A_p64 = PF.pdhg_fused(data, 40, polish=40, engine="pallas")
        harness.assert_decisions_identical(x_s64, A_s64, x_p64, A_p64,
                                           msg="(pure f64)")
        # mixed precision: f32-sweep FMA noise, still decision-inert
        x_s, A_s = PF.pdhg_fused(data, 80, polish=16, engine="scan")
        x_p, A_p = PF.pdhg_fused(data, 80, polish=16, engine="pallas")
        assert float(np.abs(np.asarray(x_p) - np.asarray(x_s)).max()) < 2e-5
        assert float(np.abs(np.asarray(A_p) - np.asarray(A_s)).max()) < 2e-5
        u_cat, u_phi = draw_rounding_uniforms(11, 4, inst.N, inst.M,
                                              inst.U, inst.H)
        oh = inst.onehot_mu()
        xs, As = round_from_uniforms(np.asarray(x_s), np.asarray(A_s),
                                     oh, u_cat, u_phi)
        xp, Ap = round_from_uniforms(np.asarray(x_p), np.asarray(A_p),
                                     oh, u_cat, u_phi)
        harness.assert_decisions_identical(xs, As, xp, Ap,
                                           msg="(pallas vs scan)")


@pytest.mark.slow_compile
def test_pallas_block_remainder_and_short_runs():
    """Iteration counts that don't divide the block, and runs shorter
    than one block, must execute exactly ``iters`` steps."""
    with _x64():
        inst = make_instance(seed=5, n_users=20)
        data = _data(inst)
        for iters, polish, block in ((37, 5, 8), (6, 2, 8), (16, 16, 4)):
            # tolerance: f64-only runs are one program on both engines;
            # any f32 sweep raises the engine-vs-engine floor to f32 ulps
            tol = 1e-12 if polish >= iters else 2e-5
            x_s, A_s = PF.pdhg_fused(data, iters, polish=polish,
                                     engine="scan")
            x_p, A_p = PF.pdhg_fused(data, iters, polish=polish,
                                     engine="pallas", block=block)
            assert float(np.abs(np.asarray(x_p) - np.asarray(x_s)).max()) \
                < tol, (iters, polish, block)
            assert float(np.abs(np.asarray(A_p) - np.asarray(A_s)).max()) \
                < tol, (iters, polish, block)


def test_solve_lp_pdhg_backend_api():
    inst = make_instance(seed=6, n_users=30)
    res = LP.solve_lp_pdhg(inst, iters=ITERS, backend="pallas")
    assert res.primal_res < 0.05
    assert res.obj > 0
    with pytest.raises(ValueError, match="unknown LP backend"):
        LP._lp_solve_kernel(_data(inst), 10, backend="nope")
    with pytest.raises(ValueError, match="unknown engine"):
        PF.pdhg_fused(_data(inst), 10, engine="mosaic")


# ---------------------------------------------------------------------------
# pipeline layer: decision identity end to end
# ---------------------------------------------------------------------------

def test_offline_grid_decisions_identical_across_backends():
    """cocar_grid(lp_backend="pallas") == cocar_grid(lp_backend=
    "reference"): bit-identical cache/routing decisions and winning
    trials on a heterogeneous padded grid."""
    insts = harness.hetero_insts(HETERO)
    ref = CC.cocar_grid(insts, seed=0, pdhg_iters=ITERS, best_of=BO,
                        n_seeds=S)
    pal = CC.cocar_grid(insts, seed=0, pdhg_iters=ITERS, best_of=BO,
                        n_seeds=S, lp_backend="pallas")
    assert_same_offline(ref, pal)
    for per_r, per_p in zip(ref, pal):
        for (_, _, ir), (_, _, ip) in zip(per_r, per_p):
            np.testing.assert_array_equal(ir["trial_objs"], ip["trial_objs"])
            harness.assert_obj_close(ir["obj"], ip["obj"])


def test_sharded_executor_fused_matches_vmap():
    """The fused backend through shard_map + bucketed batching stays
    decision-identical to its single-device dispatch."""
    insts = harness.hetero_insts(HETERO)
    kw = dict(kind="offline", insts=insts, seed=0, n_seeds=S, best_of=BO,
              pdhg_iters=ITERS, lp_backend="pallas")
    ref = run_grid(GridSpec(**kw, backend="vmap", max_buckets=1))
    out = run_grid(GridSpec(**kw, backend="sharded", devices=1,
                            max_buckets=2, chunk_size=2))
    assert_same_offline(ref.results, out.results)


def test_policy_grid_decisions_identical_across_backends():
    """All five policies (CoCaR + SPR³ both re-solve the LP) keep
    bit-identical decisions under the fused backend."""
    insts = harness.hetero_insts(HETERO[:2])
    stacked = stack_instances(insts)
    uniforms = CC.policy_uniforms(stacked, 3, S, BO)
    gat = CC.gat_grid_policies(stacked, 0, episodes=4)
    ref = CC.policy_grid_device(stacked, pdhg_iters=ITERS, best_of=BO,
                                n_seeds=S, uniforms=uniforms, gat=gat)
    pal = CC.policy_grid_device(stacked, pdhg_iters=ITERS, best_of=BO,
                                n_seeds=S, uniforms=uniforms, gat=gat,
                                lp_backend="pallas")
    for p in CC.OFFLINE_POLICIES:
        for i, inst in enumerate(insts):
            harness.assert_decisions_identical(
                ref[p]["x"][i, :, :inst.N], ref[p]["A"][i, :, :inst.N,
                                                        :inst.U],
                pal[p]["x"][i, :, :inst.N], pal[p]["A"][i, :, :inst.N,
                                                        :inst.U],
                msg=f"({p}[{i}])")
            for k in ref[p]["metrics"]:
                np.testing.assert_allclose(ref[p]["metrics"][k][i],
                                           pal[p]["metrics"][k][i],
                                           atol=1e-9, err_msg=f"{p}.{k}")


# ---------------------------------------------------------------------------
# certificate layer
# ---------------------------------------------------------------------------

def test_rounding_margin_certifies_decision_identity():
    """The fused fractional gap must sit far below every uniform's
    distance to its rounding threshold — decisions then *cannot* differ,
    rather than merely not differing on this draw."""
    insts, stacked = harness.padded_stack(HETERO)
    u_cat, u_phi = CC.offline_uniforms(stacked, 7, S, BO)
    ref = CC.offline_pipeline_device(stacked, u_cat, u_phi,
                                     pdhg_iters=ITERS, n_seeds=S)
    pal = CC.offline_pipeline_device(stacked, u_cat, u_phi,
                                     pdhg_iters=ITERS, n_seeds=S,
                                     lp_backend="pallas")
    for i, inst in enumerate(insts):
        N, U = inst.N, inst.U
        gap = max(
            float(np.abs(ref["x_frac"][i, :N] - pal["x_frac"][i, :N]).max()),
            float(np.abs(ref["A_frac"][i, :N, :U]
                         - pal["A_frac"][i, :N, :U]).max()))
        m = decision_margin(ref["x_frac"][i, :N], ref["A_frac"][i, :N, :U],
                            insts[i].onehot_mu(), u_cat[i, :, :N],
                            u_phi[i, :, :N, :U])
        assert m["min"] > 0
        assert gap < m["min"] / 10.0, (i, gap, m)
        # the sharper per-comparison certificate (what bench_lp gates at
        # scale, where the global min-margin collapses) must also certify
        cert = harness.threshold_shift_certificate(
            ref["x_frac"][i, :N], ref["A_frac"][i, :N, :U],
            pal["x_frac"][i, :N], pal["A_frac"][i, :N, :U],
            insts[i].onehot_mu(), u_cat[i, :, :N], u_phi[i, :, :N, :U])
        assert cert["certified"], (i, cert)
        assert cert["headroom"] > 10.0, (i, cert)


# ---------------------------------------------------------------------------
# property tests (hypothesis)
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(n_users=st.integers(8, 20), n_bs=st.integers(2, 4),
       pad_bs=st.integers(1, 3), pad_users=st.integers(1, 8),
       seed=st.integers(0, 3))
def test_fused_padding_is_exactly_inert(n_users, n_bs, pad_bs, pad_users,
                                        seed):
    """Padded base-station rows AND padded user columns of the fused A
    stay exactly 0.0 through both precision phases (the zero step sizes
    folded into tau_A), and the primal stays finite in [0, 1]."""
    with _x64():
        inst = make_instance(seed=seed, n_users=n_users, n_bs=n_bs)
        stacked = stack_instances([inst], pad_to=(n_bs + pad_bs,
                                                  n_users + pad_users))
        data = type(stacked.data)(*(v[0] for v in stacked.data))
        x, A = PF.pdhg_fused(data, 48, polish=8, engine="scan")
        x, A = np.asarray(x), np.asarray(A)
    assert (A[inst.N:] == 0.0).all()
    assert (A[:, inst.U:] == 0.0).all()
    assert np.isfinite(x).all() and (x >= 0).all() and (x <= 1).all()
    assert np.isfinite(A).all()


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
