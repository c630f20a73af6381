"""The sharded grid executor (``repro.scale``): bucket planning, the
bucketed-padding == max-padding decision identity for all five offline
policies AND the online scan engine, chunked streaming, the shard_map
path (``devices=1``, a 1-device mesh — the multi-device run is exercised
by ``benchmarks/bench_scale.py`` under
``--xla_force_host_platform_device_count=8`` in CI), spec and mesh
validation, and jit-cache stability across repeated sweeps."""
import harness
import numpy as np
import pytest
from harness import assert_same_offline

from repro.core import cocar as CC
from repro.core.online import OnlineConfig
from repro.mec.scenario import MECConfig, stack_instances
from repro.scale import GridSpec, plan_buckets, run_grid
from repro.scale.executor import compiled_cache_stats
from repro.traces.registry import make_trace

#: heterogeneous (seed, n_users, n_bs) grid shared by the identity tests
HETERO = [(0, 16, 3), (1, 20, 4), (2, 16, 3), (3, 24, 4), (4, 20, 3)]


def hetero_insts():
    return harness.hetero_insts(HETERO)


# ---------------------------------------------------------------------------
# bucket planning
# ---------------------------------------------------------------------------

def test_plan_one_bucket_is_global_max_pad():
    plan = plan_buckets([(3, 16), (4, 20), (3, 24)], max_buckets=1)
    assert len(plan) == 1
    b = plan.buckets[0]
    assert (b.n_bs, b.n_users) == (4, 24)
    assert b.indices == (0, 1, 2)


def test_plan_one_shape_per_bucket():
    shapes = [(3, 16), (4, 20), (5, 24)]
    plan = plan_buckets(shapes, max_buckets=8)
    assert len(plan) == 3
    for b, (n, u) in zip(plan.buckets, shapes):
        assert (b.n_bs, b.n_users) == (n, u)
        assert len(b.indices) == 1


def test_plan_covers_indices_and_fits_members():
    shapes = [(3, 40), (6, 10), (3, 41), (6, 12), (4, 38), (5, 11)]
    plan = plan_buckets(shapes, max_buckets=2)
    assert len(plan) == 2
    seen = sorted(i for b in plan.buckets for i in b.indices)
    assert seen == list(range(len(shapes)))
    for b in plan.buckets:
        for i in b.indices:
            n, u = shapes[i]
            assert n <= b.n_bs and u <= b.n_users
    # merging similar shapes must waste fewer cells than one global pad
    assert plan.padded_cells() < plan_buckets(shapes, 1).padded_cells()


def test_plan_key_stable_and_rounding():
    shapes = [(3, 15), (3, 17)]
    p1 = plan_buckets(shapes, max_buckets=2, round_users_to=8)
    p2 = plan_buckets(list(shapes), max_buckets=2, round_users_to=8)
    assert p1.key == p2.key
    assert all(b.n_users % 8 == 0 for b in p1.buckets)
    with pytest.raises(ValueError):
        plan_buckets(shapes, max_buckets=0)
    with pytest.raises(ValueError):
        plan_buckets([], max_buckets=1)


def test_stack_pad_to_and_signature():
    insts = hetero_insts()[:2]
    stk = stack_instances(insts, pad_to=(6, 32))
    assert stk.signature == (2, 6, 32, 4, insts[0].H)
    assert stk.data.T.shape == (2, 6, 32, insts[0].H)
    # pads are zeros beyond each instance's true rows
    assert not stk.data.bs_mask[:, 5:].any()
    with pytest.raises(ValueError):
        stack_instances(insts, pad_to=(3, 32))    # smaller than max N


def test_make_host_mesh_validates_device_count():
    import jax

    from repro.launch.mesh import make_host_mesh

    n = len(jax.devices())
    mesh = make_host_mesh(data=n, model=1)
    assert mesh.shape == {"data": n, "model": 1}
    with pytest.raises(RuntimeError, match="xla_force_host_platform"):
        make_host_mesh(data=n + 1, model=1)
    with pytest.raises(ValueError):
        make_host_mesh(data=0, model=1)


# ---------------------------------------------------------------------------
# offline kind: bucketing / chunking / shard_map decision identity
# ---------------------------------------------------------------------------

S, BO, ITERS = 2, 2, 250


def offline_grid(**kw):
    spec = dict(kind="offline", insts=hetero_insts(), seed=0, n_seeds=S,
                best_of=BO, pdhg_iters=ITERS, max_buckets=1)
    spec.update(kw)
    return run_grid(GridSpec(**spec))


def test_offline_bucketed_matches_max_padded():
    ref = offline_grid()
    assert ref.stats["plan"] == ((4, 24, 5),)
    # k=4 = one bucket per distinct shape (three of them single-instance)
    for k in (2, 3, 4):
        out = offline_grid(max_buckets=k)
        assert len(out.stats["plan"]) == k
        assert_same_offline(ref.results, out.results)
    # every result is at its true shape
    for inst, per_seed in zip(hetero_insts(), ref.results):
        for x, A, _ in per_seed:
            assert x.shape == (inst.N, inst.M, inst.H + 1)
            assert A.shape == (inst.N, inst.U, inst.H)


def test_offline_chunked_matches_one_chunk():
    ref = offline_grid()                   # one bucket, one chunk of 5
    out = offline_grid(chunk_size=2)       # same bucket, three chunks of 2
    assert ref.stats["chunks"] == 1 and out.stats["chunks"] == 3
    assert out.stats["peak_chunk_in_bytes"] < ref.stats["peak_chunk_in_bytes"]
    assert_same_offline(ref.results, out.results)
    # chunking composes with bucketing
    both = offline_grid(max_buckets=2, chunk_size=2)
    assert_same_offline(ref.results, both.results)


def test_offline_sharded_matches_vmap():
    """shard_map over a 1-device mesh must be decision-identical to the
    plain vmap dispatch (the multi-device identity is gated in CI by
    bench_scale under 8 forced host devices)."""
    ref = offline_grid(max_buckets=2)
    out = offline_grid(max_buckets=2, devices=1)
    assert out.stats["devices"] == 1
    assert_same_offline(ref.results, out.results)


def test_offline_matches_legacy_single_dispatch():
    """The executor's 1-bucket vmap path == the pre-scale fused dispatch
    (same kernel, same uniforms, same unstacking)."""
    insts = hetero_insts()
    stacked = stack_instances(insts)
    u_cat, u_phi = CC.offline_uniforms(stacked, 0, S, BO)
    dev = CC.offline_pipeline_device(stacked, u_cat, u_phi,
                                     pdhg_iters=ITERS, n_seeds=S)
    legacy = CC._unstack_device(stacked, dev, S)
    assert_same_offline(legacy, offline_grid().results)


def test_compiled_cache_stable_across_repeats():
    """Re-running the same spec must hit both the executor's compiled-fn
    cache and jit's shape cache — no retraces (the stack_instances
    recompile-churn satellite)."""
    offline_grid(max_buckets=2)
    before = compiled_cache_stats()
    offline_grid(max_buckets=2)
    after = compiled_cache_stats()
    assert set(after) == set(before)
    for k in before:
        if before[k] >= 0:                 # -1 = no _cache_size API
            assert after[k] == before[k]


# ---------------------------------------------------------------------------
# policy kind: all five policies, bucketed == max-padded
# ---------------------------------------------------------------------------

def test_policy_bucketed_matches_max_padded():
    insts = hetero_insts()[:4]
    kw = dict(kind="policy", insts=insts, seed=0, n_seeds=S, best_of=BO,
              pdhg_iters=ITERS, episodes=5)
    ref = run_grid(GridSpec(**kw, max_buckets=1))
    out = run_grid(GridSpec(**kw, max_buckets=2))
    assert len(out.stats["plan"]) == 2
    # lp_obj is a plain einsum over the padded axes — the reduction order
    # (not the decisions) shifts with the padding target, so it carries
    # the usual ~1e-15 float slack rather than bit equality
    np.testing.assert_allclose(ref.stats["lp_obj"], out.stats["lp_obj"],
                               rtol=1e-12)
    for p in CC.OFFLINE_POLICIES:
        for i, inst in enumerate(insts):
            for s in range(S):
                x1, A1, m1 = ref.results[p][i][s]
                x2, A2, m2 = out.results[p][i][s]
                np.testing.assert_array_equal(x1, x2, err_msg=f"{p}[{i},{s}]")
                np.testing.assert_array_equal(A1, A2, err_msg=f"{p}[{i},{s}]")
                assert x1.shape == (inst.N, inst.M, inst.H + 1)
                assert m1 == m2


def test_policy_matches_legacy_policy_grid():
    insts = hetero_insts()[:2]
    stacked = stack_instances(insts)
    uniforms = CC.policy_uniforms(stacked, 0, S, BO)
    gat = CC.gat_grid_policies(stacked, 0, 5)
    dev = CC.policy_grid_device(stacked, seed=0, pdhg_iters=ITERS,
                                best_of=BO, n_seeds=S, uniforms=uniforms,
                                gat=gat)
    res = run_grid(GridSpec(kind="policy", insts=insts, seed=0, n_seeds=S,
                            best_of=BO, pdhg_iters=ITERS, episodes=5,
                            max_buckets=1))
    for p in CC.OFFLINE_POLICIES:
        for i, inst in enumerate(insts):
            for s in range(S):
                x_n, A_n, _ = res.results[p][i][s]
                np.testing.assert_array_equal(
                    dev[p]["x"][i, s, :inst.N], x_n)
                np.testing.assert_array_equal(
                    dev[p]["A"][i, s, :inst.N, :inst.U], A_n)


@pytest.mark.parametrize("layout", [dict(chunk_size=2), dict(devices=1)],
                         ids=["chunked", "devices1"])
def test_policy_chunked_and_sharded_match_one_chunk(layout):
    """All five policies, streamed in chunks of 2 or run under shard_map
    on a 1-device mesh, make the decisions of one no-mesh chunk."""
    insts = hetero_insts()[:3]
    kw = dict(kind="policy", insts=insts, seed=0, n_seeds=S, best_of=BO,
              pdhg_iters=ITERS, episodes=5, max_buckets=1)
    ref = run_grid(GridSpec(**kw))
    out = run_grid(GridSpec(**kw, **layout))
    assert ref.stats["chunks"] == 1
    assert out.stats["chunks"] == (2 if "chunk_size" in layout else 1)
    for p in CC.OFFLINE_POLICIES:
        assert_same_offline(ref.results[p], out.results[p])
    np.testing.assert_array_equal(ref.stats["lp_obj"], out.stats["lp_obj"])


# ---------------------------------------------------------------------------
# online kind: shape-bucketed scan grids
# ---------------------------------------------------------------------------

OCFG = OnlineConfig(n_slots=12, rounds=2)


def _online_jobs():
    # twin of benchmarks/bench_scale.py::_online_jobs — the CI bench gates
    # the same mixed-shape grid this asserts on; keep them in sync
    cfg_a = MECConfig(n_bs=3, n_users=40, n_models=4, seed=0)
    cfg_b = MECConfig(n_bs=4, n_users=30, n_models=4, seed=1)
    tr_a = make_trace("stationary", cfg_a, OCFG.n_slots, seed=0)
    tr_b = make_trace("flash_crowd", cfg_b, OCFG.n_slots, seed=1)
    return ([dict(cfg=cfg_a, algo=a, trace=tr_a)
             for a in ("cocar-ol", "lfu", "random")]
            + [dict(cfg=cfg_b, algo=a, trace=tr_b, seed=1)
               for a in ("cocar-ol", "lfu-mad")])


def test_online_bucketed_grid_matches_solo_runs():
    jobs = _online_jobs()
    res = run_grid(GridSpec(kind="online", jobs=jobs, ocfg=OCFG))
    assert len(res.results) == len(jobs)
    assert len(res.stats["plan"]) == 2     # two shape buckets
    for j, g in zip(jobs, res.results):
        from repro.core.online import run_online
        solo = run_online(j["trace"], j["algo"], cfg=j["cfg"], ocfg=OCFG,
                          engine="scan", seed=j.get("seed", 0))
        np.testing.assert_array_equal(g["slot_qoe"], solo["slot_qoe"])
        np.testing.assert_array_equal(g["final_state"].lvl,
                                      solo["final_state"].lvl)
        np.testing.assert_array_equal(g["final_state"].O,
                                      solo["final_state"].O)


def test_online_sharded_chunked_matches_vmap():
    jobs = _online_jobs()
    ref = run_grid(GridSpec(kind="online", jobs=jobs, ocfg=OCFG))
    out = run_grid(GridSpec(kind="online", jobs=jobs, ocfg=OCFG,
                            devices=1, chunk_size=2))
    for a, b in zip(ref.results, out.results):
        np.testing.assert_array_equal(a["slot_qoe"], b["slot_qoe"])
        np.testing.assert_array_equal(a["final_state"].lvl,
                                      b["final_state"].lvl)
    assert run_grid(GridSpec(kind="online", jobs=[], ocfg=OCFG)).results \
        == []


# ---------------------------------------------------------------------------
# spec validation + progress reporting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec,error,match", [
    (dict(kind="nope", insts="hetero"), ValueError, "unknown grid kind"),
    (dict(kind="offline", insts=[]), ValueError, "spec.insts"),
    (dict(kind="online"), ValueError, "spec.jobs"),
    (dict(kind="offline", insts="hetero", devices=0), ValueError,
     "mesh axes must be >= 1"),
    (dict(kind="offline", insts="hetero", devices="visible+1"),
     RuntimeError, "xla_force_host_platform"),
], ids=["unknown_kind", "empty_insts", "missing_jobs", "devices_0",
        "devices_above_visible"])
def test_run_grid_validates_spec(spec, error, match):
    import jax

    spec = dict(spec)
    if spec.get("insts") == "hetero":
        spec["insts"] = hetero_insts()
    if spec.get("devices") == "visible+1":
        spec["devices"] = len(jax.devices()) + 1
    with pytest.raises(error, match=match):
        run_grid(GridSpec(**spec))


def test_progress_callback_sees_every_chunk():
    seen = []
    offline_grid(max_buckets=2, chunk_size=2, progress=seen.append)
    assert len(seen) >= 3                  # 5 instances, 2 buckets, chunk 2
    assert all(ev["batch"] > 0 and ev["seconds"] >= 0 for ev in seen)
    assert {ev["bucket"] for ev in seen} == {(3, 20), (4, 24)}
