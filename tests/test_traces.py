"""Trace workload subsystem: generator determinism/shape/semantics, and
NumPy-vs-scan engine equivalence (per-slot QoE, final cache state, and the
download state machine edge cases, Eqs. 35-37)."""
import numpy as np
import pytest

from repro.core.online import OnlineConfig, OnlineSim, run_online
from repro.mec.scenario import MECConfig
from repro.traces import available, draw_decision_stream, make_trace
from repro.traces import engine as E

# one shared shape so every jitted variant compiles once per test session
CFG = MECConfig(n_users=60)
OCFG = OnlineConfig(n_slots=20)
T, U, N, M = OCFG.n_slots, CFG.n_users, CFG.n_bs, CFG.n_models


# ---------------------------------------------------------------------------
# generators
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", available())
def test_trace_shapes_and_determinism(name):
    tr1 = make_trace(name, CFG, T, seed=3)
    tr2 = make_trace(name, CFG, T, seed=3)
    tr3 = make_trace(name, CFG, T, seed=4)
    assert tr1.model.shape == tr1.home.shape == tr1.mask.shape == (T, U)
    assert tr1.model.min() >= 0 and tr1.model.max() < M
    assert tr1.home.min() >= 0 and tr1.home.max() < N
    # pure function of the key
    np.testing.assert_array_equal(tr1.model, tr2.model)
    np.testing.assert_array_equal(tr1.home, tr2.home)
    np.testing.assert_array_equal(tr1.mask, tr2.mask)
    assert not (np.array_equal(tr1.model, tr3.model)
                and np.array_equal(tr1.home, tr3.home))


def test_counts_match_requests():
    tr = make_trace("diurnal", CFG, T, seed=1, min_load=0.3)
    counts = tr.counts(N, M)
    assert counts.shape == (T, N, M)
    for t in (0, T // 2, T - 1):
        m_u, home = tr.requests(t)
        ref = np.zeros((N, M))
        np.add.at(ref, (home, m_u), 1.0)
        np.testing.assert_array_equal(counts[t], ref)
    assert counts.sum() == tr.mask.sum()


def test_drift_changes_popularity():
    tr = make_trace("drift", CFG, 80, seed=0, change_every=40, warmup=0)
    h1 = np.bincount(tr.model[:35].ravel(), minlength=M)
    h2 = np.bincount(tr.model[45:].ravel(), minlength=M)
    # distributions across periods differ substantially
    tv = 0.5 * np.abs(h1 / h1.sum() - h2 / h2.sum()).sum()
    assert tv > 0.1


def test_flash_crowd_concentrates_demand():
    tr = make_trace("flash_crowd", CFG, T, seed=2, n_events=1,
                    duration=10, intensity=0.9)
    ev = tr.meta["events"][0]
    spike = tr.model[ev["start"]:ev["end"]]
    share = (spike == ev["model"]).mean()
    assert share > 0.6                      # ~0.9 by construction
    calm = np.concatenate([tr.model[:ev["start"]], tr.model[ev["end"]:]])
    if calm.size:
        assert (calm == ev["model"]).mean() < share


def test_diurnal_load_oscillates():
    tr = make_trace("diurnal", CFG, 50, seed=0, period=50, min_load=0.1)
    load = tr.mask.mean(1)
    assert load.max() > 0.7 and load.min() < 0.4


def test_mobility_handover():
    tr = make_trace("mobility", CFG, T, seed=0, p_move=0.2)
    assert tr.meta["handovers"] > 0
    # homes persist between moves: consecutive-slot agreement far above iid
    agree = (tr.home[1:] == tr.home[:-1]).mean()
    assert agree > 0.5


def test_mmpp_burst_metadata():
    tr = make_trace("mmpp", CFG, 100, seed=1)
    assert 0 < tr.meta["burst_slots"] < 100
    assert tr.mask.any() and not tr.mask.all()


def test_flash_crowd_overlapping_events_compose():
    from repro.traces.generators import flash_crowd
    tr = flash_crowd(0, 20, U, N, M, n_events=2, duration=15,
                     intensity=0.8)
    e1, e2 = tr.meta["events"]
    lo, hi = max(e1["start"], e2["start"]), min(e1["end"], e2["end"])
    if hi > lo and e1["model"] != e2["model"]:       # overlap happened
        overlap = tr.model[lo:hi]
        # both hot models elevated above the 1/M baseline in the overlap
        assert (overlap == e1["model"]).mean() > 1.2 / M
        assert (overlap == e2["model"]).mean() > 1.2 / M


def test_registry_unknown_name():
    with pytest.raises(KeyError):
        make_trace("nope", CFG, T)


def test_scenario_trace_hook():
    from repro.mec.scenario import Scenario
    sc = Scenario(CFG)
    tr = sc.trace("stationary", T)
    ref = make_trace("stationary", CFG, T, seed=CFG.seed)
    np.testing.assert_array_equal(tr.model, ref.model)
    np.testing.assert_array_equal(tr.home, ref.home)


def test_decision_stream_deterministic():
    s1 = draw_decision_stream(T, 3, N, M, seed=7)
    s2 = draw_decision_stream(T, 3, N, M, seed=7)
    np.testing.assert_array_equal(s1.adjust_ns, s2.adjust_ns)
    np.testing.assert_array_equal(s1.u_shrink, s2.u_shrink)
    assert s1.adjust_ns.shape == (T, 3)
    assert s1.perms.shape == (T, 3, M)
    assert sorted(s1.perms[0, 0]) == list(range(M))


# ---------------------------------------------------------------------------
# engine equivalence (the acceptance bar: per-slot QoE + final cache state
# match OnlineSim for all four policies on a fixed stationary trace)
# ---------------------------------------------------------------------------

def _numpy_reference(cfg, ocfg, algo, trace, stream):
    from repro.core.online import run_online_trace

    return run_online_trace(cfg, ocfg, algo, trace, stream)


STAT_TRACE = make_trace("stationary", CFG, T, seed=CFG.seed)
STREAM = draw_decision_stream(T, OCFG.rounds, N, M, CFG.seed + 99)


@pytest.mark.parametrize("algo", E.POLICIES)
def test_scan_matches_numpy(algo):
    qs, hs, sim = _numpy_reference(CFG, OCFG, algo, STAT_TRACE, STREAM)
    res = run_online(STAT_TRACE, algo, cfg=CFG, ocfg=OCFG,
                     engine="scan", stream=STREAM)
    np.testing.assert_allclose(res["slot_qoe"], qs, rtol=1e-9, atol=1e-9)
    np.testing.assert_array_equal(res["slot_hits"], hs)
    fs = res["final_state"]
    np.testing.assert_array_equal(fs.lvl, np.argmax(sim.X, -1))
    np.testing.assert_allclose(fs.O, sim.O, rtol=1e-9, atol=1e-9)
    np.testing.assert_array_equal(fs.target, sim.target)


def test_scan_matches_numpy_no_partition():
    ocfg = OnlineConfig(n_slots=T, partition=False)
    qs, _, sim = _numpy_reference(CFG, ocfg, "cocar-ol", STAT_TRACE, STREAM)
    res = run_online(STAT_TRACE, "cocar-ol", cfg=CFG, ocfg=ocfg,
                     engine="scan", stream=STREAM)
    np.testing.assert_allclose(res["slot_qoe"], qs, rtol=1e-9, atol=1e-9)
    np.testing.assert_array_equal(res["final_state"].lvl,
                                  np.argmax(sim.X, -1))


def test_grid_matches_single_runs():
    """vmapped grid (mixed traces x policies via lax.switch) == per-job
    NumPy runs, including jobs with a non-default seed (the grid's
    default-seed/stream derivation must match run_online's)."""
    drift_tr = make_trace("drift", CFG, T, seed=CFG.seed, change_every=8)
    jobs = [dict(cfg=CFG, algo=a, trace=STAT_TRACE, stream=STREAM)
            for a in ("cocar-ol", "lfu", "lfu-mad", "random")]
    # seed=5 jobs, no explicit stream: the grid must draw it from seed+99
    jobs += [dict(cfg=CFG, algo=a, trace=drift_tr, seed=5)
             for a in ("cocar-ol", "lfu", "lfu-mad", "random")]
    stream5 = draw_decision_stream(T, OCFG.rounds, N, M, 5 + 99)
    grid = E.run_online_grid(jobs, OCFG)
    assert len(grid) == 8
    from dataclasses import replace
    for job, g in zip(jobs, grid):
        cfg = replace(CFG, seed=job.get("seed", 0))   # as run_online does
        qs, _, sim = _numpy_reference(cfg, OCFG, job["algo"], job["trace"],
                                      job.get("stream", stream5))
        np.testing.assert_allclose(g["slot_qoe"], qs, rtol=1e-9, atol=1e-9)
        np.testing.assert_array_equal(g["final_state"].lvl,
                                      np.argmax(sim.X, -1))


def test_grid_mixed_shapes_bucketed():
    """Mixed (n_bs, n_models) grids — rejected before the scale executor
    — are now bucketed by shape, and every job still reproduces its solo
    scan run bit-exactly."""
    cfg2 = MECConfig(n_bs=4, n_users=60, n_models=M, seed=3)
    jobs = [dict(cfg=CFG, algo="lfu", trace=STAT_TRACE, stream=STREAM),
            dict(cfg=cfg2, algo="lfu", seed=3)]
    grid = E.run_online_grid(jobs, OCFG)
    assert len(grid) == 2
    solo0 = run_online(STAT_TRACE, "lfu", cfg=CFG, ocfg=OCFG,
                       engine="scan", stream=STREAM)
    from repro.traces.registry import default_trace
    solo1 = run_online(default_trace(cfg2, OCFG), "lfu", cfg=cfg2,
                       ocfg=OCFG, engine="scan", seed=3)
    np.testing.assert_array_equal(grid[0]["slot_qoe"], solo0["slot_qoe"])
    np.testing.assert_array_equal(grid[1]["slot_qoe"], solo1["slot_qoe"])
    np.testing.assert_array_equal(grid[1]["final_state"].lvl,
                                  solo1["final_state"].lvl)


def test_online_sweep_rows():
    from repro.experiments.sweep import run_online_sweep

    rows = run_online_sweep(
        base=CFG, axes={"mem_capacity_mb": (300.0, 500.0)},
        workloads=("stationary", "drift"), policies=("cocar-ol", "lfu"),
        ocfg=OCFG)
    assert len(rows) == 8
    for r in rows:
        assert set(r) == {"mem_capacity_mb", "workload", "family", "algo",
                          "avg_qoe", "hit_rate"}
        assert 0.0 <= r["avg_qoe"] <= 1.0


# ---------------------------------------------------------------------------
# download state machine edge cases — asserted identically on both engines
# ---------------------------------------------------------------------------

def _both_engines(sim):
    """Mirror a NumPy sim's download state into engine pytrees."""
    params = E.make_params(sim.cfg, sim.ocfg, sc=sim.sc)
    st = E.init_state(params, sim.ocfg.dT_past)
    st = st._replace(lvl=np.argmax(sim.X, -1).astype(np.int32),
                     O=sim.O.copy(),
                     target=sim.target.astype(np.int32))
    return params, st


def _routine_jax(params, st):
    import jax

    with jax.enable_x64(True):
        out = E._routine_update(params, st)
        return E.OnlineState(*(np.asarray(x) for x in out))


def test_one_slot_finishes_multiple_deltas_both_engines():
    """A slot budget large enough for several queued Δ components finishes
    them all; the cache jumps to the LAST finished submodel (Eq. 37)."""
    sim = OnlineSim(CFG, OCFG)
    s = sim.sc.sizes
    budget = sim.W[0] * OCFG.slot_s
    n, m = 0, 0
    # two tiny deltas well inside one budget + a third partial one
    d1, d2 = 0.2 * budget, 0.3 * budget
    sim.O[n, m, 0], sim.O[n, m, 1], sim.O[n, m, 2] = d1, d2, 2.0 * budget
    sim.target[n, m] = 3
    params, st = _both_engines(sim)
    sim.routine_update()
    out = _routine_jax(params, st)
    assert np.argmax(sim.X[n, m]) == 2          # h2 live, h3 still in flight
    np.testing.assert_array_equal(out.lvl, np.argmax(sim.X, -1))
    np.testing.assert_allclose(out.O, sim.O, rtol=1e-12, atol=1e-12)
    assert sim.O[n, m, 2] > 0                   # partial remains queued


def test_partial_cross_slot_download_both_engines():
    """A Δ bigger than one slot budget survives across slots, decremented
    exactly by the budget; no cache switch until it completes."""
    sim = OnlineSim(CFG, OCFG)
    budget = sim.W[0] * OCFG.slot_s
    n, m = 1, 2
    sim.O[n, m, 0] = 2.5 * budget
    sim.target[n, m] = 1
    params, st = _both_engines(sim)
    for _ in range(2):
        sim.routine_update()
        st = _routine_jax(params, st)
        np.testing.assert_array_equal(st.lvl, np.argmax(sim.X, -1))
        np.testing.assert_allclose(st.O, sim.O, rtol=1e-12, atol=1e-12)
        assert np.argmax(sim.X[n, m]) == 0      # still not servable
    sim.routine_update()
    st = _routine_jax(params, st)
    assert np.argmax(sim.X[n, m]) == 1          # third slot completes it
    np.testing.assert_array_equal(st.lvl, np.argmax(sim.X, -1))


def test_eviction_mid_download_both_engines():
    """LFU-style eviction can shrink a model while its download is in
    flight (Eq. 49 is immediate); when the download lands the cache jumps
    to the downloaded target on both engines."""
    sim = OnlineSim(CFG, OCFG)
    budget = sim.W[0] * OCFG.slot_s
    n, m = 0, 1
    sim.X[n, m, :] = 0
    sim.X[n, m, 2] = 1                          # cached at h2
    sim.O[n, m, 2] = 0.5 * budget               # upgrading h2 -> h3
    sim.target[n, m] = 3
    # mid-download eviction: cache shrunk to h0 while O is in flight
    sim.X[n, m, :] = 0
    sim.X[n, m, 0] = 1
    params, st = _both_engines(sim)
    sim.routine_update()
    out = _routine_jax(params, st)
    assert np.argmax(sim.X[n, m]) == 3          # landed download wins
    np.testing.assert_array_equal(out.lvl, np.argmax(sim.X, -1))
    np.testing.assert_allclose(out.O, sim.O, rtol=1e-12, atol=1e-12)
