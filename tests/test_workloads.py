"""The Workload API: protocol/coercion basics, streaming families, the
chunked engine, the legacy shims, and the aggregation-exactness property
tests (per-(BS, model) counts are an exact representation of Eq. 40/45-49
demand — only the summation order can differ)."""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.online import OnlineConfig, OnlineSim, run_online
from repro.mec.scenario import MECConfig
from repro.traces import (AggregatedWorkload, DenseWorkload, PoissonWorkload,
                          Trace, TraceLogWorkload, as_workload,
                          available_workloads, check_trace, check_workload,
                          default_stream, default_workload, make_trace,
                          make_workload)
from repro.traces import engine as E

CFG = MECConfig(n_users=50)
OCFG = OnlineConfig(n_slots=12)


def stat_workload(cfg=CFG, n_slots=OCFG.n_slots, seed=0):
    return DenseWorkload(make_trace("stationary", cfg, n_slots, seed=seed),
                         cfg.n_bs, cfg.n_models)


# ---------------------------------------------------------------- protocol

def test_dense_workload_counts_match_trace():
    wl = stat_workload()
    counts = wl.counts()
    assert counts.shape == (OCFG.n_slots, CFG.n_bs, CFG.n_models)
    assert counts.dtype == np.float64
    # every masked request lands in exactly one (BS, model) cell
    assert counts.sum() == wl.trace.mask.sum() == wl.total()
    assert wl.exact and wl.n_users == CFG.n_users


def test_iter_chunks_covers_horizon_in_order():
    wl = stat_workload()
    spans, parts = [], []
    for t0, t1, c in wl.iter_chunks(5):
        spans.append((t0, t1))
        parts.append(c)
        assert c.shape == (t1 - t0, CFG.n_bs, CFG.n_models)
    assert spans == [(0, 5), (5, 10), (10, 12)]
    np.testing.assert_array_equal(np.concatenate(parts), wl.counts())


def test_as_workload_coercions():
    wl = stat_workload()
    assert as_workload(wl) is wl
    dense = as_workload(wl.trace, cfg=CFG)
    assert isinstance(dense, DenseWorkload)
    np.testing.assert_array_equal(dense.counts(), wl.counts())
    agg = as_workload(wl.counts())
    assert isinstance(agg, AggregatedWorkload) and agg.exact
    np.testing.assert_array_equal(agg.counts(), wl.counts())
    with pytest.raises(ValueError, match="n_bs"):
        as_workload(wl.trace)           # no aggregation shape
    with pytest.raises(TypeError, match="cannot interpret"):
        as_workload({"not": "a workload"})
    with pytest.raises(ValueError, match="count tensor"):
        AggregatedWorkload(np.zeros((3, 4)))


def test_registry_builds_all_families():
    names = available_workloads()
    assert {"stationary", "poisson_zipf", "request_log"} <= set(names)
    for name in names:
        if name == "request_log":
            continue                    # needs log arrays, tested below
        kw = {"users_per_slot": 500.0} if name == "poisson_zipf" else {}
        wl = make_workload(name, CFG, OCFG.n_slots, seed=1, **kw)
        check_workload(wl, CFG, OCFG)
        assert wl.counts().shape == (OCFG.n_slots, CFG.n_bs, CFG.n_models)
    with pytest.raises(KeyError, match="poisson_zipf"):
        make_workload("nope", CFG, OCFG.n_slots)


# ------------------------------------------------------ streaming families

def test_poisson_chunk_layout_invariance():
    wl = PoissonWorkload(10, CFG.n_bs, CFG.n_models, 1e5, seed=3,
                         chunk_slots=4)
    whole = wl.counts()
    assert whole.shape == (10, CFG.n_bs, CFG.n_models)
    for step in (1, 3, 7, 10):
        parts = [c for _, _, c in wl.iter_chunks(step)]
        np.testing.assert_array_equal(np.concatenate(parts), whole)
    # counter-based keying: same seed reproduces, other seeds differ
    np.testing.assert_array_equal(
        PoissonWorkload(10, CFG.n_bs, CFG.n_models, 1e5, seed=3).counts(),
        whole)
    assert not np.array_equal(
        PoissonWorkload(10, CFG.n_bs, CFG.n_models, 1e5, seed=4).counts(),
        whole)


def test_poisson_mean_tracks_popularity():
    wl = PoissonWorkload(400, 3, 4, 1e4, seed=0, zipf=0.8)
    got = wl.counts().mean(axis=0)
    lam = 1e4 / 3 * wl.pop
    np.testing.assert_allclose(got, lam, rtol=0.05)


def test_trace_log_matches_dense_aggregation():
    rng = np.random.default_rng(7)
    n_req = 500
    slot = rng.integers(0, OCFG.n_slots, n_req)
    home = rng.integers(0, CFG.n_bs, n_req)
    model = rng.integers(0, CFG.n_models, n_req)
    wl = TraceLogWorkload(slot, home, model, n_slots=OCFG.n_slots,
                          n_bs=CFG.n_bs, n_models=CFG.n_models)
    ref = np.zeros((OCFG.n_slots, CFG.n_bs, CFG.n_models))
    np.add.at(ref, (slot, home, model), 1.0)
    np.testing.assert_array_equal(wl.counts(), ref)
    # chunk slices agree with the whole-horizon tensor
    for t0, t1, c in wl.iter_chunks(5):
        np.testing.assert_array_equal(c, ref[t0:t1])
    assert wl.total() == n_req
    with pytest.raises(ValueError, match="model"):
        TraceLogWorkload(slot, home, model + CFG.n_models,
                         n_slots=OCFG.n_slots, n_bs=CFG.n_bs,
                         n_models=CFG.n_models)
    with pytest.raises(ValueError, match="one entry per request"):
        TraceLogWorkload(slot[:-1], home, model, n_slots=OCFG.n_slots,
                         n_bs=CFG.n_bs, n_models=CFG.n_models)


def test_make_workload_request_log_family():
    wl = make_workload("request_log", CFG, OCFG.n_slots,
                       slot=[0, 0, 3], home=[1, 2, 0], model=[0, 1, 2])
    check_workload(wl, CFG, OCFG)
    assert wl.total() == 3 and wl.family == "request_log"


# ------------------------------------------------- engine: chunks + unified

def test_chunked_scan_bit_identical_to_one_shot():
    wl = stat_workload()
    stream = default_stream(CFG, OCFG, 0)
    one = run_online(wl, "cocar-ol", cfg=CFG, ocfg=OCFG, engine="scan",
                     stream=stream)
    for chunk in (1, 5, 7):
        ch = run_online(wl, "cocar-ol", cfg=CFG, ocfg=OCFG, engine="scan",
                        stream=stream, chunk_slots=chunk)
        np.testing.assert_array_equal(one["slot_qoe"], ch["slot_qoe"])
        np.testing.assert_array_equal(one["final_state"].lvl,
                                      ch["final_state"].lvl)


def test_unified_engines_agree():
    wl = stat_workload()
    stream = default_stream(CFG, OCFG, 0)
    a = run_online(wl, "lfu", cfg=CFG, ocfg=OCFG, engine="numpy",
                   stream=stream)
    b = run_online(wl, "lfu", cfg=CFG, ocfg=OCFG, engine="scan",
                   stream=stream)
    assert a["workload"] == b["workload"] == wl.name
    np.testing.assert_allclose(a["slot_qoe"], b["slot_qoe"], rtol=1e-9)
    np.testing.assert_array_equal(np.asarray(a["final_state"].lvl),
                                  np.asarray(b["final_state"].lvl))
    with pytest.raises(ValueError, match="engine"):
        run_online(wl, "lfu", cfg=CFG, ocfg=OCFG, engine="pallas")
    with pytest.raises(TypeError, match="cfg"):
        run_online(wl, "lfu")


def test_new_api_emits_no_deprecation_warning(recwarn):
    run_online(stat_workload(), "lfu", cfg=CFG, ocfg=OCFG, engine="numpy")
    assert not [w for w in recwarn.list
                if issubclass(w.category, DeprecationWarning)]


# ------------------------------------------------- error-message contracts

def test_check_trace_error_names_workload_and_family():
    tr = make_trace("flash_crowd", CFG, 8, seed=0)
    bad = OnlineConfig(n_slots=9)
    with pytest.raises(ValueError) as exc:
        check_trace(tr, CFG, bad)
    msg = str(exc.value)
    assert "flash_crowd" in msg                       # name AND family
    assert "make_trace('flash_crowd', cfg, n_slots=9" in msg
    assert "repro.traces.available()" in msg


def test_check_workload_error_names_family_and_registry():
    wl = PoissonWorkload(8, CFG.n_bs, CFG.n_models, 100.0, name="mega")
    with pytest.raises(ValueError) as exc:
        check_workload(wl, CFG, OCFG)
    msg = str(exc.value)
    assert "'mega'" in msg and "'poisson_zipf'" in msg
    assert f"make_workload('poisson_zipf', cfg, n_slots={OCFG.n_slots}" in msg
    assert "available_workloads" in msg
    wrong_shape = AggregatedWorkload(
        np.zeros((OCFG.n_slots, CFG.n_bs + 1, CFG.n_models)))
    with pytest.raises(ValueError, match="n_bs"):
        check_workload(wrong_shape, CFG, OCFG)


# ------------------------------------------------------ property: exactness

@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 50), t=st.integers(0, OCFG.n_slots - 1),
       family=st.sampled_from(["stationary", "flash_crowd", "mobility"]))
def test_aggregation_qoe_exactness(seed, t, family):
    """Counts-driven routing (Eq. 41 over aggregated demand) equals the
    per-user sum: same QoE within float summation-order drift, hits
    exactly (they are integer counts)."""
    trace = make_trace(family, CFG, OCFG.n_slots, seed=seed)
    sim = OnlineSim(CFG, OCFG, trace=trace)
    m_u, home = sim.draw_slot_requests(t)
    q_user, hits_user = sim.route(m_u, home)
    q_cnt, hits_cnt = sim.route_counts(sim.workload.counts()[t])
    assert hits_cnt == hits_user
    np.testing.assert_allclose(q_cnt, q_user, rtol=1e-9, atol=1e-12)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 50), perm_seed=st.integers(0, 10))
def test_user_permutation_invariance(seed, perm_seed):
    """Relabeling users changes nothing downstream: the aggregated count
    tensor is bit-identical, so every engine result is too."""
    trace = make_trace("stationary", CFG, OCFG.n_slots, seed=seed)
    perm = np.random.default_rng(perm_seed).permutation(CFG.n_users)
    permuted = Trace(name=trace.name, model=trace.model[:, perm],
                     home=trace.home[:, perm], mask=trace.mask[:, perm],
                     meta=dict(trace.meta))
    a = DenseWorkload(trace, CFG.n_bs, CFG.n_models)
    b = DenseWorkload(permuted, CFG.n_bs, CFG.n_models)
    np.testing.assert_array_equal(a.counts(), b.counts())
    stream = default_stream(CFG, OCFG, 0)
    ra = run_online(a, "cocar-ol", cfg=CFG, ocfg=OCFG, engine="scan",
                    stream=stream)
    rb = run_online(b, "cocar-ol", cfg=CFG, ocfg=OCFG, engine="scan",
                    stream=stream)
    np.testing.assert_array_equal(ra["slot_qoe"], rb["slot_qoe"])
    np.testing.assert_array_equal(ra["final_state"].lvl,
                                  rb["final_state"].lvl)
