"""Request-level telemetry: streaming metrics, event log, watermarks.

Four layers, mirroring the subsystem:

  * histograms/counters/gauges — percentile math, serialization
    roundtrips, and the merge laws (associative + commutative, property
    tested) that let per-run registries fold in any order;
  * the Prometheus textfile exporter, validated with the same parser
    ``scripts/check_metrics.py`` runs as a CI gate;
  * the per-request event log and its conservation law (every arrival
    terminates exactly once as finish | miss | drop);
  * the stack taps — QueueSim attribution exactness and decision
    inertness, online diagnostics folding, executor memory watermarks.
"""
import importlib.util
import json
import pathlib
import sys
import time

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.obs import (COUNT_EDGES, UNIT_EDGES, Counter, EventLog, Gauge,
                       Histogram, MetricsRegistry, memory_snapshot,
                       observe_online_diag, observe_queue_sim)


def _check_metrics_mod():
    path = (pathlib.Path(__file__).resolve().parent.parent
            / "scripts" / "check_metrics.py")
    spec = importlib.util.spec_from_file_location("obs_check_metrics", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["obs_check_metrics"] = mod
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# histograms
# ---------------------------------------------------------------------------

def test_histogram_counts_and_percentiles():
    h = Histogram("lat", edges=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 1.5, 3.0, 9.0):
        h.observe(v)
    assert h.n == 5 and h.counts == [1, 2, 1, 1]
    assert h.mean == pytest.approx((0.5 + 1.5 + 1.5 + 3.0 + 9.0) / 5)
    # percentiles stay inside the observed range and are monotone in q
    qs = [h.percentile(q) for q in (1, 25, 50, 75, 99)]
    assert all(0.5 <= v <= 9.0 for v in qs)
    assert qs == sorted(qs)
    # empty histogram pins to zero, not NaN
    assert Histogram("e").percentile(50) == 0.0
    assert Histogram("e").mean == 0.0


def test_histogram_percentile_single_value():
    h = Histogram("one", edges=(1.0, 2.0))
    h.observe(1.5, count=100)
    for q in (1, 50, 99):
        assert h.percentile(q) == pytest.approx(1.5)


def test_histogram_roundtrip_and_bad_edges():
    h = Histogram("x", edges=(0.1, 0.2))
    h.observe(0.15)
    h2 = Histogram.from_dict(json.loads(json.dumps(h.to_dict())))
    assert (h2.edges, h2.counts, h2.n, h2.total) == \
        (h.edges, h.counts, h.n, h.total)
    assert (h2.vmin, h2.vmax) == (h.vmin, h.vmax)
    with pytest.raises(ValueError):
        Histogram("bad", edges=(2.0, 1.0))
    with pytest.raises(ValueError):
        Histogram("bad", edges=())
    with pytest.raises(ValueError):
        h.merge(Histogram("other", edges=(0.1, 0.2, 0.3)))


def _merged(parts):
    out = Histogram("m", edges=(0.25, 0.5, 1.0))
    for p in parts:
        out.merge(p)
    return out


def _hist_of(values):
    h = Histogram("m", edges=(0.25, 0.5, 1.0))
    h.observe_many(values)
    return h


def _state(h):
    return (h.counts, h.n, h.total, h.vmin, h.vmax)


@settings(max_examples=50, deadline=None)
@given(st.lists(st.floats(min_value=0.0, max_value=2.0), min_size=1,
                max_size=8),
       st.lists(st.floats(min_value=0.0, max_value=2.0), min_size=1,
                max_size=8),
       st.lists(st.floats(min_value=0.0, max_value=2.0), min_size=1,
                max_size=8))
def test_histogram_merge_associative_commutative(a, b, c):
    """Merging per-run histograms is order-independent: (a+b)+c ==
    a+(b+c) == any permutation == observing the concatenation."""
    ha, hb, hc = _hist_of(a), _hist_of(b), _hist_of(c)
    left = _merged([_merged([_hist_of(a), _hist_of(b)]), _hist_of(c)])
    right = _merged([_hist_of(a), _merged([_hist_of(b), _hist_of(c)])])
    perm = _merged([hc, ha, hb])
    pooled = _hist_of(list(a) + list(b) + list(c))
    assert _state(left) == _state(right) == _state(perm)
    assert _state(left)[:2] == _state(pooled)[:2]
    assert left.total == pytest.approx(pooled.total)
    assert (left.vmin, left.vmax) == (pooled.vmin, pooled.vmax)


# ---------------------------------------------------------------------------
# counters / gauges / registry
# ---------------------------------------------------------------------------

def test_counter_and_gauge_semantics():
    c = Counter("c")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = Gauge("g")
    g.set(5.0)
    g.set(2.0)
    assert g.value == 2.0 and g.hwm == 5.0       # high-water mark sticks


def test_registry_merge_and_redeclare():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.histogram("h", (1.0, 2.0)).observe(0.5)
    b.histogram("h", (1.0, 2.0)).observe(1.5)
    b.histogram("only_b", (1.0,)).observe(0.1)
    a.counter("n").inc(3)
    b.counter("n").inc(4)
    a.gauge("mem").set(10.0)
    b.gauge("mem").set(7.0)
    a.merge(b)
    assert a.histogram("h", (1.0, 2.0)).n == 2
    assert a.histogram("only_b", (1.0,)).n == 1
    assert a.counters["n"].value == 7
    assert a.gauges["mem"].value == 10.0 and a.gauges["mem"].hwm == 10.0
    with pytest.raises(ValueError):
        a.histogram("h", (1.0, 3.0))             # edge re-declare mismatch
    # roundtrip keeps the whole registry mergeable
    back = MetricsRegistry.from_dict(
        json.loads(json.dumps(a.to_dict())))
    assert back.to_dict() == a.to_dict()


def test_prometheus_export_passes_schema_gate(tmp_path):
    """The exporter's textfile must satisfy the exact parser ci.sh runs
    (cumulative buckets, +Inf == _count, typed samples)."""
    cm = _check_metrics_mod()
    reg = MetricsRegistry()
    reg.histogram("request_latency_seconds").observe_many(
        [0.004, 0.09, 1.7, 80.0])                # incl. overflow bucket
    reg.counter("requests_served_total").inc(4)
    reg.gauge("online_cache_mb").set(123.5)
    path = tmp_path / "m.prom"
    reg.export_prometheus(path)
    assert cm.check_file(path, require=("repro_request_latency_seconds",
                                        "repro_requests_served_total")) == []
    fams = cm.parse_textfile(path.read_text())
    hist = fams["repro_request_latency_seconds"]
    assert hist["type"] == "histogram"
    inf = [v for n, lb, v in hist["samples"]
           if n.endswith("_bucket") and '+Inf' in lb]
    assert inf == [4.0]
    # a doctored file (broken cumulativity) must FAIL the gate
    text = path.read_text().replace(
        'repro_request_latency_seconds_bucket{le="+Inf"} 4',
        'repro_request_latency_seconds_bucket{le="+Inf"} 2')
    bad = tmp_path / "bad.prom"
    bad.write_text(text)
    assert cm.check_file(bad) != []
    # and a missing required family is reported
    errs = cm.check_file(path, require=("repro_absent_total",))
    assert any("repro_absent_total" in e for e in errs)


# ---------------------------------------------------------------------------
# event log conservation
# ---------------------------------------------------------------------------

def _emit_lifecycle(log, rid, terminal="finish"):
    log.emit("arrival", rid, 0.0)
    log.emit("route", rid, 0.0, chosen=0)
    log.emit(terminal, rid, 1.0)


def test_event_log_conservation_ok(tmp_path):
    log = EventLog()
    log.new_run("a")
    _emit_lifecycle(log, 0, "finish")
    _emit_lifecycle(log, 1, "miss")
    log.new_run("b")
    _emit_lifecycle(log, 0, "drop")              # same rid, new run: fine
    c = log.conservation()
    assert c["ok"] and c["n_arrivals"] == c["n_terminals"] == 3
    assert c["by_kind"]["arrival"] == 3 and c["by_kind"]["route"] == 3
    # jsonl roundtrip preserves the verdict
    p = log.export_jsonl(tmp_path / "ev.jsonl")
    back = EventLog.read_jsonl(p)
    assert len(back) == len(log)
    assert back.conservation() == c


def test_event_log_conservation_failures():
    log = EventLog()
    log.new_run()
    log.emit("arrival", 0, 0.0)                  # never terminated
    log.emit("arrival", 1, 0.0)
    log.emit("finish", 1, 1.0)
    log.emit("finish", 1, 2.0)                   # double-terminated
    log.emit("drop", 2, 0.0)                     # orphan terminal
    c = log.conservation()
    assert not c["ok"]
    assert (c["unterminated"], c["orphans"], c["duplicates"]) == (1, 1, 1)
    with pytest.raises(ValueError):
        log.emit("teleport", 3, 0.0)


# ---------------------------------------------------------------------------
# stack taps
# ---------------------------------------------------------------------------

def test_observe_queue_sim_matches_sim_state():
    from repro import configs
    from repro.serving.simulator import QueueSim, poisson_arrivals

    from repro.models import partition
    cfgs = {"a": configs.get_smoke("qwen1.5-0.5b")}
    c = partition.submodel_flops_per_token(cfgs["a"], 0, ctx=64)
    sim = QueueSim(cfgs, {0: {"a": 0}}, 64 * c / 0.05)
    arr = poisson_arrivals(50.0, 5.0, ["a"], [1.0], tokens=64, seed=3)
    m = sim.run(arr)
    reg = MetricsRegistry()
    observe_queue_sim(reg, sim)
    assert reg.histogram("request_latency_seconds").n == m["served"]
    assert reg.counters["requests_served_total"].value == m["served"]
    assert reg.counters["requests_dropped_total"].value == m["dropped"]
    assert reg.counters["deadline_misses_total"].value == \
        m["deadline_misses"]
    # histogram mass telescopes exactly like the attribution identity
    parts = sum(reg.histogram(f"request_{ph}_seconds").total
                for ph in ("queue", "stall", "service"))
    assert parts == pytest.approx(
        reg.histogram("request_latency_seconds").total, abs=1e-9)


def test_observe_online_diag_folds_curves():
    reg = MetricsRegistry()
    diag = {"hit_rate": np.array([0.25, 0.75, 1.0]),
            "dl_in_flight": np.array([0.0, 2.0, 1.0]),
            "evictions": np.array([0.0, 3.0, 1.0]),
            "cache_mb": np.array([100.0, 180.0, 120.0])}
    observe_online_diag(reg, diag)
    assert reg.histogram("online_hit_rate", UNIT_EDGES).n == 3
    assert reg.histogram("online_dl_in_flight", COUNT_EDGES).n == 3
    assert reg.counters["online_evictions_total"].value == 4.0
    g = reg.gauges["online_cache_mb"]
    assert g.value == 120.0 and g.hwm == 180.0   # final value, peak hwm


def test_memory_snapshot_host_and_device():
    snap = memory_snapshot()
    assert snap["host_rss_kb"] > 0
    assert snap["host_maxrss_kb"] > 0
    import jax.numpy as jnp
    keep = jnp.zeros((1024,), jnp.float32) + 1   # ensure a live array
    snap2 = memory_snapshot()
    assert snap2["device_live_bytes"] >= keep.nbytes
    assert snap2["device_live_arrays"] >= 1


def test_executor_watermarks_decision_inert():
    """diagnostics=True adds peak memory watermarks to executor stats
    (and per-chunk span attrs) without changing a single decision."""
    from harness import assert_same_offline, make_instance

    from repro.obs import tracing as OT
    from repro.scale import GridSpec, run_grid

    insts = [make_instance(seed=s, n_users=20) for s in (0, 1)]
    kw = dict(kind="offline", insts=insts, seed=0, n_seeds=1, best_of=2,
              pdhg_iters=150)
    off = run_grid(GridSpec(**kw))
    t0 = time.perf_counter()
    on = run_grid(GridSpec(**kw, diagnostics=True))
    assert_same_offline(off.results, on.results)
    for k in ("peak_host_rss_kb", "peak_host_maxrss_kb",
              "peak_device_live_bytes"):
        assert k in on.stats, k
        assert k not in off.stats                # skipped when off
    assert on.stats["peak_host_rss_kb"] > 0
    # every chunk span of the diagnostics run carries the watermarks
    chunks = [s for s in OT.TRACER.since(t0) if s.name == "chunk"]
    assert chunks
    for s in chunks:
        assert "host_rss_kb" in s.attrs
        assert "device_live_bytes" in s.attrs
