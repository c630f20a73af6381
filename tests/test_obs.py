"""Observability subsystem (``repro.obs``) contract tests.

Three layers, mirroring the subsystem:

  * host-side tracing/manifests — pure-python span nesting, exports,
    retrace accounting, and run provenance (no jax required);
  * jit-safe diagnostics taps — the solver/engine curves must be
    DECISION-INERT: bit-identical x/A/QoE with the tap on or off, on the
    reference kernel, the fused kernel's scan engine, the offline and
    policy device grids, the online scan, and the sharded executor;
  * regression invariants — repeat sweeps retrace nothing
    (compile-cache deltas stay zero), ``solve_lp_pdhg`` carries an
    honest ``converged`` flag, and ``scripts/report.py`` renders the
    artifacts and gates on convergence.
"""
import importlib.util
import json
import pathlib
import sys

import numpy as np
import pytest
from harness import assert_same_offline, make_instance

from repro.obs import (Tracer, config_hash, lp_diag_summary,
                       register_jit, retrace_snapshot, run_manifest,
                       total_retraces_since, write_manifest)

ITERS = 150          # truncated solver budget: cheap, deterministic


# ---------------------------------------------------------------------------
# tracing (pure host)
# ---------------------------------------------------------------------------

def test_span_nesting_and_summary():
    tr = Tracer()
    with tr.span("outer", kind="test"):
        with tr.span("inner") as sp:
            assert sp.depth == 1
    spans = tr.spans
    assert [s.name for s in spans] == ["outer", "inner"]
    assert spans[0].depth == 0 and spans[0].parent == -1
    assert spans[1].parent == spans[0].index == 0
    assert spans[0].seconds >= spans[1].seconds >= 0.0
    assert spans[0].attrs == {"kind": "test"}


def test_span_exports(tmp_path):
    tr = Tracer()
    with tr.span("a", n=1):
        with tr.span("b"):
            pass
    jl = tr.export_jsonl(tmp_path / "t.trace.jsonl")
    rows = [json.loads(line) for line in
            pathlib.Path(jl).read_text().splitlines()]
    assert [r["name"] for r in rows] == ["a", "b"]
    assert rows[0]["attrs"] == {"n": 1}
    assert rows[1]["parent"] == rows[0]["index"]
    assert all(r["cpu_seconds"] >= 0.0 for r in rows)


def test_span_cpu_time_and_profiler_annotation(tmp_path):
    """A span records its thread's CPU time beside its wall time, and
    appears by name as a host event in a ``jax.profiler`` trace."""
    import time

    import jax
    from jax.profiler import ProfileData

    tr = Tracer()
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("obs.test.busy") as busy:
            t = time.perf_counter()
            while time.perf_counter() - t < 0.02:
                pass
        with tr.span("obs.test.sleep") as sleep:
            time.sleep(0.05)
    # where the kernel counts thread CPU time in 10 ms ticks, a reading
    # can pass the wall time by up to one tick
    assert 0.0 < busy.cpu_seconds <= busy.seconds + 0.011
    assert sleep.seconds >= 0.05 and sleep.cpu_seconds < 0.025
    paths = list(tmp_path.rglob("*.xplane.pb"))
    assert len(paths) == 1
    found = {}
    for plane in ProfileData.from_file(str(paths[0])).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("obs.test."):
                    found[ev.name] = (plane.name, ev.duration_ns * 1e-9)
    assert set(found) == {"obs.test.busy", "obs.test.sleep"}
    assert all(plane.startswith("/host:") for plane, _ in found.values())
    assert found["obs.test.sleep"][1] == pytest.approx(sleep.seconds,
                                                       abs=5e-3)


def test_span_name_reserved_prefix():
    with pytest.raises(ValueError, match="bench:"):
        with Tracer().span("bench:pipeline"):
            pass


def test_no_program_span_takes_reserved_prefix():
    """No span that the repo's code opens starts with the benchmark
    harness's prefix, which ``Tracer.span`` refuses."""
    import re

    from repro.obs.tracing import RESERVED_PREFIX

    root = pathlib.Path(__file__).resolve().parent.parent
    files = [p for d in ("src", "benchmarks", "examples", "scripts")
             for p in sorted((root / d).rglob("*.py"))]
    files += sorted(root.glob("*.py"))
    opened = re.compile(r"""span\(\s*f?["']([^"']*)""")
    names = [(p.name, m.group(1)) for p in files
             for m in opened.finditer(p.read_text())]
    assert ("bench_scale.py", "scale:one_device") in names
    assert [n for n in names if n[1].startswith(RESERVED_PREFIX)] == []


def test_tracer_ring_bound_since_and_parents():
    """Past its bound the tracer keeps the newest spans; ``since`` and
    every kept span's ``parent`` stay right across the wrap."""
    import time

    from repro.obs.tracing import MAX_SPANS

    tr = Tracer()
    marks = {}
    for i in range(5000):                        # 10 000 spans
        if i == 4500:
            marks["t"] = time.perf_counter()
        with tr.span("outer", count_retraces=False, i=i):
            with tr.span("inner", count_retraces=False, i=i):
                pass
    spans = tr.spans
    assert len(spans) == MAX_SPANS
    assert [s.index for s in spans] == list(range(10000 - MAX_SPANS, 10000))
    by_index = {s.index: s for s in spans}
    for s in spans:
        if s.name == "inner":
            if s.parent in by_index:
                parent = by_index[s.parent]
                assert parent.name == "outer"
                assert parent.attrs["i"] == s.attrs["i"]
            assert s.parent == s.index - 1
        else:
            assert s.parent == -1
    recent = tr.since(marks["t"])
    assert len(recent) == 1000
    assert recent[0].attrs["i"] == 4500 and recent[0].name == "outer"
    assert [s.index for s in recent] == list(range(9000, 10000))
    assert tr.since(time.perf_counter()) == []
    assert len(tr.since(0.0)) == MAX_SPANS


def test_retrace_accounting_via_registry():
    class FakeJit:
        def __init__(self):
            self.n = 0

        def _cache_size(self):
            return self.n

    fn = FakeJit()
    register_jit("test:fake", fn)
    tr = Tracer()
    snap = retrace_snapshot()
    with tr.span("warm") as sp:
        fn.n += 2                                # "compiled twice"
    assert sp.retraces == 2
    assert total_retraces_since(snap) == 2
    with tr.span("hot") as sp:
        pass                                     # no new executables
    assert sp.retraces == 0


# ---------------------------------------------------------------------------
# manifests
# ---------------------------------------------------------------------------

def test_run_manifest_fields():
    m = run_manifest(config={"a": 1}, seeds={"seed": 0})
    assert m["schema"] == "repro.obs.manifest/v1"
    assert m["git"] is None or "sha" in m["git"]
    assert m["python"].count(".") >= 1          # "3.10.x" version string
    assert m["seeds"] == {"seed": 0}
    assert m["config_hash"] == config_hash({"a": 1})
    # jax block is honest about whether jax was imported
    assert m["jax"]["imported"] in (True, False)


def test_config_hash_stable_under_key_order():
    assert config_hash({"a": 1, "b": 2}) == config_hash({"b": 2, "a": 1})
    assert config_hash({"a": 1}) != config_hash({"a": 2})


def test_write_manifest_sibling(tmp_path):
    res = tmp_path / "grid.json"
    res.write_text("[]")
    p = write_manifest(res, config={"k": 1})
    assert pathlib.Path(p).name == "grid.manifest.json"
    m = json.loads(pathlib.Path(p).read_text())
    assert m["config"] == {"k": 1}


# ---------------------------------------------------------------------------
# solver diagnostics: convergence flag + decision inertness
# ---------------------------------------------------------------------------

def test_solve_lp_pdhg_converged_flag():
    from repro.core import lp as LP

    inst = make_instance(n_users=24)
    full = LP.solve_lp_pdhg(inst, iters=4000)
    assert full.converged and full.tol == LP.PDHG_TOL
    short = LP.solve_lp_pdhg(inst, iters=20, check_every=10)
    assert not short.converged
    assert short.primal_res > short.tol


def test_reference_diag_inert_and_curves():
    from repro.core import lp as LP

    inst = make_instance(n_users=24)
    off = LP.solve_lp_pdhg(inst, iters=ITERS, check_every=40)
    on = LP.solve_lp_pdhg(inst, iters=ITERS, check_every=40,
                          diagnostics=True)
    np.testing.assert_array_equal(off.x, on.x)
    np.testing.assert_array_equal(off.A, on.A)
    d = on.diag
    # ITERS=150, stride 40 -> samples at 40, 80, 120 and the final 150
    assert list(d["iters"]) == [40, 80, 120, 150]
    assert d["primal_res"].shape == d["dual_res"].shape == d["obj"].shape
    summ = lp_diag_summary(d)
    assert summ["final_residual"] == pytest.approx(float(
        d["primal_res"][-1]))
    assert summ["n_samples"] == 4


def test_fused_scan_diag_inert():
    import jax
    import jax.numpy as jnp

    from repro.core import lp as LP
    from repro.kernels.pdhg_fused import pdhg_fused

    inst = make_instance(n_users=24)
    data = jax.tree_util.tree_map(jnp.asarray, LP.pdhg_data(inst))
    x0, A0 = pdhg_fused(data, ITERS, engine="scan")
    out = pdhg_fused(data, ITERS, engine="scan", diagnostics=True,
                     diag_stride=40)
    np.testing.assert_array_equal(np.asarray(x0), np.asarray(out[0]))
    np.testing.assert_array_equal(np.asarray(A0), np.asarray(out[1]))
    d = out[2]
    assert float(d["polish_delta"]) >= 0.0
    assert d["primal_res"].shape == d["iters"].shape


def test_offline_grid_diag_inert():
    from repro.core.cocar import cocar_grid

    insts = [make_instance(seed=s, n_users=20) for s in (0, 1)]
    kw = dict(seed=0, pdhg_iters=ITERS, best_of=2, n_seeds=2)
    off = cocar_grid(insts, backend="device", **kw)
    on = cocar_grid(insts, backend="device", diagnostics=True, **kw)
    assert_same_offline(off, on)
    summ = on[0][0][2]["lp_diag"]["summary"]
    assert {"final_residual", "converged", "iters_to_tol"} <= set(summ)


def test_sharded_grid_diag_inert():
    from repro.core.cocar import cocar_grid

    insts = [make_instance(seed=s, n_users=20) for s in (0, 1, 2)]
    kw = dict(seed=0, pdhg_iters=ITERS, best_of=2, n_seeds=1)
    off = cocar_grid(insts, backend="device", **kw)
    on = cocar_grid(insts, backend="sharded", diagnostics=True, **kw)
    assert_same_offline(off, on)
    assert "lp_diag" in on[0][0][2]


def test_policy_grid_diag_inert():
    from repro.scale import GridSpec, run_grid

    insts = [make_instance(seed=s, n_users=20) for s in (0, 1)]
    kw = dict(kind="policy", insts=insts, seed=0, n_seeds=1, best_of=2,
              pdhg_iters=ITERS, episodes=4, backend="vmap")
    off = run_grid(GridSpec(**kw))
    on = run_grid(GridSpec(**kw, diagnostics=True))
    for p in off.results:
        assert_same_offline(off.results[p], on.results[p])
    diags = on.stats["lp_diag"]
    assert len(diags) == len(insts)
    assert all("final_residual" in d for d in diags)
    assert "lp_diag" not in off.stats


def test_online_scan_diag_inert():
    from repro.core.online import OnlineConfig, run_online
    from repro.mec.scenario import MECConfig
    from repro.traces.registry import default_workload

    cfg = MECConfig(n_bs=3, n_users=30, n_models=4, seed=0)
    ocfg = OnlineConfig(n_slots=12, rounds=2)
    wl = default_workload(cfg, ocfg)
    off = run_online(wl, "cocar-ol", cfg=cfg, ocfg=ocfg, engine="scan")
    on = run_online(wl, "cocar-ol", cfg=cfg, ocfg=ocfg, engine="scan",
                    diagnostics=True)
    np.testing.assert_array_equal(off["slot_qoe"], on["slot_qoe"])
    np.testing.assert_array_equal(off["final_state"].lvl,
                                  on["final_state"].lvl)
    d = on["diagnostics"]
    assert set(d) == {"hit_rate", "dl_in_flight", "evictions", "cache_mb"}
    assert all(v.shape == (12,) for v in d.values())
    assert np.all((d["hit_rate"] >= 0.0) & (d["hit_rate"] <= 1.0))
    assert "diagnostics" not in off


def test_online_grid_sharded_diag_inert():
    from repro.core.online import OnlineConfig
    from repro.mec.scenario import MECConfig
    from repro.traces.engine import run_online_grid
    from repro.traces.registry import make_trace

    cfg = MECConfig(n_bs=3, n_users=30, n_models=4, seed=0)
    jobs = [dict(cfg=cfg, algo=a, trace=make_trace("stationary", cfg, 10,
                                                   seed=0))
            for a in ("cocar-ol", "lfu")]
    ocfg = OnlineConfig(n_slots=10, rounds=2)
    off = run_online_grid(jobs, ocfg, backend="vmap")
    on = run_online_grid(jobs, ocfg, backend="sharded", diagnostics=True)
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a["slot_qoe"], b["slot_qoe"])
        assert b["diagnostics"]["hit_rate"].shape == (10,)


# ---------------------------------------------------------------------------
# retrace regression: repeat dispatches must not recompile
# ---------------------------------------------------------------------------

def test_repeat_sweep_zero_retraces():
    from repro.core.cocar import cocar_grid

    insts = [make_instance(seed=s, n_users=20) for s in (0, 1)]
    kw = dict(seed=0, pdhg_iters=ITERS, best_of=2, n_seeds=1,
              backend="device", diagnostics=True)
    cocar_grid(insts, **kw)                      # warm every cache
    snap = retrace_snapshot()
    again = cocar_grid(insts, **kw)
    assert total_retraces_since(snap) == 0, (
        "repeat sweep recompiled a registered jit entry point")
    assert len(again) == 2


def test_executor_stats_spans():
    from repro.scale import GridSpec, run_grid

    insts = [make_instance(seed=s, n_users=20) for s in (0, 1)]
    res = run_grid(GridSpec(kind="offline", insts=insts, seed=0,
                            n_seeds=1, best_of=2, pdhg_iters=ITERS,
                            backend="vmap"))
    assert res.stats["seconds"] > 0.0
    assert res.stats["retraces"] >= 0
    assert res.stats["chunks"] >= 1


def test_grid_stage_spans():
    """One offline ``cocar_grid`` call spans each executor stage:
    preparation (the plan and rounding draws, then the one bucket's
    stacking) and unstacking under ``run_grid``, the device put,
    dispatch, wait and fetch once each under ``chunk``; the stages'
    walls fit in ``run_grid``'s."""
    import time

    from repro.core.cocar import cocar_grid
    from repro.obs import TRACER

    inst = make_instance(seed=3, n_users=20)
    kw = dict(seed=0, pdhg_iters=ITERS, best_of=2)
    cocar_grid([inst], **kw)                     # compile outside
    t = time.perf_counter()
    cocar_grid([inst], **kw)
    spans = TRACER.since(t)
    names = [s.name for s in spans]
    stages = ("grid.prepare", "grid.put", "grid.dispatch", "grid.wait",
              "grid.fetch", "grid.unstack")
    assert names == ["run_grid", "grid.prepare", "grid.prepare", "chunk",
                     "grid.put", "grid.dispatch", "grid.wait", "grid.fetch",
                     "grid.unstack"]
    by = {s.name: s for s in spans}
    prepares = [s for s in spans if s.name == "grid.prepare"]
    assert by["chunk"].parent == by["run_grid"].index
    for sp in prepares + [by["grid.unstack"]]:
        assert sp.parent == by["run_grid"].index
    for name in ("grid.put", "grid.dispatch", "grid.wait", "grid.fetch"):
        assert by[name].parent == by["chunk"].index
        assert by[name].depth == by["chunk"].depth + 1
    staged = [s for s in spans if s.name in stages]
    assert sum(s.seconds for s in staged) <= by["run_grid"].seconds
    assert all(s.retraces == 0 for s in staged)
    assert by["run_grid"].cpu_seconds > 0.0


# ---------------------------------------------------------------------------
# report.py rendering + convergence gate
# ---------------------------------------------------------------------------

def _report_mod():
    path = (pathlib.Path(__file__).resolve().parent.parent
            / "scripts" / "report.py")
    spec = importlib.util.spec_from_file_location("obs_report", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["obs_report"] = mod
    spec.loader.exec_module(mod)
    return mod


def _fake_artifacts(root, converged=True):
    rows = [{"zipf": 0.4, "lp_obj": 17.0, "pdhg_final_residual": 0.004,
             "pdhg_converged": True},
            {"zipf": 0.8, "lp_obj": 17.5,
             "pdhg_final_residual": 0.004 if converged else 0.2,
             "pdhg_converged": converged}]
    (root / "grid.json").write_text(json.dumps(rows))
    write_manifest(root / "grid.json", config={"smoke": True})
    tr = Tracer()
    with tr.span("sweep", kind="offline"):
        with tr.span("chunk", kind="offline", bucket="(3, 20)", chunk=0,
                     n_chunks=1, batch=2, pad_rows=0, in_bytes=1024):
            pass
    tr.export_jsonl(root / "grid.trace.jsonl")


def test_report_renders_and_gates(tmp_path, capsys):
    rep = _report_mod()
    _fake_artifacts(tmp_path, converged=True)
    assert rep.main([str(tmp_path), "--check-converged"]) == 0
    out = capsys.readouterr().out
    assert "== Manifests ==" in out
    assert "== Spans ==" in out
    assert "== Chunks ==" in out
    assert "== Convergence (grid.json) ==" in out
    assert "check-converged: OK" in out


def test_report_gate_fails_on_nonconverged(tmp_path, capsys):
    rep = _report_mod()
    _fake_artifacts(tmp_path, converged=False)
    assert rep.main([str(tmp_path), "--check-converged"]) == 1
    assert "1 window(s) above tolerance" in capsys.readouterr().out


def test_report_gate_fails_without_data(tmp_path):
    rep = _report_mod()
    assert rep.main([str(tmp_path), "--check-converged"]) == 1


def _fake_serving(misses, att_frac=0.2):
    att = {ph: {"sum": 1.0, "frac": att_frac, "p50": 0.01, "p95": 0.05,
                "p99": 0.1} for ph in ("queue", "stall", "service")}
    return {"offline": {"per_policy": {
        "cocar": {"delayed": {"deadline_misses": misses},
                  "attribution": att},
        "lfu": {"delayed": {"deadline_misses": misses + 1.0},
                "attribution": att}}}}


def test_report_attribution_table(tmp_path, capsys):
    rep = _report_mod()
    (tmp_path / "BENCH_serving.json").write_text(
        json.dumps(_fake_serving(2.0)))
    rep.report_attribution(tmp_path)
    out = capsys.readouterr().out
    assert "== Latency attribution" in out
    for needle in ("cocar", "lfu", "queue", "stall", "service", "20.0%"):
        assert needle in out
    # no serving payload -> section absent entirely
    rep.report_attribution(tmp_path / "nowhere")
    assert "attribution" not in capsys.readouterr().out


def test_deadline_miss_gate(tmp_path, capsys):
    """check_deadline_misses: None without a fresh payload, ok when at
    or below baseline, counts regressing policies above it — and the
    --check-converged gate turns a regression into exit 1."""
    rep = _report_mod()
    assert rep.check_deadline_misses(tmp_path) is None
    (tmp_path / "BENCH_serving.json").write_text(
        json.dumps(_fake_serving(3.0)))
    base = _fake_serving(3.0)
    assert rep.check_deadline_misses(tmp_path, baseline=base) == 0
    better = _fake_serving(2.0)                  # fewer misses: fine
    assert rep.check_deadline_misses(tmp_path, baseline=better) == 2
    out = capsys.readouterr().out
    assert "REGRESSION" in out
    # a baseline missing one policy gates only on the shared ones
    del base["offline"]["per_policy"]["lfu"]
    assert rep.check_deadline_misses(tmp_path, baseline=base) == 0


def test_check_converged_fails_on_miss_regression(tmp_path, capsys,
                                                  monkeypatch):
    rep = _report_mod()
    _fake_artifacts(tmp_path, converged=True)
    (tmp_path / "BENCH_serving.json").write_text(
        json.dumps(_fake_serving(5.0)))
    monkeypatch.setattr(rep, "_baseline_serving",
                        lambda: _fake_serving(1.0))
    assert rep.main([str(tmp_path), "--check-converged"]) == 1
    assert "regressed on deadline misses" in capsys.readouterr().out
    monkeypatch.setattr(rep, "_baseline_serving",
                        lambda: _fake_serving(5.0))
    assert rep.main([str(tmp_path), "--check-converged"]) == 0
    assert "no deadline-miss regressions" in capsys.readouterr().out


@pytest.mark.slow_compile
def test_sweep_smoke_end_to_end(tmp_path, monkeypatch, capsys):
    """``sweep --smoke`` in-process: rows converge, artifacts land, and
    report.py renders them with the gate green."""
    from repro.experiments import sweep as SW

    monkeypatch.chdir(tmp_path)
    rows = SW.main(smoke=True)
    assert len(rows) == 2
    assert all(r["pdhg_converged"] for r in rows)
    ci = tmp_path / "results" / "sweep" / "ci"
    for name in ("grid.json", "grid.manifest.json", "grid.trace.jsonl"):
        assert (ci / name).exists(), name
    capsys.readouterr()
    rep = _report_mod()
    assert rep.main([str(ci), "--check-converged"]) == 0
    assert "check-converged: OK" in capsys.readouterr().out
