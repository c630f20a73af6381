"""Offline pipeline benchmark: the fused one-dispatch CoCaR grid vs the
host-loop path.

Two measurements, persisted as ``results/bench/BENCH_offline.json``:

  * **equivalence** — on the default 16-variant offline grid, the device
    round+repair must reproduce the NumPy reference *decisions* exactly
    when both consume the same fractional LP solution and the same
    pre-drawn rounding uniforms: identical cache/routing arrays, the same
    winning ``best_of`` trial per seed, objectives and window metrics
    within 1e-9;
  * **throughput** — a (16 variants × rounding seeds) grid through
    (a) the pre-refactor host-loop path (each rounding seed re-runs the
    batched LP dispatch + per-window NumPy round/repair — what a
    multi-seed sweep cost before the fused pipeline), (b) the LP-sharing
    host loop (one LP dispatch, NumPy round/repair over all seeds), and
    (c) ONE fused jitted/vmapped device dispatch.  Compile time is
    reported separately: the steady-state number is what a sweep pays per
    additional grid.

Speedup ratios (not absolute times) are what ``scripts/check_bench.py``
gates on — they are stable across machines.

Run standalone:  PYTHONPATH=src python -m benchmarks.bench_offline
Quick CI smoke:  PYTHONPATH=src python -m benchmarks.bench_offline --smoke
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks import common
from repro.core import cocar as CC
from repro.core import lp as LP
from repro.experiments.sweep import DEFAULT_AXES
from repro.mec.scenario import MECConfig, Scenario, config_grid, stack_instances


def _grid_stack(n_users):
    cfgs = config_grid(MECConfig(n_users=n_users), DEFAULT_AXES)
    insts = []
    for c in cfgs:
        sc = Scenario(c)
        insts.append(sc.instance(0, sc.empty_cache()))
    return stack_instances(insts)


def _compare(stacked, dev, host, n_seeds):
    """Device vs host-reference results: decision identity + value gaps."""
    devu = CC._unstack_device(stacked, dev, n_seeds)
    identical = True
    obj_gap = 0.0
    met_gap = 0.0
    for per_dev, per_host in zip(devu, host):
        for (xd, Ad, idv), (xh, Ah, ih) in zip(per_dev, per_host):
            identical &= bool(np.array_equal(xd, xh))
            identical &= bool(np.array_equal(Ad, Ah))
            identical &= idv["best_t"] == ih["best_t"]
            obj_gap = max(obj_gap, abs(idv["obj"] - ih["obj"]))
            met_gap = max(met_gap, max(
                abs(idv["metrics"][k] - ih["metrics"][k])
                for k in ih["metrics"]))
    return identical, obj_gap, met_gap


def bench_equivalence(n_users=40, n_seeds=2, best_of=4, iters=800):
    """Default 16-variant grid: device round+repair vs the NumPy oracle on
    the same fractional solution and uniforms."""
    stacked = _grid_stack(n_users)
    u_cat, u_phi = CC.offline_uniforms(stacked, 0, n_seeds, best_of)
    dev = CC.offline_pipeline_device(stacked, u_cat, u_phi,
                                     pdhg_iters=iters, n_seeds=n_seeds)
    host = CC.offline_pipeline_host(stacked, dev["x_frac"], dev["A_frac"],
                                    u_cat, u_phi, n_seeds=n_seeds)
    identical, obj_gap, met_gap = _compare(stacked, dev, host, n_seeds)
    out = {"variants": len(stacked), "n_seeds": n_seeds,
           "best_of": best_of, "pdhg_iters": iters,
           "decisions_identical": identical,
           "max_obj_gap": obj_gap, "max_metric_gap": met_gap}
    common.csv_row("offline_equiv", 0,
                   f"identical={identical};obj_gap={obj_gap:.2e};"
                   f"metric_gap={met_gap:.2e}")
    return out


def bench_throughput(n_users=None, n_seeds=None, best_of=8, iters=1500):
    """(16 variants × seeds) grid: one fused dispatch vs the host loops."""
    n_users = n_users or (300 if common.FULL else 150)
    n_seeds = n_seeds or (16 if common.FULL else 8)
    stacked = _grid_stack(n_users)
    B = len(stacked)
    T = max(best_of, 1)
    u_cat, u_phi = CC.offline_uniforms(stacked, 0, n_seeds, best_of)

    t0 = time.time()
    CC.offline_pipeline_device(stacked, u_cat, u_phi, pdhg_iters=iters,
                               n_seeds=n_seeds)
    t_first = time.time() - t0
    t0 = time.time()
    dev = CC.offline_pipeline_device(stacked, u_cat, u_phi,
                                     pdhg_iters=iters, n_seeds=n_seeds)
    t_dev = time.time() - t0

    # (b) LP-sharing host loop: one LP dispatch + NumPy round/repair
    LP.solve_lp_pdhg_batched(stacked.data, iters=iters)       # warm compile
    t0 = time.time()
    res = LP.solve_lp_pdhg_batched(stacked.data, iters=iters)
    host = CC.offline_pipeline_host(stacked, res.x, res.A, u_cat, u_phi,
                                    n_seeds=n_seeds)
    t_host_rr = time.time() - t0

    # (a) pre-refactor host-loop path: every rounding seed re-runs the LP
    # dispatch (rounding+repair were welded to the solve, so a multi-seed
    # sweep had no way to share it)
    t0 = time.time()
    for s in range(n_seeds):
        sl = slice(s * T, (s + 1) * T)
        res_s = LP.solve_lp_pdhg_batched(stacked.data, iters=iters)
        CC.offline_pipeline_host(stacked, res_s.x, res_s.A,
                                 u_cat[:, sl], u_phi[:, sl], n_seeds=1)
    t_host_loop = time.time() - t0

    # quality: the same algorithm on both paths, each from its own f64
    # LP solve
    prec_dev = np.asarray(dev["metrics"]["avg_precision"]).mean()
    prec_host = np.mean([[ih["metrics"]["avg_precision"]
                          for _, _, ih in per] for per in host])
    grids = B * n_seeds                       # windows solved end to end
    out = {
        "variants": B, "n_seeds": n_seeds, "best_of": best_of,
        "pdhg_iters": iters, "n_users": n_users,
        "device_s": t_dev, "device_first_call_s": t_first,
        "host_rr_s": t_host_rr, "host_loop_s": t_host_loop,
        "windows_per_s_device": grids / t_dev,
        "windows_per_s_host_loop": grids / t_host_loop,
        "speedup_vs_host_loop": t_host_loop / t_dev,
        "speedup_vs_host_rr": t_host_rr / t_dev,
        "avg_precision_device": float(prec_dev),
        "avg_precision_host": float(prec_host),
        "avg_precision_gap": float(abs(prec_dev - prec_host)),
    }
    common.csv_row(
        f"offline_grid_B{B}x{n_seeds}", t_dev / grids * 1e6,
        f"speedup={out['speedup_vs_host_loop']:.1f}x;"
        f"vs_shared_lp={out['speedup_vs_host_rr']:.2f}x;"
        f"prec_gap={out['avg_precision_gap']:.2e}")
    return out


def main():
    out = {"equivalence": bench_equivalence(),
           "throughput": bench_throughput()}
    assert out["equivalence"]["decisions_identical"], out["equivalence"]
    common.save("BENCH_offline", out)
    th = out["throughput"]
    print(f"offline grid ({th['variants']} variants x {th['n_seeds']} "
          f"seeds x best_of {th['best_of']}): one dispatch {th['device_s']:.1f}s "
          f"vs host-loop {th['host_loop_s']:.1f}s "
          f"({th['speedup_vs_host_loop']:.1f}x; "
          f"{th['speedup_vs_host_rr']:.2f}x vs LP-sharing host, "
          f"compile {th['device_first_call_s']:.1f}s, "
          f"prec gap {th['avg_precision_gap']:.2e})")
    return out


def smoke():
    """CI smoke: tiny grid, device==reference decisions + a fused dispatch.

    Persists the equivalence block (no throughput at this scale) to the
    ``ci/`` scratch subdir — never over the committed baseline — so
    ``scripts/check_bench.py`` can gate the correctness gaps in CI."""
    eq = bench_equivalence(n_users=25, n_seeds=2, best_of=2, iters=200)
    common.save("BENCH_offline", {"equivalence": eq}, subdir="ci")
    assert eq["decisions_identical"], eq
    assert eq["max_obj_gap"] < 1e-9, eq
    assert eq["max_metric_gap"] < 1e-9, eq
    print("offline smoke OK: device round+repair == numpy reference "
          f"on {eq['variants']} variants x {eq['n_seeds']} seeds")


if __name__ == "__main__":
    import sys

    if "--smoke" in sys.argv[1:]:
        smoke()
    else:
        main()
