"""Scenario-grid sweeps: the fused offline pipeline and the vmapped scan
engine (online).

Offline: fans a cross-product of :class:`MECConfig` variants (topology
size, Zipf skew, memory capacity, deadline — the axes of the paper's
Sec. VII comparisons) into per-variant JDCR windows and runs LP →
randomized rounding → repair → metrics for ALL of them — optionally
crossed with ``n_seeds`` independent rounding seeds — in ONE jitted/
vmapped device dispatch (``repro.core.cocar.cocar_grid``), emitting one
flat results table: a list of row dicts, each carrying the swept axis
values, the LP objective, and the post-repair window metrics.
``backend="host"`` keeps the NumPy round+repair loop (the reference
path) behind the same interface.

Online: ``run_online_sweep`` crosses config variants with *workload
families* (``repro.traces.make_workload``: flash crowds, diurnal load,
MMPP bursts, mobility, streaming Poisson arrivals, …) and policies, and
runs the whole grid — aggregated per-(BS, model) demand tensors, never
per-user ones — in ONE ``lax.scan``+vmap dispatch
(``repro.traces.engine.run_online_grid``) instead of per-scenario Python
slot loops.

``benchmarks/tables.py::sweep_table`` persists the offline table next to
the other paper tables; run standalone with

    PYTHONPATH=src python -m repro.experiments.sweep            # offline
    PYTHONPATH=src python -m repro.experiments.sweep --online   # online

``--shard`` partitions any of the grids across a device mesh via the
``repro.scale`` executor (the chips of a TPU host; on a CPU run under
``XLA_FLAGS=--xla_force_host_platform_device_count=K``); ``--devices``
and ``--chunk`` tune the mesh width and streaming chunk.

Observability (``repro.obs``): diagnostics taps are ON by default —
PDHG residual/convergence columns on offline rows, per-slot cache
telemetry summaries on online rows — and provably decision-inert
(``--no-diag`` compiles them out).  Every results JSON gets a sibling
``*.manifest.json`` (git SHA, jax/device info, seeds, config hash) and
a ``*.trace.jsonl`` export of the program's spans (``repro.obs.tracing``:
wall and thread CPU time per sweep, grid run, chunk and executor stage);
render them with ``scripts/report.py results/sweep``.  The same spans are
named host events in a ``jax.profiler`` trace, beside the device's
operations: run under ``jax.profiler.trace(<dir>)`` and open the trace in
TensorBoard or Perfetto.  ``--smoke`` runs a 2-window offline CI grid
into ``results/sweep/ci/``.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

from repro.core.cocar import cocar_grid
from repro.mec.scenario import MECConfig, Scenario, config_grid
from repro.obs import TRACER, convergence_table, write_manifest

#: Default sweep: 2^4 = 16 variants over the four axes the paper varies.
#: n_bs values sit close together on purpose — heterogeneous topologies are
#: padded to the max N for the single dispatch, so a tight spread keeps the
#: padding waste low (vary it wider when the question needs it).
DEFAULT_AXES = {
    "n_bs": (5, 6),
    "zipf": (0.4, 0.8),
    "mem_capacity_mb": (300.0, 500.0),
    "ddl_s": (0.25, 0.35),
}


def run_sweep(base: MECConfig = None, axes: dict = None, window: int = 0,
              pdhg_iters: int = 4000, best_of: int = 8, seed: int = 0,
              n_seeds: int = 1, backend: str = "device",
              devices: int = None, chunk_size: int = 0,
              max_buckets: int = 1, diagnostics: bool = False):
    """One CoCaR window per (grid variant × rounding seed), the whole grid
    as ONE fused device dispatch — LP, rounding, repair, trial argmax and
    window metrics all inside the jit (mirroring the ``--online`` grid).
    ``backend="sharded"`` (the ``--shard`` flag) partitions the grid
    across a host-device mesh via ``repro.scale`` — decision-identical,
    just spread over ``devices`` devices in ``chunk_size`` streams.
    ``max_buckets > 1`` opts heterogeneous grids into size-bucketed
    padding (still decision-identical; only the reported ``lp_obj``
    carries ~1e-14 reduction-order slack).

    ``diagnostics=True`` taps the PDHG solver's residual curves inside
    the jit (``repro.obs``) and adds ``pdhg_final_residual`` /
    ``pdhg_converged`` columns to every row — decisions stay bit-
    identical (device/sharded backends only; the host reference loop
    has no tap).

    Returns a list of row dicts (variant-major, seed-minor, in grid
    order); with ``n_seeds > 1`` each row carries its ``rounding_seed``.
    """
    base = base or MECConfig(n_users=40)
    axes = axes or DEFAULT_AXES
    cfgs = config_grid(base, axes)
    scenarios = [Scenario(c) for c in cfgs]
    insts = [sc.instance(window, sc.empty_cache()) for sc in scenarios]
    grid = cocar_grid(insts, seed=seed, pdhg_iters=pdhg_iters,
                      best_of=best_of, n_seeds=n_seeds, backend=backend,
                      devices=devices, chunk_size=chunk_size,
                      max_buckets=max_buckets, diagnostics=diagnostics)
    rows = []
    for cfg, per_seed in zip(cfgs, grid):
        for s, (_x, _A, info) in enumerate(per_seed):
            row = {k: getattr(cfg, k) for k in axes}
            if n_seeds > 1:
                row["rounding_seed"] = s
            row["lp_obj"] = info["lp_obj"]
            row.update(info["metrics"])
            if "lp_diag" in info:
                summ = info["lp_diag"]["summary"]
                row["pdhg_final_residual"] = summ["final_residual"]
                row["pdhg_converged"] = summ["converged"]
            rows.append(row)
    return rows


def run_policy_sweep(base: MECConfig = None, axes: dict = None,
                     window: int = 0, pdhg_iters: int = 4000,
                     best_of: int = 8, seed: int = 0, n_seeds: int = 1,
                     episodes: int = 60, backend: str = "device",
                     devices: int = None, chunk_size: int = 0,
                     max_buckets: int = 1, diagnostics: bool = False):
    """The paper's Sec. VII-B headline comparison — CoCaR vs SPR³ /
    Greedy / Random / GatMARL — across (grid variants × rounding seeds ×
    policies), every policy's decisions AND the shared evaluation stage in
    ONE fused device dispatch (GatMARL training excepted: host-side,
    cached per topology).

    ``diagnostics=True`` (device/sharded only) taps the CoCaR LP's PDHG
    residuals per window and attaches a ``summary["convergence"]`` table
    over the grid; decisions stay bit-identical.

    Returns ``(rows, summary)``: one row dict per (variant, seed, policy)
    plus a summary with per-policy grid means and the CoCaR-vs-best-
    baseline improvement ratio.
    """
    from repro.core.baselines import spr3_relaxed
    from repro.core.cocar import (gat_grid_policies, policy_grid_host,
                                  policy_uniforms)
    from repro.core.lp import solve_lp_pdhg_batched
    from repro.mec.scenario import stack_instances

    base = base or MECConfig(n_users=40)
    axes = axes or DEFAULT_AXES
    cfgs = config_grid(base, axes)
    scenarios = [Scenario(c) for c in cfgs]
    insts = [sc.instance(window, sc.empty_cache()) for sc in scenarios]

    lp_diag = None
    if backend in ("device", "sharded"):
        from repro.scale import GridSpec, run_grid

        gr = run_grid(GridSpec(
            kind="policy", insts=insts, seed=seed, n_seeds=n_seeds,
            best_of=best_of, pdhg_iters=pdhg_iters, episodes=episodes,
            backend="vmap" if backend == "device" else "sharded",
            devices=devices, chunk_size=chunk_size,
            max_buckets=max_buckets, diagnostics=diagnostics))
        res = gr.results
        lp_diag = gr.stats.get("lp_diag")
        met = _policy_met(res, len(insts), n_seeds)
    elif backend == "host":
        stacked = stack_instances(insts)
        uniforms = policy_uniforms(stacked, seed, n_seeds, best_of)
        gat = gat_grid_policies(stacked, seed, episodes)
        res = solve_lp_pdhg_batched(stacked.data, iters=pdhg_iters)
        relaxed = stack_instances([spr3_relaxed(i) for i in insts])
        res_s = solve_lp_pdhg_batched(relaxed.data, iters=pdhg_iters)
        host = policy_grid_host(stacked, uniforms, gat, res.x, res.A,
                                {"x": res_s.x, "A": res_s.A},
                                n_seeds=n_seeds)
        met = _policy_met(host, len(stacked), n_seeds)
    else:
        raise ValueError(f"unknown backend {backend!r}")
    rows, summary = _policy_rows(cfgs, axes, met, n_seeds)
    if lp_diag:
        summary["convergence"] = convergence_table(
            np.asarray([d["final_residual"] for d in lp_diag]),
            tol=lp_diag[0]["tol"])
    return rows, summary


def _policy_met(results, n_windows, n_seeds):
    """``results[policy][b][s] = (x, A, metrics)`` → per-policy metric
    arrays ``met[p][k] (B, S)``."""
    from repro.core.cocar import OFFLINE_POLICIES

    return {p: {k: np.asarray(
        [[results[p][b][s][2][k] for s in range(n_seeds)]
         for b in range(n_windows)])
        for k in results[p][0][0][2]} for p in OFFLINE_POLICIES}


def _policy_rows(cfgs, axes, met, n_seeds):
    """Flatten per-policy metric arrays ``met[p][k] (B, S)`` into the
    sweep's row table + headline summary."""
    from repro.core.cocar import OFFLINE_POLICIES, improvement_ratio

    rows = []
    for i, cfg in enumerate(cfgs):
        for s in range(n_seeds):
            for p in OFFLINE_POLICIES:
                row = {k: getattr(cfg, k) for k in axes}
                if n_seeds > 1:
                    row["rounding_seed"] = s
                row["policy"] = p
                row.update({k: float(v[i, s])
                            for k, v in met[p].items()})
                rows.append(row)
    summary = improvement_ratio(
        {p: met[p]["avg_precision"] for p in OFFLINE_POLICIES})
    summary["avg_qoe"] = {p: float(np.mean(met[p]["avg_qoe"]))
                          for p in OFFLINE_POLICIES}
    return rows, summary


#: Default online sweep: 2 config axes x 2 workload families x 2 policies
#: = 16 scenarios, one vmapped scan dispatch.
DEFAULT_ONLINE_AXES = {
    "zipf": (0.4, 0.8),
    "mem_capacity_mb": (300.0, 500.0),
}
DEFAULT_WORKLOADS = ("stationary", "flash_crowd")
DEFAULT_POLICIES = ("cocar-ol", "lfu")


def run_online_sweep(base: MECConfig = None, axes: dict = None,
                     workloads=None, policies=DEFAULT_POLICIES,
                     ocfg=None, seed: int = 0, backend: str = "vmap",
                     devices: int = None, chunk_size: int = 0,
                     diagnostics: bool = False, registry=None):
    """Cross (config grid x workload family x policy), run everything in
    one vmapped scan dispatch (``backend="sharded"`` spreads it across a
    host-device mesh).  ``workloads`` names registry families
    (``repro.traces.make_workload`` — per-user traces and the streaming
    ``poisson_zipf`` family alike; all flow through the unified
    aggregated-demand engine).  ``diagnostics=True`` taps the per-slot
    cache telemetry inside the scan (hit rate, downloads in flight,
    evictions, cache occupancy) and adds summary columns — decisions and
    QoE stay bit-identical.  With a ``registry``
    (``repro.obs.metrics.MetricsRegistry``) every job's per-slot curves
    are additionally folded into the shared streaming-histogram schema
    (``online_hit_rate`` / ``online_dl_in_flight`` / ``online_evictions``
    — the same types the serving plane exports), still after the fact
    and decision-inert.  Returns a list of row dicts in grid order."""
    from repro.core.online import OnlineConfig
    from repro.traces.engine import run_online_grid
    from repro.traces.registry import make_workload

    workloads = workloads or DEFAULT_WORKLOADS
    base = base or MECConfig(n_users=150)
    axes = axes or DEFAULT_ONLINE_AXES
    ocfg = ocfg or OnlineConfig(n_slots=60)
    cfgs = config_grid(base, axes)
    jobs, keys = [], []
    for cfg in cfgs:
        for wname in workloads:
            wl = make_workload(wname, cfg, ocfg.n_slots, seed=seed)
            for algo in policies:
                jobs.append(dict(cfg=cfg, algo=algo, workload=wl,
                                 seed=seed))
                keys.append((cfg, wl, algo))
    results = run_online_grid(jobs, ocfg, backend=backend,
                              devices=devices, chunk_size=chunk_size,
                              diagnostics=diagnostics)
    rows = []
    for (cfg, wl, algo), res in zip(keys, results):
        row = {k: getattr(cfg, k) for k in axes}
        row.update(workload=wl.name, family=wl.family, algo=algo,
                   avg_qoe=res["avg_qoe"], hit_rate=res["hit_rate"])
        if "diagnostics" in res:
            d = res["diagnostics"]
            row["mean_dl_in_flight"] = float(np.mean(d["dl_in_flight"]))
            row["evictions"] = float(np.sum(d["evictions"]))
            row["final_cache_mb"] = float(d["cache_mb"][-1])
            if registry is not None:
                from repro.obs import observe_online_diag

                observe_online_diag(registry, d)
        rows.append(row)
    return rows


def format_table(rows) -> str:
    """Fixed-width text rendering of a sweep table."""
    if not rows:
        return "(empty sweep)"
    cols = list(rows[0])
    widths = {c: max(len(c), 9) for c in cols}
    fmt = "  ".join(f"{{:>{widths[c]}}}" for c in cols)
    lines = [fmt.format(*cols)]
    for r in rows:
        lines.append(fmt.format(*(
            f"{v:.3f}" if isinstance(v, float) else str(v)
            for v in (r[c] for c in cols))))
    return "\n".join(lines)


#: CI smoke grid: two small offline windows at the smallest iteration
#: budget whose final PDHG residuals all clear ``obs.DEFAULT_TOL``
#: (measured: max final residual 6.6e-3 at 3000 iterations, tol 1e-2).
SMOKE_AXES = {"zipf": (0.4, 0.8)}
SMOKE_ITERS = 3000


def main(online: bool = False, backend: str = "device", n_seeds: int = 1,
         policies: bool = False, devices: int = None, chunk_size: int = 0,
         max_buckets: int = 1, diagnostics: bool = True,
         smoke: bool = False):
    payload, registry = None, None
    kind = "online" if online else "policy" if policies else "offline"
    out = pathlib.Path("results") / "sweep" / ("ci" if smoke else "")
    with TRACER.span("sweep", kind=kind, backend=backend, smoke=smoke,
                     diagnostics=diagnostics):
        if smoke:
            rows = run_sweep(base=MECConfig(n_users=20), axes=SMOKE_AXES,
                             pdhg_iters=SMOKE_ITERS, backend=backend,
                             n_seeds=n_seeds, devices=devices,
                             chunk_size=chunk_size,
                             diagnostics=diagnostics)
            name = "grid.json"
        elif online:
            from repro.obs import MetricsRegistry

            registry = MetricsRegistry() if diagnostics else None
            rows = run_online_sweep(
                backend="sharded" if backend == "sharded" else "vmap",
                devices=devices, chunk_size=chunk_size,
                diagnostics=diagnostics, registry=registry)
            name = "online_grid.json"
        elif policies:
            rows, summary = run_policy_sweep(backend=backend,
                                             n_seeds=n_seeds,
                                             devices=devices,
                                             chunk_size=chunk_size,
                                             max_buckets=max_buckets,
                                             diagnostics=diagnostics)
            name = "policy_grid.json"
            payload = {"rows": rows, "summary": summary}
        else:
            rows = run_sweep(backend=backend, n_seeds=n_seeds,
                             devices=devices, chunk_size=chunk_size,
                             max_buckets=max_buckets,
                             diagnostics=diagnostics)
            name = "grid.json"
    print(format_table(rows))
    out.mkdir(parents=True, exist_ok=True)
    path = out / name
    path.write_text(json.dumps(payload if payload is not None else rows,
                               indent=1, default=float))
    write_manifest(path,
                   config=dict(kind=kind, backend=backend,
                               n_seeds=n_seeds, devices=devices,
                               chunk_size=chunk_size,
                               max_buckets=max_buckets,
                               diagnostics=diagnostics, smoke=smoke),
                   seeds={"seed": 0, "n_seeds": n_seeds})
    TRACER.export_jsonl(path.with_name(path.stem + ".trace.jsonl"))
    if registry is not None:
        registry.export_prometheus(
            path.with_name(path.stem + ".metrics.prom"))
        registry.export_json(path.with_name(path.stem + ".metrics.json"))
    if policies:
        s = payload["summary"]
        print(f"\nCoCaR vs best baseline ({s['best_baseline']}): "
              f"{s['ratio']:.2f}x avg served precision")
        if "convergence" in s:
            c = s["convergence"]
            print(f"pdhg convergence: "
                  f"{c['n_windows'] - c['n_not_converged']}/"
                  f"{c['n_windows']} windows <= tol {c['tol']:g}")
    elif diagnostics and not online and backend != "host":
        bad = sum(1 for r in rows if not r.get("pdhg_converged", True))
        print(f"\npdhg convergence: {len(rows) - bad}/{len(rows)} "
              f"windows converged")
    print(f"\n{len(rows)} rows -> {path}")
    return rows


if __name__ == "__main__":
    import argparse

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(description="scenario-grid sweeps")
    ap.add_argument("--online", action="store_true",
                    help="trace-family grid through the scan engine")
    ap.add_argument("--policies", action="store_true",
                    help="CoCaR vs the Sec. VII-B baseline zoo, one "
                         "dispatch across (variants x seeds x policies)")
    ap.add_argument("--host", action="store_true",
                    help="NumPy round+repair reference loop")
    ap.add_argument("--shard", action="store_true",
                    help="partition the grid across a device mesh "
                         "(repro.scale; the chips of a TPU host, or on a "
                         "CPU K virtual devices under XLA_FLAGS="
                         "--xla_force_host_platform_device_count=K)")
    ap.add_argument("--devices", type=int, default=None,
                    help="mesh width for --shard (default: all devices)")
    ap.add_argument("--chunk", type=int, default=0,
                    help="streaming chunk size (0 = one chunk per bucket)")
    ap.add_argument("--buckets", type=int, default=1,
                    help="max size buckets for heterogeneous grids "
                         "(1 = classic single padded shape)")
    ap.add_argument("--seeds", type=int, default=1,
                    help="rounding seeds per variant (offline only)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny offline CI grid (2 windows, converging "
                         "iteration budget) written to results/sweep/ci/")
    ap.add_argument("--no-diag", action="store_true",
                    help="compile the solver/scan diagnostics taps out "
                         "(decisions are bit-identical either way)")
    args = ap.parse_args()
    if args.host and args.shard:
        ap.error("--host and --shard are mutually exclusive")
    if args.devices is not None and not args.shard:
        ap.error("--devices requires --shard (a plain run would "
                 "silently ignore it)")
    if args.smoke and (args.online or args.policies or args.host):
        ap.error("--smoke is an offline device/sharded grid; it takes "
                 "none of --online/--policies/--host")
    main(online=args.online,
         backend=("host" if args.host
                  else "sharded" if args.shard else "device"),
         n_seeds=args.seeds, policies=args.policies,
         devices=args.devices, chunk_size=args.chunk,
         max_buckets=args.buckets, diagnostics=not args.no_diag,
         smoke=args.smoke)
