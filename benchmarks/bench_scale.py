"""Sharded grid executor benchmark: ``repro.scale.run_grid`` across a
forced 8-device host mesh vs the one-device vmap dispatch.

Two measurements, persisted as ``results/bench/BENCH_scale.json``:

  * **equivalence** — on a heterogeneous grid, the sharded + bucketed +
    chunked executor must reproduce the one-device, max-padded vmap
    dispatch's *decisions* exactly: identical cache/routing arrays and
    winning ``best_of`` trials for the offline pipeline, and bit-equal
    per-slot QoE for the online scan engine; objective/metric value
    gaps stay at float-reduction noise;
  * **throughput** — the same (variants × seeds) offline grid through
    (a) ONE one-device vmapped dispatch (the PR-3 path) and (b) the
    executor sharding chunks across all 8 host devices
    (``shard_map`` over the batch axis, chunk streaming with donated
    buffers).  ``sharded_speedup = t_one_device / t_sharded`` is the
    machine-portable ratio ``scripts/check_bench.py`` gates; the
    chunked run's ``peak_chunk_in_bytes`` vs the one-shot grid bytes is
    the recorded evidence that streaming bounds peak live memory.

The module forces ``--xla_force_host_platform_device_count=8`` before
the first jax import, so it exercises the real multi-device shard_map
path even on a single-CPU box (the same trick ``launch/dryrun.py`` uses
for the 512-chip production meshes).

Run standalone:  PYTHONPATH=src python -m benchmarks.bench_scale
Quick CI smoke:  PYTHONPATH=src python -m benchmarks.bench_scale --smoke
"""
from __future__ import annotations

# before ANY jax-importing module: the device count locks on first init
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import resource                                             # noqa: E402
from dataclasses import replace                             # noqa: E402

import numpy as np                                          # noqa: E402

from benchmarks import common                               # noqa: E402
from repro.experiments.sweep import DEFAULT_AXES            # noqa: E402
from repro.mec.scenario import (MECConfig, Scenario,        # noqa: E402
                                config_grid)
from repro.obs import TRACER                                # noqa: E402
from repro.scale import GridSpec, run_grid                  # noqa: E402

N_DEVICES = 8


def _grid_insts(n_variants, n_users=40, hetero=True):
    """``n_variants`` scenario windows cycling over the default sweep
    axes with distinct seeds; ``hetero`` alternates user counts so the
    grid actually has multiple (N, U) shapes to bucket."""
    cfgs = config_grid(MECConfig(n_users=n_users), DEFAULT_AXES)
    insts = []
    for i in range(n_variants):
        cfg = replace(cfgs[i % len(cfgs)], seed=i,
                      n_users=n_users - (10 if hetero and i % 2 else 0))
        sc = Scenario(cfg)
        insts.append(sc.instance(0, sc.empty_cache()))
    return insts


def _compare_offline(ref, out):
    """Decision identity + value gaps between two offline grid results."""
    identical, obj_gap, met_gap = True, 0.0, 0.0
    for per_r, per_o in zip(ref, out):
        for (xr, Ar, ir), (xo, Ao, io) in zip(per_r, per_o):
            identical &= bool(np.array_equal(xr, xo))
            identical &= bool(np.array_equal(Ar, Ao))
            identical &= ir["best_t"] == io["best_t"]
            obj_gap = max(obj_gap, abs(ir["obj"] - io["obj"]))
            met_gap = max(met_gap, max(
                abs(ir["metrics"][k] - io["metrics"][k])
                for k in ir["metrics"]))
    return identical, obj_gap, met_gap


def _online_jobs(n_slots=12):
    # twin of tests/test_scale.py::_online_jobs — pytest asserts the same
    # mixed-shape grid this bench gates; keep them in sync
    from repro.traces.registry import make_trace

    cfg_a = MECConfig(n_bs=3, n_users=40, n_models=4, seed=0)
    cfg_b = MECConfig(n_bs=4, n_users=30, n_models=4, seed=1)
    tr_a = make_trace("stationary", cfg_a, n_slots, seed=0)
    tr_b = make_trace("flash_crowd", cfg_b, n_slots, seed=1)
    return ([dict(cfg=cfg_a, algo=a, trace=tr_a)
             for a in ("cocar-ol", "lfu", "random")]
            + [dict(cfg=cfg_b, algo=a, trace=tr_b, seed=1)
               for a in ("cocar-ol", "lfu-mad")])


def bench_equivalence(n_variants=16, n_users=40, n_seeds=2, best_of=4,
                      iters=800):
    """Sharded+bucketed+chunked executor vs the one-device max-padded
    vmap dispatch, plus the online scan engine across the mesh."""
    import jax

    insts = _grid_insts(n_variants, n_users)
    kw = dict(kind="offline", insts=insts, seed=0, n_seeds=n_seeds,
              best_of=best_of, pdhg_iters=iters)
    ref = run_grid(GridSpec(**kw, max_buckets=1))
    bkt = run_grid(GridSpec(**kw, max_buckets=3))
    shd = run_grid(GridSpec(**kw, devices=len(jax.devices()),
                            max_buckets=3,
                            chunk_size=max(n_variants // 2, N_DEVICES)))
    identical_b, obj_b, met_b = _compare_offline(ref.results, bkt.results)
    identical_s, obj_s, met_s = _compare_offline(ref.results, shd.results)

    from repro.core.online import OnlineConfig
    from repro.traces.engine import run_online_grid

    jobs = _online_jobs()
    ocfg = OnlineConfig(n_slots=12, rounds=2)
    on_ref = run_online_grid(jobs, ocfg)
    on_shd = run_online_grid(jobs, ocfg, devices=len(jax.devices()))
    online_identical = all(
        np.array_equal(a["slot_qoe"], b["slot_qoe"])
        and np.array_equal(a["final_state"].lvl, b["final_state"].lvl)
        for a, b in zip(on_ref, on_shd))

    out = {"variants": n_variants, "n_seeds": n_seeds, "best_of": best_of,
           "pdhg_iters": iters, "n_users": n_users,
           "devices": len(jax.devices()),
           "plan": [list(p) for p in shd.stats["plan"]],
           "decisions_identical": bool(identical_s),
           "bucketed_identical": bool(identical_b),
           "online_identical": bool(online_identical),
           "max_obj_gap": float(max(obj_b, obj_s)),
           "max_metric_gap": float(max(met_b, met_s))}
    common.csv_row("scale_equiv", 0,
                   f"sharded={identical_s};bucketed={identical_b};"
                   f"online={online_identical};"
                   f"obj_gap={out['max_obj_gap']:.2e}")
    return out


def bench_throughput(n_variants=None, n_users=40, n_seeds=2, best_of=8,
                     iters=1500):
    """(variants × seeds) homogeneous grid: one-device vmap dispatch vs
    the executor streaming chunks across the 8-device mesh."""
    import jax

    n_variants = n_variants or (96 if common.FULL else 64)
    insts = _grid_insts(n_variants, n_users, hetero=False)
    kw = dict(kind="offline", insts=insts, seed=0, n_seeds=n_seeds,
              best_of=best_of, pdhg_iters=iters, max_buckets=1)
    chunk = max(n_variants // 4, N_DEVICES)

    # warm both compile caches, then measure steady state
    run_grid(GridSpec(**kw))
    with TRACER.span("scale:one_device", variants=n_variants) as sp:
        one_dev = run_grid(GridSpec(**kw))
    t_vmap = sp.seconds

    sharded = dict(kw, devices=len(jax.devices()), chunk_size=chunk)
    run_grid(GridSpec(**sharded))
    with TRACER.span("scale:sharded", variants=n_variants,
                     chunk=chunk) as sp:
        shd = run_grid(GridSpec(**sharded))
    t_shard = sp.seconds

    identical, obj_gap, met_gap = _compare_offline(one_dev.results,
                                                   shd.results)
    grids = n_variants * n_seeds
    one_shot_bytes = one_dev.stats["peak_chunk_in_bytes"]
    out = {
        "variants": n_variants, "n_seeds": n_seeds, "best_of": best_of,
        "pdhg_iters": iters, "n_users": n_users,
        "devices": len(jax.devices()), "chunk_size": chunk,
        "one_device_s": t_vmap, "sharded_s": t_shard,
        "windows_per_s_one_device": grids / t_vmap,
        "windows_per_s_sharded": grids / t_shard,
        "sharded_speedup": t_vmap / t_shard,
        "decisions_identical": bool(identical),
        "decision_obj_gap": float(obj_gap),
        "decision_metric_gap": float(met_gap),
        # streaming keeps live input bytes at one chunk, not the grid
        "grid_in_bytes": int(one_shot_bytes),
        "peak_chunk_in_bytes": int(shd.stats["peak_chunk_in_bytes"]),
        "memory_bounded": bool(
            shd.stats["peak_chunk_in_bytes"] * 2 <= one_shot_bytes),
        "ru_maxrss_kb": int(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss),
    }
    common.csv_row(
        f"scale_grid_B{n_variants}x{n_seeds}", t_shard / grids * 1e6,
        f"speedup={out['sharded_speedup']:.2f}x;"
        f"chunk_bytes={out['peak_chunk_in_bytes']};"
        f"grid_bytes={out['grid_in_bytes']}")
    return out


def main():
    out = {"equivalence": bench_equivalence(),
           "throughput": bench_throughput()}
    eq, th = out["equivalence"], out["throughput"]
    assert eq["decisions_identical"] and eq["bucketed_identical"], eq
    assert th["decisions_identical"], th
    common.save("BENCH_scale", out)
    print(f"scale grid ({th['variants']} variants x {th['n_seeds']} seeds, "
          f"{th['devices']} host devices): sharded {th['sharded_s']:.1f}s "
          f"vs one-device {th['one_device_s']:.1f}s "
          f"({th['sharded_speedup']:.2f}x), chunk bytes "
          f"{th['peak_chunk_in_bytes'] / 1e6:.1f}MB vs one-shot "
          f"{th['grid_in_bytes'] / 1e6:.1f}MB, decisions identical")
    return out


def smoke():
    """CI smoke under the forced 8-device mesh: sharded == one-device
    decisions on a small heterogeneous grid + the online engine.
    Persists the equivalence block to the ``ci/`` scratch dir so
    ``scripts/check_bench.py`` gates the flags and gaps."""
    eq = bench_equivalence(n_variants=8, n_users=25, n_seeds=1, best_of=2,
                           iters=200)
    common.save("BENCH_scale", {"equivalence": eq}, subdir="ci")
    assert eq["decisions_identical"], eq
    assert eq["bucketed_identical"], eq
    assert eq["online_identical"], eq
    assert eq["max_obj_gap"] < 1e-9, eq
    assert eq["max_metric_gap"] < 1e-9, eq
    print(f"scale smoke OK: sharded executor == one-device vmap on "
          f"{eq['variants']} variants across {eq['devices']} host devices "
          f"(plan {eq['plan']})")


if __name__ == "__main__":
    import sys

    if "--smoke" in sys.argv[1:]:
        smoke()
    else:
        main()
