"""A sharded many-scenario sweep: 256 variants x 8 rounding seeds x all
5 offline policies, streamed across an 8-device host mesh.

The grid executor (``repro.scale``) buckets the heterogeneous windows
into a few padded shapes, partitions every chunk across the mesh with
``shard_map``, and streams chunks with donated buffers — peak live
memory is one chunk, not the grid, and the decisions are bit-identical
to the one-device dispatch (see ``docs/algorithms.md`` Sec. 9).

The default run is a reduced 32 x 2 x 5 grid (~a minute on a laptop);
``--full`` runs the headline 256 x 8 x 5 (GatMARL trains once per
topology, host-side and cached, so the full grid is dominated by the
fused LP+rounding dispatches).

Run:  PYTHONPATH=src python examples/scale_sweep.py [--full]
"""
# must precede the first jax import: the device count locks on init
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse                                             # noqa: E402
import resource                                             # noqa: E402
from dataclasses import replace                             # noqa: E402

import jax                                                  # noqa: E402
import numpy as np                                          # noqa: E402

from repro.core.cocar import OFFLINE_POLICIES, improvement_ratio  # noqa: E402
from repro.experiments.sweep import DEFAULT_AXES            # noqa: E402
from repro.mec.scenario import MECConfig, Scenario, config_grid  # noqa: E402
from repro.scale import GridSpec, run_grid                  # noqa: E402

ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
ap.add_argument("--full", action="store_true",
                help="the full 256x8x5 grid (default: 32x2x5)")
args = ap.parse_args()

N_VARIANTS = 256 if args.full else 32
N_SEEDS = 8 if args.full else 2
EPISODES = 20 if args.full else 8

# 256 scenario variants: the four paper axes crossed, then cycled with
# fresh seeds and alternating user counts (heterogeneous shapes on
# purpose — the executor buckets them)
base_cfgs = config_grid(MECConfig(n_users=40), DEFAULT_AXES)
insts = []
for i in range(N_VARIANTS):
    cfg = replace(base_cfgs[i % len(base_cfgs)], seed=i,
                  n_users=40 - (10 if i % 2 else 0))
    sc = Scenario(cfg)
    insts.append(sc.instance(0, sc.empty_cache()))


def progress(ev):
    print(f"  bucket (N={ev['bucket'][0]}, U={ev['bucket'][1]}) "
          f"chunk {ev['chunk'] + 1}/{ev['n_chunks']}: "
          f"{ev['batch']} windows, {ev['in_bytes'] / 1e6:.1f} MB in, "
          f"{ev['seconds']:.2f}s")


spec = GridSpec(kind="policy", insts=insts, seed=0, n_seeds=N_SEEDS,
                best_of=8, pdhg_iters=1200, episodes=EPISODES,
                devices=len(jax.devices()),
                chunk_size=max(N_VARIANTS // 8, 8),
                max_buckets=4, progress=progress)

print(f"{N_VARIANTS} variants x {N_SEEDS} seeds x {len(OFFLINE_POLICIES)} "
      f"policies, sharded across the host mesh:\n")
res = run_grid(spec)
st = res.stats

print(f"\nbucket plan (N_pad, U_pad, windows): {st['plan']}")
print(f"{st['chunks']} chunks on {st['devices']} devices in "
      f"{st['seconds']:.1f}s "
      f"({N_VARIANTS * N_SEEDS * len(OFFLINE_POLICIES) / st['seconds']:.0f} "
      "policy-windows/s)")
print(f"peak memory: {st['peak_chunk_in_bytes'] / 1e6:.1f} MB live per "
      f"chunk (a one-shot dispatch would pin "
      f"{st['grid_in_bytes'] / 1e6:.1f} MB of inputs); "
      f"process high-water "
      f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6:.2f} GB")

met = {p: np.asarray([[res.results[p][b][s][2]["avg_precision"]
                       for s in range(N_SEEDS)]
                      for b in range(N_VARIANTS)])
       for p in OFFLINE_POLICIES}
summary = improvement_ratio(met)
print("\ngrid-mean served precision per policy:")
for p in OFFLINE_POLICIES:
    print(f"  {p:8s}  {summary['means'][p]:.3f}")
print(f"\nCoCaR vs best baseline ({summary['best_baseline']}): "
      f"{summary['ratio']:.2f}x")
