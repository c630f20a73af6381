"""Training launcher.

Smoke scale (CPU, default):
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
      --steps 200 --ckpt results/ckpt_run

Production scale (TPU pod; the same code path the dry-run compiles):
  python -m repro.launch.train --arch mixtral-8x7b --full --mesh 16x16

The loop is fault-tolerant: checkpoints are atomic and the launcher
auto-resumes from the latest complete one, so preempted jobs just re-run
the same command.
"""
from __future__ import annotations

import argparse

import jax


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--full", action="store_true",
                    help="full-size config on a production mesh (TPU)")
    ap.add_argument("--mesh", default="16x16", choices=["16x16", "2x16x16"])
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()

    from repro import configs
    from repro.distribution import sharding as shd
    from repro.launch.steps import init_train_state
    from repro.training.data import markov_stream
    from repro.training.loop import TrainConfig, train
    from repro.training.optim import AdamWConfig

    cfg = (configs.get_config(args.arch) if args.full
           else configs.get_smoke(args.arch))
    oc = AdamWConfig(lr=args.lr, total_steps=args.steps)

    if args.full:
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh(multi_pod=args.mesh == "2x16x16")
        shapes = jax.eval_shape(
            lambda: init_train_state(cfg, jax.random.key(args.seed)))
        psh = shd.named(mesh, shd.param_specs(cfg, mesh, shapes["params"]))
        print(f"mesh {mesh.shape}; params sharded FSDPxTP; "
              f"microbatches={cfg.train_microbatches}")
        with jax.set_mesh(mesh):
            _run(cfg, oc, args)
        return
    _run(cfg, oc, args)


def _run(cfg, oc, args):
    from repro.training.data import markov_stream
    from repro.training.loop import TrainConfig, train

    tc = TrainConfig(steps=args.steps, batch=args.batch, seq=args.seq,
                     ckpt_dir=args.ckpt, ckpt_every=args.ckpt_every,
                     log_every=max(args.steps // 20, 1), seed=args.seed)
    data = markov_stream(cfg.vocab_size, args.batch, args.seq,
                         args.steps + 8, seed=args.seed)
    state, hist = train(cfg, tc, data, oc=oc)
    print(f"done: final loss {hist[-1]['loss']:.4f}; "
          f"per-exit CE {[round(c, 3) for c in hist[-1]['ce_per_exit']]}")


if __name__ == "__main__":
    main()
