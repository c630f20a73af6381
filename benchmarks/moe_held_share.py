"""Held share of the router's picks in the ``ep8-moonlight-poisson``
benchmark cell, per MoE layer, and the chunks of ``moe.held_rows`` rows
that the held experts' grouped products take for them.

The cell's weights and prompts are drawn from a run's ``--seed`` as the
cell draws them (``chipbench/drivers/serve_poisson_mla.py``); the first
``B`` prompts of the window are run through the served exit's prefill,
compiled with ``with_picks=True``, at each batch ``B``.  One JSON line per
batch: ``held_share`` (the held pairs over all tokens x top_k pairs) and
``chunks`` for every MoE layer, in order.

    PYTHONPATH=src python -m benchmarks.moe_held_share --seed <n>
"""
from __future__ import annotations

import argparse
import json
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import generator as G
from chipbench.drivers import serve_poisson_mla as D
from chipbench.harness import load_json, sub_seed
from repro.models import model as M
from repro.models import moe
from repro.models.config import build_plan, submodel_plan


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--batches", type=int, nargs="+", default=[1, 2, 4])
    ap.add_argument("--exit", type=int, default=-1,
                    help="the served exit (the cell places the last)")
    args = ap.parse_args(argv)
    cfg = load_json("chipbench", "configs", "ep8-moonlight-16b-a3b.json")
    tr = load_json("chipbench", "traffic", "poisson-serve-2k.json")
    mcfg = D.model_config(cfg)
    ex = args.exit % mcfg.n_exits
    params = D.program_params(cfg, mcfg, sub_seed(args.seed, "weights"))
    P = int(tr["prompt_tokens"])
    prompts = G.PoissonArrivals(tr, mcfg.vocab_size, int(cfg["pods"]),
                                30.0, sub_seed(args.seed, "traffic")).prompts
    plan = build_plan(mcfg)
    pf = jax.jit(partial(M.prefill, mcfg, exit_idx=ex, plan=plan,
                         with_picks=True))
    first, n = mcfg.held_experts
    for B in args.batches:
        cache = M.cache_init(mcfg, B, P + int(tr["new_tokens"]),
                             submodel_plan(plan, ex))
        _, _, picks = pf(params, {"tokens": jnp.asarray(prompts[:B])}, cache)
        picks = np.concatenate([np.asarray(a) for a in picks])  # (L, BP, k)
        held = np.sum((picks >= first) & (picks < first + n), axis=(1, 2))
        print(json.dumps({
            "seed": args.seed, "batch": B, "tokens": B * P, "exit": ex,
            "rows": moe.held_rows(mcfg, B * P),
            "held_share": [round(float(h), 5)
                           for h in held / picks[0].size],
            "chunks": moe.held_chunks(mcfg, picks).tolist(),
            "device": jax.devices()[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
