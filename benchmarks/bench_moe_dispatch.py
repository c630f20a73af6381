"""Held routed experts of one MoE layer: the grouped product over every
(token, pick) pair (``moe.held_experts_fwd``, ``jax.lax.ragged_dot``)
against a dense product of every token through every held expert,
weighed by its routing weight (zero where not picked).

At Moonlight-16B-A3B's widths (hidden 2048, experts of width 1408, 6
picks of 64 by the V3 router, 8 held) in bfloat16, for decode batches
and 2k-token prefills.  Each form is compiled once, then timed over
``--reps`` calls back to back, interleaved with the other form; the
least of ``--rounds`` rounds is kept.  One JSON line per shape:
``tokens``, ``ragged_ms``, ``dense_ms``, and the largest difference
of the two outputs relative to the output's largest entry.

    PYTHONPATH=src python -m benchmarks.bench_moe_dispatch [--smoke]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.models import moe


def dense_held(cfg, p, xf, idx, w):
    """Every token through every held expert, weighed by its routing
    weight: the same sum as ``moe.held_experts_fwd``."""
    first, n = cfg.held_experts
    dt = xf.dtype
    g = jnp.sum(jnp.where(idx[..., None] == first + jnp.arange(n),
                          w[..., None], 0.0), 1)                     # (T, n)
    h = jnp.einsum("td,edf->etf", xf, p["w1"].astype(dt))
    u = jnp.einsum("td,edf->etf", xf, p["w3"].astype(dt))
    y = jnp.einsum("etf,efd->etd", jax.nn.silu(h) * u, p["w2"].astype(dt))
    return jnp.einsum("etd,te->td", y.astype(jnp.float32), g).astype(dt)


def _timed(fn, args, reps):
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t) / reps


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="the configuration's SMOKE widths, few reps")
    ap.add_argument("--reps", type=int, default=50)
    ap.add_argument("--rounds", type=int, default=5)
    args = ap.parse_args(argv)
    cfg = (configs.get_smoke if args.smoke else configs.get_config)(
        "moonlight-16b-a3b")
    tokens = [1, 2, 4, 8, 2048, 8 * 2048]
    if args.smoke:
        tokens, args.reps, args.rounds = [1, 8, 256], 2, 1
    key = jax.random.key(0)
    p = moe.moe_init(key, cfg)
    p["bias"] = 0.05 * jax.random.normal(jax.random.fold_in(key, 2),
                                         p["bias"].shape)

    def routed(fwd):
        def f(p, xf):
            _, idx, w = moe.route(cfg, p, xf)
            return fwd(cfg, p, xf, idx, w)
        return jax.jit(f)

    ragged, dense = routed(moe.held_experts_fwd), routed(dense_held)
    for T in tokens:
        xf = jax.random.normal(jax.random.fold_in(key, T),
                               (T, cfg.d_model)).astype(jnp.dtype(cfg.dtype))
        a = jax.block_until_ready(ragged(p, xf))
        b = jax.block_until_ready(dense(p, xf))
        diff = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32)))
                     / (jnp.max(jnp.abs(a.astype(jnp.float32))) + 1e-30))
        best = {"ragged_ms": float("inf"), "dense_ms": float("inf")}
        for _ in range(args.rounds):
            best["ragged_ms"] = min(best["ragged_ms"],
                                    _timed(ragged, (p, xf), args.reps))
            best["dense_ms"] = min(best["dense_ms"],
                                   _timed(dense, (p, xf), args.reps))
        print(json.dumps({"tokens": T, **best, "rel_diff": diff,
                          "device": jax.devices()[0].device_kind}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
