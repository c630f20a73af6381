"""Held routed experts of one MoE layer, three forms of one sum: the
grouped product over the held (token, pick) pairs in chunks of
``moe.held_rows`` sorted rows (``moe.held_experts_fwd``), the grouped
product over all T x top_k sorted pairs at once with the rest selected
away (``ragged``, the form before the chunks), and a dense product of
every token through every held expert, weighed by its routing weight
(zero where not picked).

At Moonlight-16B-A3B's widths (hidden 2048, experts of width 1408, 6
picks of 64 by the V3 router, 8 held) in bfloat16, for decode batches
and 2k-token prefills.  Each form is compiled once, then timed over
``--reps`` calls back to back, interleaved with the others; the least of
``--rounds`` rounds is kept.  One JSON line per shape: ``tokens``,
``chunked_ms``, ``ragged_ms``, ``dense_ms``, the held pairs the router
gave (``held_pairs``), the chunks they take (``chunks``) and the rows the
chunked form runs (``rows_run``), and the largest difference of each
form's output from the ragged form's, relative to its largest entry.

    PYTHONPATH=src python -m benchmarks.bench_moe_dispatch [--smoke]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.models import moe


def ragged_held(cfg, p, xf, idx, w):
    """The grouped products over all T x top_k sorted pairs, the held
    ones first, the rows past their groups selected away."""
    T, D = xf.shape
    k = idx.shape[-1]
    first, n = cfg.held_experts
    local = idx.reshape(-1) - first
    held = (local >= 0) & (local < n)
    eid = jnp.where(held, local, n)
    order = jnp.argsort(eid, stable=True)
    sizes = jnp.zeros((n + 1,), jnp.int32).at[eid].add(1)[:n]
    tok = order // k
    xs = xf[tok]
    dt = xf.dtype
    h = jax.lax.ragged_dot(xs, p["w1"].astype(dt), sizes)
    g = jax.lax.ragged_dot(xs, p["w3"].astype(dt), sizes)
    y = jax.lax.ragged_dot(jax.nn.silu(h) * g, p["w2"].astype(dt), sizes)
    out = jnp.where(held[order][:, None],
                    y.astype(jnp.float32) * w.reshape(-1)[order][:, None],
                    0.0)
    return jnp.zeros((T, D), jnp.float32).at[tok].add(out).astype(dt)


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

    forms = {"chunked": routed(moe.held_experts_fwd),
             "ragged": routed(ragged_held), "dense": routed(dense_held)}
    first, n = cfg.held_experts
    for T in tokens:
        xf = jax.random.normal(jax.random.fold_in(key, T),
                               (T, cfg.d_model)).astype(jnp.dtype(cfg.dtype))
        idx = np.asarray(jax.jit(lambda p, xf: moe.route(cfg, p, xf)[1])(
            p, xf))
        chunks = int(moe.held_chunks(cfg, idx))
        line = {"tokens": T,
                "held_pairs": int(np.sum((idx >= first) & (idx < first + n))),
                "chunks": chunks, "rows_run": chunks * moe.held_rows(cfg, T)}
        outs = {f: np.asarray(jax.block_until_ready(fn(p, xf)), np.float32)
                for f, fn in forms.items()}
        for f in ("chunked", "dense"):
            line[f"{f}_rel_diff"] = float(
                np.max(np.abs(outs[f] - outs["ragged"]))
                / (np.max(np.abs(outs["ragged"])) + 1e-30))
        best = dict.fromkeys(forms, float("inf"))
        for _ in range(args.rounds):
            for f, fn in forms.items():
                best[f] = min(best[f], _timed(fn, (p, xf), args.reps))
        print(json.dumps({**line, **{f"{f}_ms": v for f, v in best.items()},
                          "device": jax.devices()[0].device_kind}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
