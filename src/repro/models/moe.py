"""Mixture-of-Experts layer: a router over every routed expert, the part of
the result that this chip's experts give, and always-on shared experts.

The router scores all ``n_experts`` routed experts of the published model,
in float32, and picks ``top_k`` per token:

* ``softmax`` (Mixtral): weights are the softmax's values at the picks;
* ``sigmoid_bias`` (DeepSeek-V3 ``noaux_tc`` with one group): scores are
  ``sigmoid(logits)``, the picks are the top-k of ``scores + bias`` (the
  correction bias steers selection only), and the weights are the scores
  at the picks.

Then the weights are divided by their sum over the picks and multiplied
by ``routed_scale``.  All of this is computed at the router's full width,
as every chip of an expert-parallel deployment computes it.

The layer holds the routed experts ``experts_held`` = (first, count) and
computes only their contribution: the (token, pick) pairs that land on a
held expert are sorted first, grouped by expert, and run through the
grouped products (``jax.lax.ragged_dot``) in chunks of :func:`held_rows`
sorted rows, chunk after chunk until every held pair is covered.  A chunk
is 1.25 times the held pairs that uniform routing gives, so one chunk
covers them at the usual routing and a skewed router takes more; where
every expert is held, and at decode, one chunk is every pair.  Every pair
is computed: no token is dropped at any batch, length or routing.  Pairs
routed elsewhere get nothing here; the chips that hold those experts
(absent on one chip) would add theirs.  The shared experts, one SwiGLU of
``n_shared_experts * moe_d_ff``, are added once for every token.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.layers import ffn_fwd, pdtype


def moe_init(key, cfg: ModelConfig):
    D, F, E = cfg.d_model, cfg.expert_d_ff, cfg.n_experts
    n = cfg.held_experts[1]
    k0, k1, k2, k3 = jax.random.split(key, 4)
    p = {
        "router": (jax.random.normal(k0, (D, E)) * D ** -0.5).astype(jnp.float32),
        "w1": (jax.random.normal(k1, (n, D, F)) * D ** -0.5).astype(pdtype(cfg)),
        "w3": (jax.random.normal(k2, (n, D, F)) * D ** -0.5).astype(pdtype(cfg)),
        "w2": (jax.random.normal(k3, (n, F, D)) * F ** -0.5).astype(pdtype(cfg)),
    }
    if cfg.router == "sigmoid_bias":
        p["bias"] = jnp.zeros((E,), jnp.float32)
    if cfg.n_shared_experts:
        Fs = cfg.n_shared_experts * F
        s1, s2, s3 = jax.random.split(jax.random.fold_in(key, 1), 3)
        p["shared"] = {
            "w1": (jax.random.normal(s1, (D, Fs)) * D ** -0.5).astype(pdtype(cfg)),
            "w3": (jax.random.normal(s2, (D, Fs)) * D ** -0.5).astype(pdtype(cfg)),
            "w2": (jax.random.normal(s3, (Fs, D)) * Fs ** -0.5).astype(pdtype(cfg)),
        }
    return p


def route(cfg: ModelConfig, p, xf):
    """xf (T, D) -> (scores (T, E) f32, picks (T, k) int32, weights (T, k)
    f32), over every routed expert."""
    # float32 at full precision: the TPU's default would round the router
    # to bfloat16, and a pick near a tie would then differ from the f32
    # gate the published models compute
    logits = jnp.dot(xf.astype(jnp.float32), p["router"],
                     precision=jax.lax.Precision.HIGHEST)
    if cfg.router == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
        select = scores
    elif cfg.router == "sigmoid_bias":
        scores = jax.nn.sigmoid(logits)
        select = scores + p["bias"]
    else:
        raise ValueError(f"unknown router {cfg.router!r}")
    _, idx = jax.lax.top_k(select, cfg.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return scores, idx, w * cfg.routed_scale


def held_rows(cfg: ModelConfig, tokens: int) -> int:
    """Rows of one chunk of the held experts' grouped products for
    ``tokens`` tokens: 1.25 times the held (token, pick) pairs of uniform
    routing, ``tokens * top_k * count / n_experts``, rounded up to whole
    128-row tiles, and never more than all ``tokens * top_k`` pairs."""
    _, n = cfg.held_experts
    pairs = tokens * cfg.top_k
    want = -(-5 * pairs * n // (4 * cfg.n_experts))
    return min(pairs, -(-want // 128) * 128)


def held_chunks(cfg: ModelConfig, picks) -> np.ndarray:
    """Chunks of :func:`held_rows` rows that each MoE layer runs for the
    router's ``picks`` (..., tokens, top_k): one for every ``held_rows``
    held pairs or part of it, and at least one."""
    picks = np.asarray(picks)
    first, n = cfg.held_experts
    held = np.sum((picks >= first) & (picks < first + n), axis=(-2, -1))
    return np.maximum(1, -(-held // held_rows(cfg, picks.shape[-2])))


def held_experts_fwd(cfg: ModelConfig, p, xf, idx, w):
    """The held experts' part of the routed output for xf (T, D): every
    (token, pick) pair on a held expert, grouped by expert, run in chunks
    of :func:`held_rows` sorted rows until every one is covered."""
    T, D = xf.shape
    k = idx.shape[-1]
    first, n = cfg.held_experts
    local = idx.reshape(-1) - first
    held = (local >= 0) & (local < n)
    eid = jnp.where(held, local, n)               # the rest sort after
    order = jnp.argsort(eid, stable=True)
    sizes = jnp.zeros((n + 1,), jnp.int32).at[eid].add(1)[:n]
    cum = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)])
    total = cum[-1]
    wf = w.reshape(-1)
    dt = xf.dtype
    C = held_rows(cfg, T)
    if C < T * k:
        # the last chunk may run past the pairs: pad so that its slice is
        # not moved back
        order = jnp.pad(order, (0, C))

    def chunk(s, acc):
        """The sorted rows [s, s + C): each group cut to the chunk."""
        rows = jax.lax.dynamic_slice_in_dim(order, s, C)
        part = jnp.clip(cum[1:] - s, 0, C) - jnp.clip(cum[:-1] - s, 0, C)
        tok = rows // k
        xs = xf[tok]
        h = jax.lax.ragged_dot(xs, p["w1"].astype(dt), part)
        g = jax.lax.ragged_dot(xs, p["w3"].astype(dt), part)
        y = jax.lax.ragged_dot(jax.nn.silu(h) * g, p["w2"].astype(dt), part)
        # the grouped product leaves the rows past its groups undefined
        # (any value, NaN too, may be there): select the held pairs, never
        # scale
        out = jnp.where((s + jnp.arange(C) < total)[:, None],
                        y.astype(jnp.float32) * wf[rows][:, None], 0.0)
        return acc.at[tok].add(out)

    acc = jnp.zeros((T, D), jnp.float32)
    if C == T * k:                  # every expert held, or decode: one chunk
        return chunk(0, acc).astype(dt)
    n_chunks = jnp.maximum(1, (total + C - 1) // C)
    # a static trip count, enough for every pair held, so that the loop can
    # be differentiated; the chunks past the held pairs are skipped
    acc = jax.lax.fori_loop(
        0, -(-T * k // C), lambda i, a: jax.lax.cond(
            i < n_chunks, lambda a: chunk(i * C, a), lambda a: a, a), acc)
    return acc.astype(dt)


def moe_fwd(cfg: ModelConfig, p, x):
    """x: (B, S, D) -> (out (B, S, D), aux_loss scalar f32)."""
    B, S, D = x.shape
    xf = x.reshape(B * S, D)
    scores, idx, w = route(cfg, p, xf)
    out = held_experts_fwd(cfg, p, xf, idx, w)
    if "shared" in p:
        out = out + ffn_fwd(cfg, p["shared"], xf)
    aux = jnp.float32(0.0)
    if cfg.router == "softmax":
        # Switch-style load balance: mean router probability times the
        # share of tokens that pick each expert
        me = jnp.mean(scores, axis=0)
        ce = jnp.mean(jnp.max(jax.nn.one_hot(idx, cfg.n_experts), 1), 0)
        aux = cfg.n_experts * jnp.sum(me * ce)
    return out.reshape(B, S, D), aux
