"""Top-level model API: init / apply_train / prefill / decode over a Plan.

The dynamic-DNN technique is built in: ``apply_train`` emits logits at every
exit head (multi-exit joint training, paper Sec. III), and the serve paths
take ``exit_idx`` so a *submodel* — a prefix of the segment list + its own
ExtNet head — can be executed directly, which is exactly what a BS serves
when submodel ``h_j`` is cached.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.distribution.sharding import hint, hint_btd
from repro.models import transformer as T
from repro.models.config import ModelConfig, Plan, build_plan
from repro.models.layers import (embed_frontend, embed_init,
                                 embed_tokens, exit_head_fwd, exit_head_init,
                                 rms_norm)


def sinusoidal(positions, D):
    half = D // 2
    freqs = np.exp(-np.log(10000.0) * np.arange(half, dtype=np.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init(cfg: ModelConfig, key) -> dict:
    plan = build_plan(cfg)
    keys = jax.random.split(key, len(plan.segments) + cfg.n_exits + 3)
    ki = iter(keys)
    params = {"embed": embed_init(next(ki), cfg), "segments": [], "exits": []}
    for seg in plan.segments:
        if seg.kind == "shared_attn":
            params["segments"].append({})       # weights live in params["shared"]
        else:
            params["segments"].append(
                T.seg_init(next(ki), cfg, seg.kind, seg.n_layers))
    if any(s.kind == "shared_attn" for s in plan.segments):
        params["shared"] = T.shared_attn_init(next(ki), cfg)
    for _ in range(cfg.n_exits):
        params["exits"].append(exit_head_init(next(ki), cfg))
    if plan.has_encoder:
        params["encoder"] = {
            "layers": T.seg_init(next(ki), cfg, "encoder", cfg.encoder_layers),
            "norm": jnp.ones((cfg.d_model,), jnp.dtype(cfg.param_dtype)),
        }
    return params


# ---------------------------------------------------------------------------
# embedding / encoder front
# ---------------------------------------------------------------------------

def _embed(cfg: ModelConfig, params, batch):
    """Returns decoder-side input hidden states (B, S, D)."""
    if cfg.family == "vlm":
        pe = embed_frontend(cfg, params["embed"], batch["patches"])
        te = embed_tokens(cfg, params["embed"], batch["tokens"])
        return hint_btd(jnp.concatenate([pe, te], axis=1))
    h = embed_tokens(cfg, params["embed"], batch["tokens"])
    if cfg.family == "encdec":
        S = h.shape[1]
        h = h + sinusoidal(jnp.arange(S), cfg.d_model)[None].astype(h.dtype)
    return hint_btd(h)


def run_encoder(cfg: ModelConfig, params, frames):
    """frames: (B, T, D) stub post-conv audio embeddings."""
    h = embed_frontend(cfg, params["embed"], frames)
    T_ = h.shape[1]
    h = h + sinusoidal(jnp.arange(T_), cfg.d_model)[None].astype(h.dtype)
    h, _ = T.seg_fwd(cfg, "encoder", params["encoder"]["layers"], None, h,
                     jnp.arange(T_))
    return rms_norm(h, params["encoder"]["norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# training forward: logits at every exit
# ---------------------------------------------------------------------------

def apply_train(cfg: ModelConfig, params, batch, plan: Plan = None,
                consume=None):
    """Forward with logits at every exit head (multi-exit joint training).

    ``consume(j, h)``, when given, is applied to the exit's *hidden states*
    as soon as they are produced (the loss computes its own chunked head+CE,
    so full (B,S,V) logits tensors are never materialized).
    """
    plan = plan or build_plan(cfg)
    h = _embed(cfg, params, batch)
    S = h.shape[1]
    positions = jnp.arange(S)
    enc_out = None
    if plan.has_encoder:
        enc_out = run_encoder(cfg, params, batch["frames"])

    exit_of_seg = {s: j for j, s in enumerate(plan.exit_after)}
    outs, aux = [], 0.0
    for seg in plan.segments:
        sp = params["segments"][seg.index]
        h, a = T.seg_fwd(cfg, seg.kind, sp, params.get("shared"), h, positions,
                         enc_kv=enc_out)
        aux = aux + a
        if seg.index in exit_of_seg:
            j = exit_of_seg[seg.index]
            if consume is None:
                lg = exit_head_fwd(cfg, params["exits"][j], h)
                outs.append(hint(lg, "batch", None, "model"))
            else:
                outs.append(consume(j, h))
    return outs, aux


# ---------------------------------------------------------------------------
# serving: prefill / decode with KV-and-state caches
# ---------------------------------------------------------------------------

def cache_init(cfg: ModelConfig, B: int, max_len: int, plan: Plan = None):
    plan = plan or build_plan(cfg)
    return [T.seg_cache_init(cfg, seg, B, max_len, enc_len=cfg.encoder_len)
            for seg in plan.segments]


def prefill(cfg: ModelConfig, params, batch, cache, exit_idx: int = -1,
            plan: Plan = None, with_picks: bool = False):
    """Returns (last-position logits (B, V), updated cache); with
    ``with_picks`` also the routed experts' picks of every MoE segment
    run, each (layers, B * S, top_k)."""
    plan = plan or build_plan(cfg)
    exit_idx = exit_idx % cfg.n_exits
    last_seg = plan.exit_after[exit_idx]
    h = _embed(cfg, params, batch)
    S = h.shape[1]
    positions = jnp.arange(S)
    enc_out = None
    if plan.has_encoder:
        enc_out = run_encoder(cfg, params, batch["frames"])

    new_cache, picks = list(cache), []
    for seg in plan.segments[: last_seg + 1]:
        sp = params["segments"][seg.index]
        out = T.seg_prefill(cfg, seg, sp, params.get("shared"), h, positions,
                            cache[seg.index], enc_out=enc_out,
                            with_picks=with_picks and seg.kind == "moe")
        h, new_cache[seg.index] = out[:2]
        picks += out[2:]
    logits = exit_head_fwd(cfg, params["exits"][exit_idx], h[:, -1:, :])
    return (logits[:, 0, :], new_cache) + ((picks,) if with_picks else ())


def decode(cfg: ModelConfig, params, tokens, pos, cache, exit_idx: int = -1,
           plan: Plan = None, with_picks: bool = False):
    """One decode step. tokens: (B, 1) int32, pos: scalar int32;
    ``with_picks`` as in :func:`prefill`."""
    plan = plan or build_plan(cfg)
    exit_idx = exit_idx % cfg.n_exits
    last_seg = plan.exit_after[exit_idx]
    h = embed_tokens(cfg, params["embed"], tokens)
    if cfg.family == "encdec":
        h = h + sinusoidal(jnp.asarray(pos)[None], cfg.d_model)[None].astype(h.dtype)

    new_cache, picks = list(cache), []
    for seg in plan.segments[: last_seg + 1]:
        sp = params["segments"][seg.index]
        out = T.seg_decode(cfg, seg, sp, params.get("shared"), h, pos,
                           cache[seg.index],
                           with_picks=with_picks and seg.kind == "moe")
        h, new_cache[seg.index] = out[:2]
        picks += out[2:]
    logits = exit_head_fwd(cfg, params["exits"][exit_idx], h)
    return (logits[:, 0, :], new_cache) + ((picks,) if with_picks else ())
