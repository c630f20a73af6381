"""Segment stacks: stacked-and-scanned homogeneous layer groups.

Every segment kind provides init / fwd (train, full-seq) / prefill / decode /
cache_init with a uniform signature, so ``model.py`` can execute a Plan by
iterating segments.  Layer params are stacked on a leading ``L`` axis and run
with ``jax.lax.scan`` (small HLO, O(1) compile cost in depth) — which is also
what makes the paper's Δ-submodel loading a contiguous prefix slice.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.distribution.sharding import hint, hint_btd
from repro.models import mamba2, mla, moe, xlstm
from repro.models.config import ModelConfig, Segment
from repro.models.layers import (attn_decode, attn_fwd, attn_init,
                                 attn_prefill, ffn_fwd, ffn_init, pdtype,
                                 rms_norm, xattn_fwd, xattn_kv)


def _hint_stream(cfg, h):
    """Residual-stream constraint: batch over data; with seq_parallel also
    S over "model" (intended to elicit reduce-scatter + all-gather, Megatron
    SP — measured counterproductive under GSPMD here, see EXPERIMENTS.md
    §Perf; kept as an opt-in flag, default off)."""
    if cfg.seq_parallel and h.shape[1] > 1:
        return hint(h, "batch", "model", None)
    return hint_btd(h)


def _norm_init(cfg):
    return jnp.ones((cfg.d_model,), pdtype(cfg))


# ---------------------------------------------------------------------------
# per-layer inits
# ---------------------------------------------------------------------------

def _self_attn_init(key, cfg):
    return mla.mla_init(key, cfg) if cfg.attn_kind == "mla" \
        else attn_init(key, cfg)


def _dense_layer_init(key, cfg):
    k1, k2 = jax.random.split(key)
    return {"ln1": _norm_init(cfg), "attn": _self_attn_init(k1, cfg),
            "ln2": _norm_init(cfg), "ffn": ffn_init(k2, cfg, gated=True)}


def _moe_layer_init(key, cfg):
    k1, k2 = jax.random.split(key)
    return {"ln1": _norm_init(cfg), "attn": _self_attn_init(k1, cfg),
            "ln2": _norm_init(cfg), "moe": moe.moe_init(k2, cfg)}


def _mamba_layer_init(key, cfg):
    return {"ln": _norm_init(cfg), "mamba": mamba2.mamba_init(key, cfg)}


def _xdec_layer_init(key, cfg):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"ln1": _norm_init(cfg), "attn": attn_init(k1, cfg),
            "ln2": _norm_init(cfg), "xattn": attn_init(k2, cfg),
            "ln3": _norm_init(cfg), "ffn": ffn_init(k3, cfg, gated=False)}


def _enc_layer_init(key, cfg):
    k1, k2 = jax.random.split(key)
    return {"ln1": _norm_init(cfg), "attn": attn_init(k1, cfg),
            "ln2": _norm_init(cfg), "ffn": ffn_init(k2, cfg, gated=False)}


_LAYER_INIT = {
    "dense": _dense_layer_init,
    "moe": _moe_layer_init,
    "mamba": _mamba_layer_init,
    "mlstm": xlstm.mlstm_init,
    "slstm": xlstm.slstm_init,
    "xdec": _xdec_layer_init,
    "encoder": _enc_layer_init,
}


def seg_init(key, cfg: ModelConfig, kind: str, n_layers: int):
    keys = jax.random.split(key, n_layers)
    return jax.vmap(lambda k: _LAYER_INIT[kind](k, cfg))(keys)


def shared_attn_init(key, cfg: ModelConfig):
    """zamba2's shared attention+MLP block (one copy, applied many times)."""
    k1, k2 = jax.random.split(key)
    return {"ln1": _norm_init(cfg), "attn": attn_init(k1, cfg),
            "ln2": _norm_init(cfg), "ffn": ffn_init(k2, cfg, gated=True)}


# ---------------------------------------------------------------------------
# per-layer forwards (single layer; used inside scan)
# ---------------------------------------------------------------------------

def _self_attn_fwd(cfg, p, x, positions):
    """Causal self-attention of a dense or MoE layer, without a cache."""
    if cfg.attn_kind == "mla":
        return mla.mla_fwd(cfg, p, x, positions)[0]
    return attn_fwd(cfg, p, x, positions, window=cfg.sliding_window)


def _dense_fwd(cfg, lp, h, positions):
    h = h + _self_attn_fwd(cfg, lp["attn"],
                           rms_norm(h, lp["ln1"], cfg.norm_eps), positions)
    h = h + ffn_fwd(cfg, lp["ffn"], rms_norm(h, lp["ln2"], cfg.norm_eps),
                    gated=True)
    return h


def _moe_fwd(cfg, lp, h, positions):
    h = h + _self_attn_fwd(cfg, lp["attn"],
                           rms_norm(h, lp["ln1"], cfg.norm_eps), positions)
    mo, aux = moe.moe_fwd(cfg, lp["moe"], rms_norm(h, lp["ln2"], cfg.norm_eps))
    return h + mo, aux


def _enc_fwd(cfg, lp, h, positions):
    h = h + attn_fwd(cfg, lp["attn"], rms_norm(h, lp["ln1"], cfg.norm_eps),
                     positions, causal=False, use_rope=False)
    h = h + ffn_fwd(cfg, lp["ffn"], rms_norm(h, lp["ln2"], cfg.norm_eps),
                    gated=False)
    return h


# ---------------------------------------------------------------------------
# segment stack: train forward
# ---------------------------------------------------------------------------

def seg_fwd(cfg: ModelConfig, kind: str, sp, shared, h, positions, enc_kv=None):
    """Full-sequence forward of one segment. Returns (h, aux_loss)."""
    if kind == "shared_attn":
        lp = shared
        h = h + attn_fwd(cfg, lp["attn"], rms_norm(h, lp["ln1"], cfg.norm_eps),
                         positions)
        h = h + ffn_fwd(cfg, lp["ffn"], rms_norm(h, lp["ln2"], cfg.norm_eps))
        return h, 0.0

    if kind == "xdec":
        return _xdec_seg_fwd(cfg, sp, h, positions, enc_kv)

    if kind == "dense":
        body = lambda hh, lp: (_dense_fwd(cfg, lp, hh, positions), 0.0)
    elif kind == "moe":
        body = lambda hh, lp: _moe_fwd(cfg, lp, hh, positions)
    elif kind == "mamba":
        body = lambda hh, lp: (
            hh + mamba2.mamba_fwd(cfg, lp["mamba"],
                                  rms_norm(hh, lp["ln"], cfg.norm_eps)), 0.0)
    elif kind == "mlstm":
        body = lambda hh, lp: (xlstm.mlstm_fwd(cfg, lp, hh), 0.0)
    elif kind == "slstm":
        body = lambda hh, lp: (xlstm.slstm_fwd(cfg, lp, hh), 0.0)
    elif kind == "encoder":
        body = lambda hh, lp: (_enc_fwd(cfg, lp, hh, positions), 0.0)
    else:
        raise ValueError(kind)

    fn = jax.checkpoint(body, prevent_cse=False) if cfg.remat else body
    h, auxs = jax.lax.scan(lambda hh, lp: fn(_hint_stream(cfg, hh), lp), h, sp)
    return h, jnp.sum(jnp.asarray(auxs))


def _xdec_seg_fwd(cfg, sp, h, positions, enc_out):
    """Whisper-style decoder segment: self-attn + cross-attn + FFN.

    enc_out: (B, T, D) encoder output (cross K/V computed per layer)."""
    def body(hh, lp):
        hh = hint_btd(hh)
        hh = hh + attn_fwd(cfg, lp["attn"],
                           rms_norm(hh, lp["ln1"], cfg.norm_eps), positions,
                           use_rope=False)
        ek, ev = xattn_kv(cfg, lp["xattn"], enc_out)
        hh = hh + xattn_fwd(cfg, lp["xattn"],
                            rms_norm(hh, lp["ln2"], cfg.norm_eps), ek, ev)
        hh = hh + ffn_fwd(cfg, lp["ffn"], rms_norm(hh, lp["ln3"], cfg.norm_eps),
                          gated=False)
        return hh, 0.0

    fn = jax.checkpoint(body, prevent_cse=False) if cfg.remat else body
    h, _ = jax.lax.scan(lambda hh, lp: fn(hh, lp), h, sp)
    return h, 0.0


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def seg_cache_init(cfg: ModelConfig, seg: Segment, B: int, max_len: int,
                   enc_len: int = 0):
    L = seg.n_layers
    K, E = cfg.n_kv_heads, cfg.head_dim
    kv_dt = jnp.dtype(cfg.dtype)
    skv = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    if seg.kind in ("dense", "moe") and cfg.attn_kind == "mla":
        # the latent cache: normed c_kv and the roped, head-shared k_pe
        return {"c": jnp.zeros((L, B, max_len, cfg.kv_lora_rank), kv_dt),
                "kpe": jnp.zeros((L, B, max_len, cfg.qk_rope_dim), kv_dt)}
    if seg.kind in ("dense", "moe"):
        return {"k": jnp.zeros((L, B, skv, K, E), kv_dt),
                "v": jnp.zeros((L, B, skv, K, E), kv_dt)}
    if seg.kind == "shared_attn":
        return {"k": jnp.zeros((B, max_len, K, E), kv_dt),
                "v": jnp.zeros((B, max_len, K, E), kv_dt)}
    if seg.kind == "mamba":
        c = mamba2.mamba_cache_init(cfg, B)
        return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (L,) + a.shape), c)
    if seg.kind == "mlstm":
        c = xlstm.mlstm_cache_init(cfg, B)
        return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (L,) + a.shape), c)
    if seg.kind == "slstm":
        c = xlstm.slstm_cache_init(cfg, B)
        return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (L,) + a.shape), c)
    if seg.kind == "xdec":
        return {"k": jnp.zeros((L, B, max_len, K, E), kv_dt),
                "v": jnp.zeros((L, B, max_len, K, E), kv_dt),
                "xk": jnp.zeros((L, B, enc_len, K, E), kv_dt),
                "xv": jnp.zeros((L, B, enc_len, K, E), kv_dt)}
    raise ValueError(seg.kind)


# ---------------------------------------------------------------------------
# segment stack: prefill
# ---------------------------------------------------------------------------

def _mlp(cfg, kind, lp, h, with_picks=False):
    """The feed-forward half of a cached dense or MoE layer: (its output,
    to be added to the residual stream ``h``; with ``with_picks`` the MoE
    router's picks (B * S, top_k), else None)."""
    hn = rms_norm(h, lp["ln2"], cfg.norm_eps)
    if kind != "moe":
        return ffn_fwd(cfg, lp["ffn"], hn), None
    picks = None
    if with_picks:
        picks = moe.route(cfg, lp["moe"], hn.reshape(-1, hn.shape[-1]))[1]
    return moe.moe_fwd(cfg, lp["moe"], hn)[0], picks


def seg_prefill(cfg: ModelConfig, seg: Segment, sp, shared, h, positions,
                cache, enc_out=None, with_picks=False):
    """(h, cache) after the segment; a dense or MoE segment with
    ``with_picks`` also gives its router's picks, (layers, B * S, top_k)
    for MoE layers and None for dense ones."""
    kind = seg.kind
    if kind == "shared_attn":
        lp = shared
        a, ck, cv = attn_prefill(cfg, lp["attn"],
                                 rms_norm(h, lp["ln1"], cfg.norm_eps),
                                 positions, cache["k"], cache["v"])
        h = h + a
        h = h + ffn_fwd(cfg, lp["ffn"], rms_norm(h, lp["ln2"], cfg.norm_eps))
        return h, {"k": ck, "v": cv}

    if kind in ("dense", "moe"):
        def body(hh, xs):
            lp, lc = xs
            hh = hint_btd(hh)
            hn = rms_norm(hh, lp["ln1"], cfg.norm_eps)
            if cfg.attn_kind == "mla":
                a, c2, p2 = mla.mla_prefill(cfg, lp["attn"], hn, positions,
                                            lc["c"], lc["kpe"])
                lc2 = {"c": c2, "kpe": p2}
            else:
                a, ck2, cv2 = attn_prefill(cfg, lp["attn"], hn, positions,
                                           lc["k"], lc["v"],
                                           window=cfg.sliding_window)
                lc2 = {"k": ck2, "v": cv2}
            hh = hh + a
            out, picks = _mlp(cfg, kind, lp, hh, with_picks)
            return hh + out, (lc2, picks)

        h, (c, picks) = jax.lax.scan(body, h, (sp, cache))
        return (h, c, picks) if with_picks else (h, c)

    if kind == "mamba":
        def body(hh, xs):
            lp, _ = xs
            hh = hint_btd(hh)
            out, c = mamba2.mamba_prefill(cfg, lp["mamba"],
                                          rms_norm(hh, lp["ln"], cfg.norm_eps))
            return hh + out, c

        h, c = jax.lax.scan(body, h, (sp, cache))
        return h, c

    if kind == "mlstm":
        def body(hh, xs):
            lp, _ = xs
            out, st = xlstm.mlstm_fwd(cfg, lp, hint_btd(hh), return_state=True)
            return out, st

        h, st = jax.lax.scan(body, h, (sp, cache))
        return h, st

    if kind == "slstm":
        def body(hh, xs):
            lp, _ = xs
            out, st = xlstm.slstm_fwd(cfg, lp, hint_btd(hh), return_state=True)
            return out, st

        h, st = jax.lax.scan(body, h, (sp, cache))
        return h, st

    if kind == "xdec":
        def body(hh, xs):
            lp, ck, cv, _, _ = xs
            hh = hint_btd(hh)
            a, ck2, cv2 = attn_prefill(cfg, lp["attn"],
                                       rms_norm(hh, lp["ln1"], cfg.norm_eps),
                                       positions, ck, cv)
            hh = hh + a
            ek, ev = xattn_kv(cfg, lp["xattn"], enc_out)
            hh = hh + xattn_fwd(cfg, lp["xattn"],
                                rms_norm(hh, lp["ln2"], cfg.norm_eps), ek, ev)
            hh = hh + ffn_fwd(cfg, lp["ffn"],
                              rms_norm(hh, lp["ln3"], cfg.norm_eps), gated=False)
            return hh, (ck2, cv2, ek.astype(ck2.dtype), ev.astype(cv2.dtype))

        h, (ck, cv, xk, xv) = jax.lax.scan(
            body, h, (sp, cache["k"], cache["v"], cache["xk"], cache["xv"]))
        return h, {"k": ck, "v": cv, "xk": xk, "xv": xv}

    raise ValueError(kind)


# ---------------------------------------------------------------------------
# segment stack: decode (one token)
# ---------------------------------------------------------------------------

def seg_decode(cfg: ModelConfig, seg: Segment, sp, shared, h1, pos, cache,
               with_picks=False):
    """(h1, cache) after the segment; ``with_picks`` as in
    :func:`seg_prefill`."""
    kind = seg.kind
    if kind == "shared_attn":
        lp = shared
        a, ck, cv = attn_decode(cfg, lp["attn"],
                                rms_norm(h1, lp["ln1"], cfg.norm_eps), pos,
                                cache["k"], cache["v"])
        h1 = h1 + a
        h1 = h1 + ffn_fwd(cfg, lp["ffn"], rms_norm(h1, lp["ln2"], cfg.norm_eps))
        return h1, {"k": ck, "v": cv}

    if kind in ("dense", "moe"):
        def body(hh, xs):
            lp, lc = xs
            hh = hint_btd(hh)
            hn = rms_norm(hh, lp["ln1"], cfg.norm_eps)
            if cfg.attn_kind == "mla":
                a, c2, p2 = mla.mla_decode(cfg, lp["attn"], hn, pos,
                                           lc["c"], lc["kpe"])
                lc2 = {"c": c2, "kpe": p2}
            else:
                a, ck2, cv2 = attn_decode(cfg, lp["attn"], hn, pos,
                                          lc["k"], lc["v"],
                                          window=cfg.sliding_window)
                lc2 = {"k": ck2, "v": cv2}
            hh = hh + a
            out, picks = _mlp(cfg, kind, lp, hh, with_picks)
            return hh + out, (lc2, picks)

        h1, (c, picks) = jax.lax.scan(body, h1, (sp, cache))
        return (h1, c, picks) if with_picks else (h1, c)

    if kind == "mamba":
        def body(hh, xs):
            lp, c = xs
            hh = hint_btd(hh)
            out, c2 = mamba2.mamba_decode(cfg, lp["mamba"],
                                          rms_norm(hh, lp["ln"], cfg.norm_eps), c)
            return hh + out, c2

        h1, c = jax.lax.scan(body, h1, (sp, cache))
        return h1, c

    if kind == "mlstm":
        def body(hh, xs):
            lp, c = xs
            out, c2 = xlstm.mlstm_decode(cfg, lp, hint_btd(hh), c)
            return out, c2

        h1, c = jax.lax.scan(body, h1, (sp, cache))
        return h1, c

    if kind == "slstm":
        def body(hh, xs):
            lp, c = xs
            out, c2 = xlstm.slstm_decode(cfg, lp, hint_btd(hh), c)
            return out, c2

        h1, c = jax.lax.scan(body, h1, (sp, cache))
        return h1, c

    if kind == "xdec":
        def body(hh, xs):
            lp, ck, cv, xk, xv = xs
            hh = hint_btd(hh)
            a, ck2, cv2 = attn_decode(cfg, lp["attn"],
                                      rms_norm(hh, lp["ln1"], cfg.norm_eps),
                                      pos, ck, cv)
            hh = hh + a
            hh = hh + xattn_fwd(cfg, lp["xattn"],
                                rms_norm(hh, lp["ln2"], cfg.norm_eps), xk, xv)
            hh = hh + ffn_fwd(cfg, lp["ffn"],
                              rms_norm(hh, lp["ln3"], cfg.norm_eps), gated=False)
            return hh, (ck2, cv2, xk, xv)

        h1, (ck, cv, xk, xv) = jax.lax.scan(
            body, h1, (sp, cache["k"], cache["v"], cache["xk"], cache["xv"]))
        return h1, {"k": ck, "v": cv, "xk": xk, "xv": xv}

    raise ValueError(kind)
