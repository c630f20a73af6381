"""Core layers: norms, RoPE, GQA attention (full / cached-decode / cross), FFN.

Pure-JAX, functional: every layer is ``fwd(cfg, params, x, ...)`` with params a
dict pytree.  All softmax / norm accumulation happens in float32 regardless of
the compute dtype.  Shapes use ``B`` batch, ``S`` sequence, ``D`` d_model,
``H`` q-heads, ``K`` kv-heads, ``E`` head_dim.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.flash import flash_attention

NEG_INF = -1e30


def _pick_block(n: int, target: int) -> int:
    """Largest divisor of n that is <= target."""
    for d in range(min(n, target), 0, -1):
        if n % d == 0:
            return d
    return n


def attend(q, k, v, *, causal=True, window=0, q_offset=0):
    """Dispatch between dense masked attention (small) and blocked flash
    attention (large, memory-bounded).  q: (B,S,H,E); k,v: (B,T,K,E)."""
    S, T = q.shape[1], k.shape[1]
    B, H, E = q.shape[0], q.shape[2], q.shape[3]
    if S >= 1024 and S * T > 4 * 1024 * 1024:
        bq = _pick_block(S, 512)
        bk = _pick_block(T, 1024)
        out = flash_attention(q, k, v, causal, window, q_offset, bq, bk)
        return out.reshape(B, S, H * E)
    qpos = q_offset + jnp.arange(S)
    kpos = jnp.arange(T)
    ok = jnp.ones((S, T), bool)
    if causal:
        ok = kpos[None, :] <= qpos[:, None]
    if window:
        ok = ok & (kpos[None, :] > qpos[:, None] - window)
    mask = jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)
    return gqa_attend(q, k, v, mask)


def cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * w.astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_tables(positions, dim, theta):
    """positions: (S,) int32 -> cos,sin (S, dim/2) float32."""
    half = dim // 2
    freqs = 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) * 2.0 / dim))
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin, fraction=1.0):
    """x: (B, S, H, E); rotate the first ``fraction`` of E pairwise."""
    e = x.shape[-1]
    rot = int(e * fraction)
    rot -= rot % 2
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    c = cos[None, :, None, : rot // 2].astype(jnp.float32)
    s = sin[None, :, None, : rot // 2].astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    o1 = x1f * c - x2f * s
    o2 = x2f * c + x1f * s
    out = jnp.stack([o1, o2], axis=-1).reshape(xr.shape).astype(x.dtype)
    return jnp.concatenate([out, xp], axis=-1) if rot < e else out


# --------------------------------------------------------------------------
# Attention
# --------------------------------------------------------------------------

def attn_init(key, cfg: ModelConfig, n_heads=None, n_kv=None):
    H = n_heads or cfg.n_heads
    Hp = cfg.n_heads_padded if n_heads is None else H
    K = n_kv or cfg.n_kv_heads
    E, D = cfg.head_dim, cfg.d_model
    k1, k2, k3, k4 = jax.random.split(key, 4)
    sd = D ** -0.5
    wq = jax.random.normal(k1, (D, Hp * E)) * sd
    wo = jax.random.normal(k4, (Hp * E, D)) * (H * E) ** -0.5
    if Hp > H:
        # zero-pad PER KV GROUP (the (K, G, E) reshape is kv-major, so tail
        # padding would rewire which kv head each q head attends to);
        # wo's padded rows MUST be zero so outputs are unchanged
        G, Gp = H // K, Hp // K
        wq = wq.reshape(D, K, Gp, E).at[:, :, G:, :].set(0.0).reshape(D, Hp * E)
        wo = wo.reshape(K, Gp, E, D).at[:, G:, :, :].set(0.0).reshape(Hp * E, D)
    p = {
        "wq": wq.astype(pdtype(cfg)),
        "wk": (jax.random.normal(k2, (D, K * E)) * sd).astype(pdtype(cfg)),
        "wv": (jax.random.normal(k3, (D, K * E)) * sd).astype(pdtype(cfg)),
        "wo": wo.astype(pdtype(cfg)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((Hp * E,), pdtype(cfg))
        p["bk"] = jnp.zeros((K * E,), pdtype(cfg))
        p["bv"] = jnp.zeros((K * E,), pdtype(cfg))
    if cfg.qk_norm:
        p["qn"] = jnp.ones((E,), pdtype(cfg))
        p["kn"] = jnp.ones((E,), pdtype(cfg))
    return p


def _qkv(cfg, p, x, n_heads, n_kv, positions, use_rope=True):
    B, S, _ = x.shape
    E = cfg.head_dim
    q = x @ p["wq"].astype(x.dtype)
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(B, S, n_heads, E)
    k = k.reshape(B, S, n_kv, E)
    v = v.reshape(B, S, n_kv, E)
    if cfg.qk_norm:
        q = rms_norm(q, p["qn"], cfg.norm_eps)
        k = rms_norm(k, p["kn"], cfg.norm_eps)
    if use_rope and cfg.rope_variant != "none":
        frac = 0.5 if cfg.rope_variant == "half" else 1.0
        cos, sin = rope_tables(positions, E, cfg.rope_theta)
        q = apply_rope(q, cos, sin, frac)
        k = apply_rope(k, cos, sin, frac)
    return q, k, v


def gqa_attend(q, k, v, mask):
    """q: (B,S,H,E), k: (B,T,K,E), v: (B,T,K,Ev), mask: (S,T) or (B,S,T)
    additive f32 -> (B, S, H*Ev)."""
    B, S, H, E = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, E)
    scores = jnp.einsum("bskge,btke->bkgst", qg, k).astype(jnp.float32)
    scores = scores * (E ** -0.5)
    m = mask if mask.ndim == 3 else mask[None]
    scores = scores + m[:, None, None, :, :]
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btke->bskge", w, v)
    return out.reshape(B, S, H * v.shape[-1])


def causal_mask(S, T=None, window=0, offset=0):
    """Additive (S,T) mask. offset = absolute position of query row 0."""
    T = T or S
    qpos = jnp.arange(S)[:, None] + offset
    kpos = jnp.arange(T)[None, :]
    ok = kpos <= qpos
    if window:
        ok &= kpos > qpos - window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def attn_fwd(cfg, p, x, positions, *, causal=True, window=0,
             n_heads=None, n_kv=None, use_rope=True):
    """Full (uncached) attention — training and encoder paths."""
    H = n_heads or cfg.n_heads_padded
    K = n_kv or cfg.n_kv_heads
    q, k, v = _qkv(cfg, p, x, H, K, positions, use_rope)
    out = attend(q, k, v, causal=causal, window=window)
    return out @ p["wo"].astype(x.dtype)


def attn_prefill(cfg, p, x, positions, cache_k, cache_v, *, window=0,
                 n_heads=None, n_kv=None):
    """Prefill: attend causally over x AND write k/v into the cache.

    cache_k/v: (B, Skv, K, E) with Skv >= S (or == window for SWA ring)."""
    H = n_heads or cfg.n_heads_padded
    K = n_kv or cfg.n_kv_heads
    q, k, v = _qkv(cfg, p, x, H, K, positions)
    S = x.shape[1]
    Skv = cache_k.shape[1]
    if window and Skv == window and S > window:
        # SWA ring buffer: retain only the trailing `window` tokens, placed at
        # slot (absolute_position % window) so decode can continue the ring.
        tail_k = jax.lax.dynamic_slice_in_dim(k, S - window, window, axis=1)
        tail_v = jax.lax.dynamic_slice_in_dim(v, S - window, window, axis=1)
        roll = S % window   # slot of absolute position (S - window)
        ck = jnp.roll(tail_k, roll, axis=1).astype(cache_k.dtype)
        cv = jnp.roll(tail_v, roll, axis=1).astype(cache_v.dtype)
    else:
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache_k, k.astype(cache_k.dtype), 0, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache_v, v.astype(cache_v.dtype), 0, axis=1)
    out = attend(q, k, v, causal=True, window=window)
    return out @ p["wo"].astype(x.dtype), ck, cv


def attn_decode(cfg, p, x1, pos, cache_k, cache_v, *, window=0,
                n_heads=None, n_kv=None):
    """Single-token decode. x1: (B,1,D); pos: scalar int32 (same across batch).

    cache is (B, Skv, K, E); for windowed attention Skv == window and the
    cache is a ring buffer indexed pos % window.
    """
    H = n_heads or cfg.n_heads_padded
    K = n_kv or cfg.n_kv_heads
    q, k, v = _qkv(cfg, p, x1, H, K, jnp.asarray(pos)[None])
    Skv = cache_k.shape[1]
    slot = pos % Skv if window else pos
    ck = jax.lax.dynamic_update_slice_in_dim(cache_k, k.astype(cache_k.dtype),
                                             slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache_v, v.astype(cache_v.dtype),
                                             slot, axis=1)
    kpos = jnp.arange(Skv)
    if window:
        valid = (kpos <= slot) | (pos >= Skv)   # ring fully valid once wrapped
    else:
        valid = kpos <= pos
    mask = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)[None, None, :]
    out = gqa_attend(q, ck.astype(x1.dtype), cv.astype(x1.dtype),
                     jnp.broadcast_to(mask, (x1.shape[0], 1, Skv)))
    return out @ p["wo"].astype(x1.dtype), ck, cv


def xattn_init(key, cfg: ModelConfig):
    return attn_init(key, cfg)


def xattn_fwd(cfg, p, x, enc_k, enc_v):
    """Cross attention against precomputed encoder K/V: (B, Senc, K, E)."""
    B, S, _ = x.shape
    H, K, E = cfg.n_heads_padded, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"].astype(x.dtype)).reshape(B, S, H, E)
    out = attend(q, enc_k.astype(x.dtype), enc_v.astype(x.dtype), causal=False)
    return out @ p["wo"].astype(x.dtype)


def xattn_kv(cfg, p, enc_out):
    B, T, _ = enc_out.shape
    K, E = cfg.n_kv_heads, cfg.head_dim
    k = (enc_out @ p["wk"].astype(enc_out.dtype)).reshape(B, T, K, E)
    v = (enc_out @ p["wv"].astype(enc_out.dtype)).reshape(B, T, K, E)
    return k, v


# --------------------------------------------------------------------------
# FFN
# --------------------------------------------------------------------------

def ffn_init(key, cfg: ModelConfig, gated=True):
    D, F = cfg.d_model, cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"w1": (jax.random.normal(k1, (D, F)) * D ** -0.5).astype(pdtype(cfg)),
         "w2": (jax.random.normal(k2, (F, D)) * F ** -0.5).astype(pdtype(cfg))}
    if gated:
        p["w3"] = (jax.random.normal(k3, (D, F)) * D ** -0.5).astype(pdtype(cfg))
    return p


def ffn_fwd(cfg, p, x, gated=True):
    h = x @ p["w1"].astype(x.dtype)
    if gated:
        h = jax.nn.silu(h) * (x @ p["w3"].astype(x.dtype))
    else:
        h = jax.nn.gelu(h)
    return h @ p["w2"].astype(x.dtype)


# --------------------------------------------------------------------------
# Embedding / exit heads
# --------------------------------------------------------------------------

def embed_init(key, cfg: ModelConfig):
    V, D = cfg.padded_vocab, cfg.d_model
    p = {"tok": (jax.random.normal(key, (V, D)) * 0.02).astype(pdtype(cfg))}
    if cfg.frontend in ("patch", "audio"):
        k2 = jax.random.fold_in(key, 1)
        p["adapter"] = (jax.random.normal(k2, (D, D)) * D ** -0.5).astype(pdtype(cfg))
        p["adapter_norm"] = jnp.ones((D,), pdtype(cfg))
    return p


def embed_tokens(cfg, p, tokens):
    return jnp.take(p["tok"], tokens, axis=0).astype(cdtype(cfg))


def embed_frontend(cfg, p, feats):
    """Stub modality frontend: precomputed embeddings -> adapter."""
    h = rms_norm(feats.astype(cdtype(cfg)), p["adapter_norm"], cfg.norm_eps)
    return h @ p["adapter"].astype(h.dtype)


def exit_head_init(key, cfg: ModelConfig):
    D, V = cfg.d_model, cfg.padded_vocab
    return {"norm": jnp.ones((D,), pdtype(cfg)),
            "head": (jax.random.normal(key, (D, V)) * D ** -0.5).astype(pdtype(cfg))}


def exit_head_fwd(cfg, p, x):
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    return h @ p["head"].astype(h.dtype)
