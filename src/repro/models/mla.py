"""Multi-head latent attention (MLA), as DeepSeek-V3 publishes it
(arXiv:2412.19437 Sec. 2.1), without a LoRA on q.

Per token ``h`` (B, S, D), ``H`` heads:

* ``q = h W_q`` -> per head ``[q_nope (Dn) | q_pe (Dr)]``;
* ``[c_kv (R) | k_pe (Dr)] = h W_kv_a``, ``c_kv = RMSNorm(c_kv)``;
* ``[k_nope (Dn) | v (Dv)] = c_kv W_kv_b`` per head;
* RoPE on ``q_pe`` and on ``k_pe``, which every head shares;
* scores ``(q_nope . k_nope + q_pe . k_pe) / sqrt(Dn + Dr)``, softmax in
  float32, the heads' outputs through ``W_o``.

The cache holds, per layer and token, only the normed latent ``c_kv`` and
the roped ``k_pe`` (``R + Dr`` values).  Prefill attends in the expanded
form above and writes them.  Decode attends over the latent directly (the
"absorbed" form): ``q_nope`` is taken through the key half of ``W_kv_b``
into the latent, the scores are dot products with the cached ``c_kv``,
and the weighted sum of ``c_kv`` goes out through the value half.  No
per-head K or V is made over the context.

RoPE convention: :func:`repro.models.layers.apply_rope` turns dimensions
``2i`` and ``2i + 1`` of ``q_pe`` and ``k_pe`` together at frequency
``theta^(-2i / Dr)``.  The published checkpoint's code pairs them the same
way: ``apply_rotary_pos_emb`` de-interleaves ``(2i, 2i + 1)`` to
``(i, i + Dr/2)`` before ``rotate_half``.  Its output order differs, but
q and k are permuted alike, so every score is the same and the
checkpoint's ``W_q`` and ``W_kv_a`` columns are used as they are.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.flash import flash_attention
from repro.models.layers import (NEG_INF, apply_rope, causal_mask,
                                 gqa_attend, pdtype, rms_norm, rope_tables)

#: prompts at least this long, in whole blocks of this many, attend
#: blockwise (``models/flash.py``)
FLASH_MIN_LEN = 1024


def mla_init(key, cfg: ModelConfig):
    D, H, R = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    Dn, Dr, Dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    normal = lambda k, shape, fan_in: (jax.random.normal(k, shape)
                                       * fan_in ** -0.5).astype(pdtype(cfg))
    return {"wq": normal(k1, (D, H * (Dn + Dr)), D),
            "wkv_a": normal(k2, (D, R + Dr), D),
            "kv_norm": jnp.ones((R,), pdtype(cfg)),
            "wkv_b": normal(k3, (R, H * (Dn + Dv)), R),
            "wo": normal(k4, (H * Dv, D), H * Dv)}


def _project(cfg, p, x, positions):
    """(q_nope (B,S,H,Dn), q_pe (B,S,H,Dr) roped, c_kv (B,S,R) normed,
    k_pe (B,S,Dr) roped)."""
    B, S, _ = x.shape
    H, R, Dn = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_dim
    q = (x @ p["wq"].astype(x.dtype)).reshape(B, S, H, cfg.qk_head_dim)
    kv = x @ p["wkv_a"].astype(x.dtype)
    c = rms_norm(kv[..., :R], p["kv_norm"], cfg.norm_eps)
    cos, sin = rope_tables(positions, cfg.qk_rope_dim, cfg.rope_theta)
    q_pe = apply_rope(q[..., Dn:], cos, sin)
    k_pe = apply_rope(kv[..., None, R:], cos, sin)[:, :, 0]
    return q[..., :Dn], q_pe, c, k_pe


def _attend(q, k, v):
    """Causal attention of q (B,S,H,E) over k (B,S,H,E), v (B,S,H,Ev)."""
    S = q.shape[1]
    if S >= FLASH_MIN_LEN and S % FLASH_MIN_LEN == 0:
        out = flash_attention(q, k, v, True, 0, 0, 512, 1024)
        return out.reshape(q.shape[:2] + (-1,))
    return gqa_attend(q, k, v, causal_mask(S))


def mla_fwd(cfg, p, x, positions):
    """Full causal self-attention in the expanded form.  Returns (out
    (B,S,D), c_kv (B,S,R), k_pe (B,S,Dr)): what prefill caches."""
    B, S, _ = x.shape
    H, Dn, Dr = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    q_nope, q_pe, c, k_pe = _project(cfg, p, x, positions)
    kv = (c @ p["wkv_b"].astype(x.dtype)).reshape(B, S, H, -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    k = jnp.concatenate(
        [kv[..., :Dn], jnp.broadcast_to(k_pe[:, :, None], (B, S, H, Dr))], -1)
    out = _attend(q, k, kv[..., Dn:])
    return out @ p["wo"].astype(x.dtype), c, k_pe


def mla_prefill(cfg, p, x, positions, cache_c, cache_pe):
    """Prefill: attend causally over x and write its latent and roped key
    into the cache (B, T, R) and (B, T, Dr), T >= S."""
    out, c, k_pe = mla_fwd(cfg, p, x, positions)
    cc = jax.lax.dynamic_update_slice_in_dim(
        cache_c, c.astype(cache_c.dtype), 0, axis=1)
    cp = jax.lax.dynamic_update_slice_in_dim(
        cache_pe, k_pe.astype(cache_pe.dtype), 0, axis=1)
    return out, cc, cp


def mla_decode(cfg, p, x1, pos, cache_c, cache_pe):
    """One token x1 (B,1,D) at position ``pos`` (scalar, the same across
    the batch), attending over the latent cache in the absorbed form."""
    B = x1.shape[0]
    H, R = cfg.n_heads, cfg.kv_lora_rank
    Dn, Dv = cfg.qk_nope_dim, cfg.v_head_dim
    q_nope, q_pe, c, k_pe = _project(cfg, p, x1, jnp.asarray(pos)[None])
    cc = jax.lax.dynamic_update_slice_in_dim(
        cache_c, c.astype(cache_c.dtype), pos, axis=1)
    cp = jax.lax.dynamic_update_slice_in_dim(
        cache_pe, k_pe.astype(cache_pe.dtype), pos, axis=1)
    wkv_b = p["wkv_b"].astype(x1.dtype).reshape(R, H, Dn + Dv)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], wkv_b[..., :Dn])
    f32 = jnp.float32
    s = (jnp.einsum("bhr,btr->bht", q_lat, cc, preferred_element_type=f32)
         + jnp.einsum("bhp,btp->bht", q_pe[:, 0], cp,
                      preferred_element_type=f32)) * cfg.qk_head_dim ** -0.5
    s = jnp.where(jnp.arange(cc.shape[1]) <= pos, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1).astype(cc.dtype)
    o_lat = jnp.einsum("bht,btr->bhr", w, cc)
    o = jnp.einsum("bhr,rhv->bhv", o_lat, wkv_b[..., Dn:])
    out = o.reshape(B, 1, H * Dv) @ p["wo"].astype(x1.dtype)
    return out, cc, cp
