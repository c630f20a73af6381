"""Mixture-of-Experts layer (Mixtral-style top-2), GShard einsum dispatch.

TPU-native formulation: tokens are reshaped into groups of ``moe_group_size``;
within each group a capacity-bounded one-hot dispatch tensor routes tokens to
experts via einsum (no scatter/gather), which shards cleanly under GSPMD:
the group axis follows the batch ("data") sharding and each expert's hidden
dim shards over "model".  HLO FLOPs ≈ capacity_factor × active-expert FLOPs,
so the roofline's MODEL_FLOPS/HLO_FLOPs ratio stays honest.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import pdtype


def moe_init(key, cfg: ModelConfig):
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    k0, k1, k2, k3 = jax.random.split(key, 4)
    return {
        "router": (jax.random.normal(k0, (D, E)) * D ** -0.5).astype(jnp.float32),
        "w1": (jax.random.normal(k1, (E, D, F)) * D ** -0.5).astype(pdtype(cfg)),
        "w3": (jax.random.normal(k2, (E, D, F)) * D ** -0.5).astype(pdtype(cfg)),
        "w2": (jax.random.normal(k3, (E, F, D)) * F ** -0.5).astype(pdtype(cfg)),
    }


def _capacity(cfg: ModelConfig, group: int) -> int:
    c = int(cfg.capacity_factor * group * cfg.top_k / cfg.n_experts)
    return max(cfg.top_k, (c + 3) // 4 * 4)


def moe_fwd(cfg: ModelConfig, p, x):
    """x: (B, S, D) -> (out (B, S, D), aux_loss scalar f32)."""
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    T = B * S
    # one group of all tokens at decode (S==1): per-token groups waste
    # capacity slots (C >= top_k each); groups never cross batch rows when
    # S % g == 0, so train/prefill reshapes stay local
    g = min(cfg.moe_group_size, T)
    xf = x.reshape(T, D)
    valid = None
    if T % g:
        pad = g - T % g
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
        valid = jnp.arange(T + pad) < T       # pads get no expert assignment
        T = T + pad
    G = T // g
    C = _capacity(cfg, g)

    xg = xf.reshape(G, g, D)
    logits = (xg.astype(jnp.float32) @ p["router"]).astype(jnp.float32)  # (G,g,E)
    probs = jax.nn.softmax(logits, axis=-1)

    gate_vals, gate_idx = jax.lax.top_k(probs, k)                        # (G,g,k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # one-hot expert assignment per slot: (G, g, k, E)
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)
    if valid is not None:
        onehot = onehot * valid.reshape(G, g)[:, :, None, None]
    # position of each (token, slot) within its expert queue, slot-major so
    # first-choice assignments win capacity over second choices.
    flat = onehot.transpose(0, 2, 1, 3).reshape(G, k * g, E)
    pos_in_expert = jnp.cumsum(flat, axis=1) - flat                      # (G,kg,E)
    pos_in_expert = pos_in_expert.reshape(G, k, g, E).transpose(0, 2, 1, 3)
    pos = jnp.sum(pos_in_expert * onehot, axis=-1)                       # (G,g,k)
    keep = (pos < C).astype(jnp.float32)

    # dispatch (G,g,E,C) one-hot; combine adds gate weights
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), C,
                            dtype=jnp.float32) * keep[..., None]
    disp = jnp.einsum("gske,gskc->gsec", onehot, pos_oh)                 # (G,g,E,C)
    comb = jnp.einsum("gsk,gske,gskc->gsec", gate_vals, onehot, pos_oh)

    xin = jnp.einsum("gsec,gsd->gecd", disp.astype(x.dtype), xg)         # (G,E,C,D)
    h = jnp.einsum("gecd,edf->gecf", xin, p["w1"].astype(x.dtype))
    h = jax.nn.silu(h) * jnp.einsum("gecd,edf->gecf", xin, p["w3"].astype(x.dtype))
    hout = jnp.einsum("gecf,efd->gecd", h, p["w2"].astype(x.dtype))
    out = jnp.einsum("gsec,gecd->gsd", comb.astype(x.dtype), hout)

    # Switch-style load-balancing auxiliary loss
    me = jnp.mean(probs, axis=(0, 1))                                    # (E,)
    ce = jnp.mean(onehot[..., 0, :] if k == 1 else jnp.max(onehot, 2), axis=(0, 1))
    aux = E * jnp.sum(me * ce)

    out = out.reshape(T, D)[:B * S]
    return out.reshape(B, S, D), aux
