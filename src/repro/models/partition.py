"""Dynamic-DNN partitioning — the paper's core object model.

A model type ``m`` (a ModelConfig) is disassembled into submodels
``h_1 ≺ … ≺ h_H`` (paper Sec. III): submodel j = embed + segments up to
``plan.exit_after[j]`` + exit head j (+ shared block, + encoder).  Because
segment params are stacked, the Δ between consecutive submodels is a
contiguous parameter slice — so r_h (memory), Δr_h (switch download bytes)
and c_h (FLOPs/token) are all *derived from the real architecture*, giving
the MEC catalog its sizes and the loader its transfer volumes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as M
from repro.models.config import ModelConfig, Plan, build_plan


def _nbytes(tree) -> int:
    return sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))


def _nparams(tree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


@functools.lru_cache(maxsize=64)
def _shapes(cfg: ModelConfig):
    return jax.eval_shape(lambda: M.init(cfg, jax.random.key(0)))


def submodel_params(cfg: ModelConfig, params, j: int, plan: Plan = None):
    """Truncate a real (or abstract) param tree to submodel j (0-based)."""
    plan = plan or build_plan(cfg)
    last = plan.exit_after[j]
    out = {"embed": params["embed"],
           "segments": list(params["segments"][: last + 1]),
           "exits": list(params["exits"][: j + 1])}
    if "shared" in params:
        out["shared"] = params["shared"]
    if "encoder" in params:
        out["encoder"] = params["encoder"]
    return out


def submodel_bytes(cfg: ModelConfig, j: int) -> int:
    return _nbytes(submodel_params(cfg, _shapes(cfg), j))


def submodel_param_count(cfg: ModelConfig, j: int = None) -> int:
    if j is None:
        j = cfg.n_exits - 1
    return _nparams(submodel_params(cfg, _shapes(cfg), j))


def delta_bytes(cfg: ModelConfig, i: int, j: int) -> int:
    """Download bytes to switch submodel i -> j (paper D^swit); i=-1 means
    cold load from nothing (paper D^new)."""
    if j <= i:
        return 0                       # shrink = eviction, ~free (paper Sec VI)
    lo = 0 if i < 0 else submodel_bytes(cfg, i)
    return submodel_bytes(cfg, j) - lo


def delta_segments(cfg: ModelConfig, params, i: int, j: int, plan: Plan = None):
    """The actual Δ param subtree transferred for an i->j upgrade."""
    plan = plan or build_plan(cfg)
    lo_seg = -1 if i < 0 else plan.exit_after[i]
    hi_seg = plan.exit_after[j]
    return {"segments": list(params["segments"][lo_seg + 1: hi_seg + 1]),
            "exits": list(params["exits"][i + 1: j + 1])}


# ---------------------------------------------------------------------------
# analytic FLOPs (forward, per token) — feeds c_h and roofline MODEL_FLOPS
# ---------------------------------------------------------------------------

def _mla_flops(cfg: ModelConfig, ctx: int) -> float:
    """MLA per token in the absorbed form that decode runs: the q, latent
    and output projections, q_nope taken into the latent and the output
    out of it, and scores and the weighted sum over ``ctx`` latents."""
    D, H, R = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    Dn, Dr, Dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    proj = 2 * D * H * (Dn + Dr) + 2 * D * (R + Dr) + 2 * H * Dv * D
    absorb = 2 * H * Dn * R + 2 * H * R * Dv
    return proj + absorb + 2 * H * (2 * R + Dr) * ctx


def _layer_flops(cfg: ModelConfig, kind: str, ctx: int) -> float:
    D, F = cfg.d_model, cfg.d_ff
    H, K, E = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    attn_ctx = min(ctx, cfg.sliding_window) if cfg.sliding_window else ctx
    attn = 2 * D * (H * E + 2 * K * E) + 2 * H * E * D \
        + 2 * 2 * H * E * attn_ctx                     # qkv+out proj + scores/av
    if cfg.attn_kind == "mla" and kind in ("dense", "moe"):
        attn = _mla_flops(cfg, ctx)
    ffn = 3 * 2 * D * F
    ffn_ng = 2 * 2 * D * F
    if kind == "dense":
        return attn + ffn
    if kind == "moe":
        # the router at full width; of the top_k picks, the share that lands
        # on the experts held here (all of them unless experts_held is set)
        router = 2 * D * cfg.n_experts
        expert = 3 * 2 * D * cfg.expert_d_ff
        picks = cfg.top_k * cfg.held_experts[1] / cfg.n_experts
        return attn + router + (picks + cfg.n_shared_experts) * expert
    if kind == "mamba":
        I, N, Hs = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
        proj = 2 * D * (2 * I + 2 * N + Hs) + 2 * I * D
        conv = 2 * cfg.ssm_conv * (I + 2 * N)
        ssd = 2 * 2 * I * N + 2 * 2 * cfg.ssm_chunk * (N + cfg.ssm_head_dim) * Hs
        return proj + conv + ssd
    if kind == "mlstm":
        P = D // H
        return 5 * 2 * D * D + 4 * 2 * D * P
    if kind == "slstm":
        P = D // H
        return 2 * D * 4 * D + 4 * 2 * D * P + 2 * D * D
    if kind in ("xdec",):
        xattn = 2 * D * H * E + 2 * H * E * D + 2 * 2 * H * E * cfg.encoder_len
        return attn + xattn + ffn_ng
    if kind in ("encoder", "shared_attn"):
        return attn + (ffn if kind == "shared_attn" else ffn_ng)
    raise ValueError(kind)


def submodel_flops_per_token(cfg: ModelConfig, j: int, ctx: int = 2048,
                             plan: Plan = None) -> float:
    """Forward FLOPs per decoder token for submodel j (c_h in the paper)."""
    plan = plan or build_plan(cfg)
    total = 0.0
    for seg in plan.segments[: plan.exit_after[j] + 1]:
        total += seg.n_layers * _layer_flops(cfg, seg.kind, ctx)
    total += 2 * cfg.d_model * cfg.padded_vocab          # exit head
    if plan.has_encoder:
        total += cfg.encoder_layers * _layer_flops(cfg, "encoder", cfg.encoder_len) \
            * cfg.encoder_len / max(ctx, 1)
    return total


def model_flops(cfg: ModelConfig, batch: int, seq: int, mode: str) -> float:
    """Roofline MODEL_FLOPS: 6·N·D for train, 2·N_active·D for inference."""
    n_active = active_param_count(cfg)
    tokens = batch * seq if mode == "train" else batch  # decode: 1 tok/step
    if mode == "prefill":
        tokens = batch * seq
    mult = 6 if mode == "train" else 2
    return mult * n_active * tokens


def active_param_count(cfg: ModelConfig) -> int:
    """Params touched per token (MoE: of the experts held, the share of the
    top_k picks that lands on them)."""
    n = submodel_param_count(cfg)
    if cfg.n_experts:
        expert = 3 * cfg.d_model * cfg.expert_d_ff     # w1,w2,w3 per expert
        held = cfg.held_experts[1]
        idle = held - cfg.top_k * held / cfg.n_experts
        n -= int(idle * expert * (cfg.n_layers - cfg.first_dense_layers))
    return n


def catalog_entry(cfg: ModelConfig, ctx: int = 2048):
    """(r_h bytes, Δr_h bytes, c_h flops/token) per submodel — the paper's
    Table II analogue, derived from the real architecture."""
    out = []
    for j in range(cfg.n_exits):
        out.append({
            "r_h": submodel_bytes(cfg, j),
            "delta_r": delta_bytes(cfg, j - 1, j),
            "c_h": submodel_flops_per_token(cfg, j, ctx),
        })
    return out
