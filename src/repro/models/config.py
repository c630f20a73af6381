"""Model configuration + execution-plan machinery.

A model is described by a ``ModelConfig`` and compiled (at trace time, in
Python) into a ``Plan``: an ordered tuple of ``Segment``s, each a homogeneous
stack of layers that is stored stacked on a leading ``L`` axis and executed
with ``jax.lax.scan``.  Segments are split at

  * kind changes (e.g. mamba -> shared attention block in zamba2), and
  * dynamic-DNN exit boundaries (the paper's submodel cut points),

so that the paper's submodel ``h_j`` is *literally* a prefix of the segment
list plus exit head ``j`` — and a submodel switch loads exactly the Δ-segment
parameters (paper Sec. III / Fig. 1).
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Tuple


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | hybrid_mamba | xlstm | encdec | vlm
    n_layers: int                    # backbone (decoder) depth
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // n_heads
    # --- attention options -------------------------------------------------
    rope_variant: str = "full"       # full | half (chatglm 2d-rope) | none
    rope_theta: float = 10000.0
    qk_norm: bool = False
    qkv_bias: bool = False
    sliding_window: int = 0          # 0 -> full attention
    attn_kind: str = "gqa"           # gqa | mla (DeepSeek-V3 latent attention)
    # --- MLA (attn_kind "mla"): q without LoRA, k/v from a normed latent ---
    kv_lora_rank: int = 0            # latent width of c_kv (cached)
    qk_nope_dim: int = 0             # per-head q/k width without RoPE
    qk_rope_dim: int = 0             # per-head q width with RoPE; k's is shared
    v_head_dim: int = 0
    # --- MoE ----------------------------------------------------------------
    n_experts: int = 0               # routed experts the router scores
    top_k: int = 0
    moe_d_ff: int = 0                # routed/shared expert width; 0 -> d_ff
    n_shared_experts: int = 0        # always-on experts, one SwiGLU of
                                     # n_shared_experts * moe_d_ff
    first_dense_layers: int = 0      # leading dense layers before the MoE
    router: str = "softmax"          # softmax | sigmoid_bias (V3 noaux_tc)
    routed_scale: float = 1.0        # routed weights' factor after top-k
    experts_held: Tuple[int, ...] = ()  # (first, count) of the routed
                                     # experts this chip holds; () -> all
    # --- SSM (mamba2) -------------------------------------------------------
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_chunk: int = 128
    attn_every: int = 0              # hybrid: insert shared attn block after
                                     # every `attn_every` mamba layers
    # --- xlstm ---------------------------------------------------------------
    slstm_at: Tuple[int, ...] = ()   # backbone indices that are sLSTM blocks
    # --- encoder-decoder ----------------------------------------------------
    encoder_layers: int = 0
    encoder_len: int = 0             # stub frontend sequence length (frames)
    # --- stub multimodal frontend -------------------------------------------
    frontend: str = "none"           # none | patch | audio
    frontend_len: int = 0            # patches prepended to the text sequence
    # --- dynamic DNN (the paper's technique) ---------------------------------
    exit_layers: Tuple[int, ...] = ()   # 1-based backbone depths with exit
                                        # heads; () -> (L/3, 2L/3, L)
    exit_loss_weights: Tuple[float, ...] = ()
    # --- TP head padding (§Perf): pad q heads with zero-weight heads so the
    # head dim divides the model axis; wo's padded input rows are zero, so
    # outputs are bit-identical to the unpadded model ----------------------
    q_head_pad: int = 0              # 0 -> no padding
    seq_parallel: bool = False       # §Perf: shard the residual stream's S
                                     # over "model" (Megatron-SP: RS+AG
                                     # replaces the post-attn/FFN all-reduce)
    # --- training memory (§Perf): gradient-accumulation microbatches so the
    # remat-saved per-layer residuals fit 16 GB/chip HBM at train_4k --------
    train_microbatches: int = 1
    # --- numerics ------------------------------------------------------------
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"
    norm_eps: float = 1e-5
    vocab_pad_multiple: int = 256
    remat: bool = True

    # ------------------------------------------------------------------ ---
    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if not self.exit_layers:
            L = self.n_layers
            cuts = sorted({max(1, math.ceil(L / 3)), max(1, math.ceil(2 * L / 3)), L})
            object.__setattr__(self, "exit_layers", tuple(cuts))
        if self.exit_layers[-1] != self.n_layers:
            raise ValueError("last exit must sit at the full depth")
        if not self.exit_loss_weights:
            n = len(self.exit_layers)
            w = tuple(0.3 for _ in range(n - 1)) + (1.0,)
            object.__setattr__(self, "exit_loss_weights", w)

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def held_experts(self) -> Tuple[int, int]:
        """(first, count) of the routed experts whose weights live here."""
        return tuple(self.experts_held) or (0, self.n_experts)

    @property
    def qk_head_dim(self) -> int:   # MLA: q/k width per head
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def padded_vocab(self) -> int:
        return _round_up(self.vocab_size, self.vocab_pad_multiple)

    @property
    def n_heads_padded(self) -> int:
        return max(self.q_head_pad, self.n_heads)

    @property
    def n_exits(self) -> int:
        return len(self.exit_layers)

    @property
    def d_inner(self) -> int:      # mamba2 inner width
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    def replace(self, **kw) -> "ModelConfig":
        # reset derived fields when their drivers change, so __post_init__
        # recomputes them instead of keeping stale values
        if "n_layers" in kw and "exit_layers" not in kw:
            kw["exit_layers"] = ()
        if ("exit_layers" in kw or "n_layers" in kw) \
                and "exit_loss_weights" not in kw:
            kw["exit_loss_weights"] = ()
        if ("d_model" in kw or "n_heads" in kw) and "head_dim" not in kw:
            kw["head_dim"] = 0
        return dataclasses.replace(self, **kw)


# --------------------------------------------------------------------------
# Plan
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class Segment:
    kind: str          # dense | moe | mamba | mlstm | slstm | shared_attn | xdec
    n_layers: int
    index: int         # position in plan
    depth_end: int     # cumulative backbone depth after this segment
                       # (shared_attn does not advance backbone depth)


@dataclass(frozen=True)
class Plan:
    segments: Tuple[Segment, ...]
    exit_after: Tuple[int, ...]    # segment index whose output feeds exit j
    has_encoder: bool = False


def _backbone_kinds(cfg: ModelConfig):
    """Per-backbone-layer kind list, plus inserted (non-backbone) blocks."""
    kinds = []
    if cfg.family in ("dense", "vlm"):
        kinds = [("dense", True)] * cfg.n_layers
    elif cfg.family == "moe":
        k = cfg.first_dense_layers
        kinds = [("dense", True)] * k + [("moe", True)] * (cfg.n_layers - k)
    elif cfg.family == "hybrid_mamba":
        for i in range(cfg.n_layers):
            kinds.append(("mamba", True))
            if cfg.attn_every and (i + 1) % cfg.attn_every == 0 and i + 1 < cfg.n_layers:
                kinds.append(("shared_attn", False))
    elif cfg.family == "xlstm":
        for i in range(cfg.n_layers):
            kinds.append(("slstm" if i in cfg.slstm_at else "mlstm", True))
    elif cfg.family == "encdec":
        kinds = [("xdec", True)] * cfg.n_layers
    else:
        raise ValueError(f"unknown family {cfg.family}")
    return kinds


def build_plan(cfg: ModelConfig) -> Plan:
    kinds = _backbone_kinds(cfg)
    exit_set = set(cfg.exit_layers)
    segments = []
    exit_after = {}
    cur_kind, cur_count = None, 0
    depth = 0

    def flush():
        nonlocal cur_kind, cur_count
        if cur_kind is not None and cur_count > 0:
            segments.append(Segment(cur_kind, cur_count, len(segments), depth))
            cur_kind, cur_count = None, 0

    for kind, is_backbone in kinds:
        if kind != cur_kind:
            flush()
            cur_kind = kind
        cur_count += 1
        if is_backbone:
            depth += 1
            if depth in exit_set:
                flush()
                exit_after[depth] = len(segments) - 1
        if kind == "shared_attn":
            flush()

    flush()
    exits = tuple(exit_after[d] for d in cfg.exit_layers)
    return Plan(tuple(segments), exits, has_encoder=cfg.family == "encdec")


def submodel_plan(plan: Plan, j: int) -> Plan:
    """The paper's submodel h_{j+1}: plan truncated at exit j (0-based)."""
    last_seg = plan.exit_after[j]
    return Plan(plan.segments[: last_seg + 1], plan.exit_after[: j + 1],
                plan.has_encoder)
