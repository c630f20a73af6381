"""Sharding rules: 2D FSDP("data") × TP("model"), pure DP over "pod".

Policy (baseline — iterated in EXPERIMENTS.md §Perf):
  * every weight shards its TP-natural dim (heads / d_ff / vocab / d_inner)
    over "model" and the complementary d_model dim over "data" (FSDP), so
    optimizer state fits at 141B params on 256 chips;
  * TP dims that are not divisible by the model-axis size (e.g. qwen3's 40
    heads, whisper's 12) fall back to FSDP-only for that weight — the waste
    shows up in the roofline MODEL/HLO ratio and is a §Perf target;
  * activations shard batch over ("pod","data") when divisible (long_500k has
    batch 1 → replicated);
  * KV caches shard batch over data and kv-heads over "model" when divisible.
Params are replicated across "pod" (gradient all-reduce is the only DCN
collective — the cross-pod axis is pure DP).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig, build_plan


def shard_axis(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis] if axis in mesh.shape else 1


def _div(n: int, mesh: Mesh, axis: str) -> bool:
    return n % shard_axis(mesh, axis) == 0


D, M = "data", "model"


def _leaf_spec(cfg: ModelConfig, mesh: Mesh, names, leaf) -> P:
    """names: list of str path keys (e.g. ['segments','0','attn','wq'])."""
    last = names[-1]
    stacked = ("segments" in names or
               ("encoder" in names and "layers" in names))
    lead = (None,) if stacked else ()
    shape = leaf.shape
    H, K, E = cfg.n_heads_padded, cfg.n_kv_heads, cfg.head_dim
    hdiv = _div(H, mesh, M)
    kdiv = _div(K, mesh, M)

    # --- 1D / small leaves: replicate -------------------------------------
    if last in ("ln", "ln1", "ln2", "ln3", "qn", "kn", "adapter_norm",
                "dt_bias", "A_log", "D", "b", "bif", "conv_bB", "conv_bC"):
        return P(*([None] * len(shape)))
    if last == "norm":                       # mamba/mlstm norm over d_inner
        if "mamba" in names:
            return P(*lead, M)
        return P(*([None] * len(shape)))
    if last == "conv_bx":
        return P(*lead, M)

    # --- embeddings / heads -------------------------------------------------
    if last == "tok":
        # replicated over data, D over model: the token gather stays local
        # (a vocab-sharded table turns every lookup into a batch all-gather)
        return P(None, M)
    if last == "adapter":
        return P(D, None)
    if last == "head":
        return P(D, M)

    # --- attention -----------------------------------------------------------
    if last == "wq":
        return P(*lead, D, M if hdiv else None)
    if last in ("wk", "wv"):
        return P(*lead, D, M if kdiv else None)
    if last == "wo":
        # mlstm wo is a gate (D,D) input-sharded; attention wo is (H*E, D)
        if "segments" in names and _is_xlstm_leaf(names):
            return P(*lead, D, None)
        return P(*lead, M if hdiv else None, D)
    if last == "bq":
        return P(*lead, M if hdiv else None)
    if last in ("bk", "bv"):
        return P(*lead, M if kdiv else None)

    # --- FFN -------------------------------------------------------------------
    if last in ("w1", "w3"):
        if len(shape) - len(lead) == 3:      # MoE (E, D, F)
            return P(*lead, None, D, M)
        return P(*lead, D, M)
    if last == "w2":
        if len(shape) - len(lead) == 3:      # MoE (E, F, D)
            return P(*lead, None, M, D)
        return P(*lead, M, D)
    if last == "router":
        return P(*lead, D, None)

    # --- mamba2 -------------------------------------------------------------
    if last in ("z_proj", "x_proj"):
        return P(*lead, D, M)
    if last in ("B_proj", "C_proj"):
        return P(*lead, D, None)
    if last == "dt_proj":
        return P(*lead, D, M if _div(cfg.ssm_heads, mesh, M) else None)
    if last == "conv_x":
        return P(*lead, None, M)
    if last in ("conv_B", "conv_C"):
        return P(*lead, None, None)
    if last == "out_proj":
        return P(*lead, M, D)

    # --- xlstm ---------------------------------------------------------------
    if last in ("wif",):
        return P(*lead, D, None)
    if last in ("wd",):
        return P(*lead, D, None)
    if last == "w":                          # slstm input proj (D, 4D)
        return P(*lead, D, None)
    if last == "r":                          # slstm recurrent (4, H, P, P)
        return P(*([None] * len(shape)))

    return P(*([None] * len(shape)))


def _is_xlstm_leaf(names) -> bool:
    # attention weights live under an "attn"/"xattn" sub-dict; xlstm block
    # weights (wq/wk/wv/wo/wd) are flat in the layer dict
    return "attn" not in names and "xattn" not in names


def _paths(tree):
    return jax.tree_util.tree_flatten_with_path(tree)


def _names_of(path):
    out = []
    for k in path:
        if isinstance(k, jax.tree_util.DictKey):
            out.append(str(k.key))
        elif isinstance(k, jax.tree_util.SequenceKey):
            out.append(str(k.idx))
        else:
            out.append(str(k))
    return out


def param_specs(cfg: ModelConfig, mesh: Mesh, params_tree, mode="train"):
    """Pytree of PartitionSpec matching ``params_tree`` (real or abstract).

    mode="train": 2D FSDP("data")×TP("model") — optimizer state must fit.
    mode="serve": weight-stationary TP — the FSDP dim is dropped (no per-layer
    weight all-gathers, which dominate collectives at decode batch sizes);
    MoE expert weights, too large for TP-only, shard over BOTH axes on their
    d_ff dim instead (no gather; the w2 psum output is tiny at decode)."""
    flat, tdef = _paths(params_tree)
    specs = [_leaf_spec(cfg, mesh, _names_of(p), l) for p, l in flat]
    if mode == "serve":
        specs = [_serve_override(cfg, mesh, _names_of(p), l, s)
                 for (p, l), s in zip(flat, specs)]
    return jax.tree_util.tree_unflatten(tdef, specs)


def _serve_override(cfg: ModelConfig, mesh: Mesh, names, leaf, spec: P) -> P:
    last = names[-1]
    stacked = ("segments" in names or
               ("encoder" in names and "layers" in names))
    lead = (None,) if stacked else ()
    both = (D, M)
    if last in ("w1", "w3") and len(leaf.shape) - len(lead) == 3:   # MoE
        return P(*lead, None, None, both)
    if last == "w2" and len(leaf.shape) - len(lead) == 3:
        return P(*lead, None, both, None)
    # drop the FSDP ("data") dim everywhere else: weight-stationary TP
    out = []
    for ax in spec:
        out.append(None if ax == D else ax)
    return P(*out)


def opt_specs(cfg: ModelConfig, mesh: Mesh, params_tree):
    ps = param_specs(cfg, mesh, params_tree)
    from jax.sharding import PartitionSpec
    return {"master": ps, "m": ps, "v": ps, "step": PartitionSpec()}


# ---------------------------------------------------------------------------
# activations / batch / cache
# ---------------------------------------------------------------------------

def _dp_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.shape else ("data",)


def dp_size(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in _dp_axes(mesh)]))


def batch_dim_spec(mesh: Mesh, batch: int):
    return _dp_axes(mesh) if batch % dp_size(mesh) == 0 else None


def batch_specs(cfg: ModelConfig, mesh: Mesh, batch: int, mode: str):
    """Specs for the input batch dict."""
    bd = batch_dim_spec(mesh, batch)
    spec = {"tokens": P(bd, None)}
    if mode == "train":
        spec["labels"] = P(bd, None)
    if cfg.family == "vlm":
        spec["patches"] = P(bd, None, None)
    if cfg.family == "encdec":
        spec["frames"] = P(bd, None, None)
    return spec


def cache_specs(cfg: ModelConfig, mesh: Mesh, batch: int, plan=None):
    """Per-segment cache specs mirroring models.model.cache_init."""
    plan = plan or build_plan(cfg)
    bd = batch_dim_spec(mesh, batch)
    kdiv = _div(cfg.n_kv_heads, mesh, M)
    kv = P(None, bd, None, M if kdiv else None, None)
    out = []
    for seg in plan.segments:
        if seg.kind in ("dense", "moe") and cfg.attn_kind == "mla":
            lat = P(None, bd, None, None)
            out.append({"c": lat, "kpe": lat})
        elif seg.kind in ("dense", "moe"):
            out.append({"k": kv, "v": kv})
        elif seg.kind == "shared_attn":
            skv = P(bd, None, M if kdiv else None, None)
            out.append({"k": skv, "v": skv})
        elif seg.kind == "mamba":
            hdiv = _div(cfg.ssm_heads, mesh, M)
            out.append({
                "conv_x": P(None, bd, None, M),
                "conv_B": P(None, bd, None, None),
                "conv_C": P(None, bd, None, None),
                "state": P(None, bd, M if hdiv else None, None, None)})
        elif seg.kind == "mlstm":
            out.append({"C": P(None, bd, None, None, None),
                        "n": P(None, bd, None, None),
                        "m": P(None, bd, None)})
        elif seg.kind == "slstm":
            out.append({k: P(None, bd, None) for k in ("h", "c", "n", "m")})
        elif seg.kind == "xdec":
            out.append({"k": kv, "v": kv, "xk": kv, "xv": kv})
        else:
            raise ValueError(seg.kind)
    return out


def named(mesh: Mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# in-model sharding hints (no-ops outside a mesh context)
# ---------------------------------------------------------------------------

def _ambient_mesh():
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def hint(x, *axes):
    """with_sharding_constraint resolved against the ambient mesh (the
    one ``jax.set_mesh`` installs).

    axes entries: "batch" (shard over ("pod","data") when divisible),
    "model" (shard over "model" when divisible), or None.  Outside a mesh
    context (CPU unit tests) this is the identity.
    """
    m = _ambient_mesh()
    if m is None or "model" not in m.shape:
        return x
    bd = _dp_axes(m)
    bsz = int(np.prod([m.shape[a] for a in bd]))
    spec = []
    for dim, a in enumerate(axes):
        if a == "batch" and x.shape[dim] % bsz == 0 and x.shape[dim] > 0:
            spec.append(bd)
        elif a == "model" and x.shape[dim] % m.shape["model"] == 0:
            spec.append("model")
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, P(*spec))


def hint_btd(h):
    """(B, S, D) or (B, 1, D) activations: batch over data axes."""
    return hint(h, "batch", None, None)
