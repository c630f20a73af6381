"""The plain reference for the served Moonlight-16B-A3B (Hugging Face
``model_type`` ``deepseek_v3``; arXiv:2412.19437 Sec. 2.1) written again in
``jax.numpy`` at float32, every matrix product at ``highest`` precision.

* RMSNorm: ``x * rsqrt(mean(x^2) + eps) * g``.
* Multi-head latent attention, without a q LoRA, in its plain form:
  ``q = h W_q`` split per head into ``q_nope`` and ``q_pe``;
  ``[c_kv | k_pe] = h W_kv_a``, ``c_kv`` RMS-normed;
  ``[k_nope | v] = c_kv W_kv_b`` per head, K and V for every head over
  the whole sequence; RoPE on ``q_pe`` and the head-shared ``k_pe`` as the
  published ``apply_rotary_pos_emb`` does it (de-interleave the pairs
  ``(2i, 2i + 1)``, then ``rotate_half``) at ``rope_theta``; causal
  scores ``[q_nope | q_pe] . [k_nope | k_pe]`` scaled by
  ``(qk_nope_head_dim + qk_rope_head_dim)^-0.5``; ``W_o``.
* The first ``first_k_dense_replace`` layers: SwiGLU of
  ``intermediate_size``.
* The other layers: the router ``sigmoid(h W_g)`` in float32 over all
  ``n_routed_experts`` experts; the top ``num_experts_per_tok``
  of the scores plus the correction bias are picked; their scores,
  divided by their sum, times ``routed_scaling_factor``, weigh the picked
  experts' SwiGLUs of ``moe_intermediate_size``; plus the shared experts,
  one SwiGLU of ``n_shared_experts * moe_intermediate_size``.
* Exit ``j``: the RMSNorm and output head of that exit after the first
  ``exit_layers[j]`` layers.

:func:`routed` can instead route every MoE layer with picks it is given
(the check gives the program's), weighing them by its own scores, and
reads how far each given pick lies below the router's own top ``k``.

Departures from the published model, each the configuration's:

* Only the routed experts ``experts_held`` (first, count) exist: this is
  one chip's share of an expert-parallel pod.  The router still scores
  and picks over all of them; a pick of an absent expert adds nothing.
  Every held expert is applied to every token and weighed by its routing
  weight, zero where not picked.
* Three exit heads (the paper's exit ladder), untied; the published model
  has one, after the last layer.
* Random weights from :func:`weights`, the benchmark's own seeded draw, in
  the layout of the published checkpoint (rows in ``x @ W`` order).

No cache, no absorption of ``W_kv_b``, no grouping of tokens by expert,
nothing of the program.  The weights are held in the configuration's
``torch_dtype`` and each layer's are taken to float32 as that layer runs.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.serve import fp8_round, rms_norm, rotate_half

#: the weights' draw: matrices are normal with standard deviation
#: fan-in^-1/2; the embedding, the norms' gains (about 1) and the router's
#: correction bias take these standard deviations
EMBED_STD = 0.02
NORM_GAIN_STD = 0.1
ROUTER_BIAS_STD = 0.05

def dims(cfg: dict) -> dict:
    """The shapes and constants the forward needs, from the
    configuration's published keys and ``experts_held``, the (first,
    count) of the ``n_routed_experts`` held here."""
    held = [int(x) for x in cfg["experts_held"]]
    Fe = int(cfg["moe_intermediate_size"])
    return {"D": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]),
            "R": int(cfg["kv_lora_rank"]), "Dn": int(cfg["qk_nope_head_dim"]),
            "Dr": int(cfg["qk_rope_head_dim"]), "Dv": int(cfg["v_head_dim"]),
            "F": int(cfg["intermediate_size"]), "Fe": Fe,
            "Fs": int(cfg["n_shared_experts"]) * Fe,
            "E": int(cfg["n_routed_experts"]),
            "first": held[0], "n": held[1],
            "k": int(cfg["num_experts_per_tok"]),
            "dense": int(cfg["first_k_dense_replace"]),
            "L": int(cfg["num_hidden_layers"]), "V": int(cfg["vocab_size"]),
            "exits": [int(x) for x in cfg["exit_layers"]],
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"]),
            "scale": float(cfg["routed_scaling_factor"]),
            "norm_topk": bool(cfg["norm_topk_prob"]),
            "dtype": str(cfg["torch_dtype"])}


def _static(d: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in d.items()))


def _shapes(d: dict, kind: str) -> dict:
    """Shapes of one layer's weights: attention, then the dense SwiGLU or
    the router, its bias, the held experts and the shared experts."""
    D, H, R, Dr = d["D"], d["H"], d["R"], d["Dr"]
    out = {"ln1": (D,), "q_proj": (D, H * (d["Dn"] + Dr)),
           "kv_a_proj": (D, R + Dr), "kv_a_norm": (R,),
           "kv_b_proj": (R, H * (d["Dn"] + d["Dv"])),
           "o_proj": (H * d["Dv"], D), "ln2": (D,)}
    if kind == "dense":
        out.update(gate=(D, d["F"]), up=(D, d["F"]), down=(d["F"], D))
    else:
        n, Fe, Fs = d["n"], d["Fe"], d["Fs"]
        out.update(router=(D, d["E"]), router_bias=(d["E"],),
                   expert_gate=(n, D, Fe), expert_up=(n, D, Fe),
                   expert_down=(n, Fe, D), shared_gate=(D, Fs),
                   shared_up=(D, Fs), shared_down=(Fs, D))
    return out


def _draw_layer(d: dict, kind: str, key):
    """One layer's weights from its own key, in ``torch_dtype``; the
    correction bias in float32, as the checkpoint keeps it."""
    dt = jnp.dtype(d["dtype"])
    shapes = _shapes(d, kind)
    keys = dict(zip(shapes, jax.random.split(key, len(shapes))))
    out = {}
    for name, shape in shapes.items():
        z = jax.random.normal(keys[name], shape, jnp.float32)
        if name == "router_bias":
            out[name] = z * ROUTER_BIAS_STD
        elif len(shape) == 1:
            out[name] = (1.0 + NORM_GAIN_STD * z).astype(dt)
        else:
            out[name] = (z * shape[-2] ** -0.5).astype(dt)
    return out


def layer_kind(d: dict, i: int) -> str:
    return "dense" if i < d["dense"] else "moe"


@partial(jax.jit, static_argnums=(0, 2, 3))
def draw_layers(static, key, lo: int, hi: int):
    """Layers ``lo..hi-1`` (all of one kind) of the dims ``static``,
    stacked on a leading axis: layer ``i`` is drawn from ``fold_in(key,
    i)``, so any split of the depth draws the same weights."""
    d = dict(static)
    kind = layer_kind(d, lo)
    assert all(layer_kind(d, i) == kind for i in range(lo, hi))
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(lo, hi))
    return jax.vmap(lambda k: _draw_layer(d, kind, k))(keys)


@partial(jax.jit, static_argnums=(0, 2))
def draw_end(static, key, j: int):
    """The embedding (V, D) for ``j`` = 0, else exit ``j - 1``'s norm (D,)
    and head (D, V); from keys folded past the layers'."""
    d = dict(static)
    dt = jnp.dtype(d["dtype"])
    k = jax.random.fold_in(jax.random.fold_in(key, d["L"]), j)
    D, V = d["D"], d["V"]
    if j == 0:
        return (jax.random.normal(k, (V, D)) * EMBED_STD).astype(dt)
    kn, kh = jax.random.split(k)
    return {"norm": (1.0 + NORM_GAIN_STD * jax.random.normal(kn, (D,))
                     ).astype(dt),
            "head": (jax.random.normal(kh, (D, V)) * D ** -0.5).astype(dt)}


def as_key(key):
    return key if isinstance(key, jax.Array) else jax.random.key(int(key))


def weights(cfg: dict, key):
    """Every weight of the model with all its exits, drawn from ``key`` (a
    ``jax.random`` key, or a seed) on the device, one part per call so
    that no call holds more than its own part: ``{"embed": (V, D),
    "dense": {name: (first_k_dense_replace, ...)}, "moe": {name: (L -
    first_k_dense_replace, ...)}, "exits": [{"norm", "head"}]}``."""
    d = dims(cfg)
    static, key = _static(d), as_key(key)
    return {"embed": draw_end(static, key, 0),
            "dense": draw_layers(static, key, 0, d["dense"]),
            "moe": draw_layers(static, key, d["dense"], d["L"]),
            "exits": [draw_end(static, key, j + 1)
                      for j in range(len(d["exits"]))]}


# ---------------------------------------------------------------------------
# the forward
# ---------------------------------------------------------------------------

def rope_interleaved(x, positions, theta):
    """x: (B, S, heads, Dr) in the checkpoint's layout: the published
    ``apply_rotary_pos_emb`` takes the pairs ``(2i, 2i + 1)`` to
    ``(i, i + Dr/2)``, then turns them with ``rotate_half`` at frequency
    ``theta^(-2i / Dr)``; the result stays in that order."""
    B, S, h, Dr = x.shape
    x = x.reshape(B, S, h, Dr // 2, 2).swapaxes(-1, -2).reshape(B, S, h, Dr)
    inv = 1.0 / (np.float32(theta) ** (np.arange(0, Dr, 2, dtype=np.float32)
                                       / np.float32(Dr)))
    ang = positions[:, None].astype(jnp.float32) * jnp.asarray(inv)[None, :]
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    return x * jnp.cos(ang) + rotate_half(x) * jnp.sin(ang)


def _attention(d, x, lw, positions, mm):
    vec = lambda a: a.astype(jnp.float32)
    B, S, _ = x.shape
    H, R, Dn, Dv = d["H"], d["R"], d["Dn"], d["Dv"]
    h = rms_norm(x, vec(lw["ln1"]), d["eps"])
    q = mm(h, lw["q_proj"]).reshape(B, S, H, -1)
    kv = mm(h, lw["kv_a_proj"])
    c = rms_norm(kv[..., :R], vec(lw["kv_a_norm"]), d["eps"])
    kvb = mm(c, lw["kv_b_proj"]).reshape(B, S, H, Dn + Dv)
    q_pe = rope_interleaved(q[..., Dn:], positions, d["theta"])
    k_pe = rope_interleaved(kv[..., None, R:], positions, d["theta"])
    qf = jnp.concatenate([q[..., :Dn], q_pe], -1)
    kf = jnp.concatenate([kvb[..., :Dn],
                          jnp.broadcast_to(k_pe, (B, S, H, d["Dr"]))], -1)
    s = jnp.einsum("bqhe,bkhe->bhqk", qf, kf) * qf.shape[-1] ** -0.5
    causal = positions[None, :] <= positions[:, None]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhe->bqhe", jax.nn.softmax(s, -1), kvb[..., Dn:])
    return x + mm(a.reshape(B, S, H * Dv), lw["o_proj"])


def _swiglu(h, gate, up, down, mm):
    return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)


def routing(d, h, lw, given=None, bias=True):
    """(picks (B, S, k), weights (B, S, k), margin (B, S)) of the V3 router
    over all experts, in float32.  The picks are the top ``k`` of the
    scores plus the correction bias, or ``given``; a position's margin is
    how far below the ``k``-th of those sums the least of its picks lies:
    0 where the picks are the router's own."""
    s = jax.nn.sigmoid(h @ lw["router"].astype(jnp.float32))
    sel = s + lw["router_bias"] if bias else s
    top, idx = jax.lax.top_k(sel, d["k"])
    if given is None:
        margin = jnp.zeros(s.shape[:-1], jnp.float32)
    else:
        idx = given
        margin = top[..., -1] - jnp.take_along_axis(sel, idx, -1).min(-1)
    w = jnp.take_along_axis(s, idx, -1)
    if d["norm_topk"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx, w * d["scale"], margin


def _moe(d, x, lw, mm, drop, given, bias):
    """(the layer's output, its picks, the picks' largest margin)."""
    h = rms_norm(x, lw["ln2"].astype(jnp.float32), d["eps"])
    idx, w, margin = routing(d, h, lw, given, bias)
    out = _swiglu(h, lw["shared_gate"], lw["shared_up"], lw["shared_down"],
                  mm)
    for e in range(d["n"]):
        if e == drop:
            continue
        g = jnp.sum(jnp.where(idx == d["first"] + e, w, 0.0), -1)
        out = out + g[..., None] * _swiglu(
            h, lw["expert_gate"][e], lw["expert_up"][e],
            lw["expert_down"][e], mm)
    return x + out, idx, margin.max()


def _dense(d, x, lw, mm):
    h = rms_norm(x, lw["ln2"].astype(jnp.float32), d["eps"])
    return x + _swiglu(h, lw["gate"], lw["up"], lw["down"], mm)


@partial(jax.jit, static_argnums=(0, 3, 4, 5, 6, 7, 8))
def _forward(static, W, tokens, exit_idx, first, fp8, skip, drop, bias,
             picks):
    d = dict(static)
    depth = d["exits"][exit_idx]
    rnd = fp8_round if fp8 else (lambda a: a)
    mm = lambda x, w: rnd(x) @ rnd(w.astype(jnp.float32))
    positions = jnp.arange(tokens.shape[1])
    x = W["embed"][tokens].astype(jnp.float32)
    for i in range(min(d["dense"], depth)):
        lw = jax.tree.map(lambda a: a[i], W["dense"])
        y = _dense(d, _attention(d, x, lw, positions, mm), lw, mm)
        x = x if i == skip else y

    def body(i, carry):
        x, used, margin = carry
        lw = jax.tree.map(lambda a: a[i], W["moe"])
        y, idx, m = _moe(d, _attention(d, x, lw, positions, mm), lw, mm,
                         drop, None if picks is None else picks[i], bias)
        return (jnp.where(i + d["dense"] == skip, x, y), used.at[i].set(idx),
                jnp.maximum(margin, m))

    n_moe = max(depth - d["dense"], 0)
    used = jnp.zeros((n_moe,) + tokens.shape + (d["k"],), jnp.int32)
    x, used, margin = jax.lax.fori_loop(0, n_moe, body,
                                        (x, used, jnp.float32(0)))
    ex = W["exits"][exit_idx]
    h = rms_norm(x[:, first:], ex["norm"].astype(jnp.float32), d["eps"])
    return mm(h, ex["head"]), used, margin


def routed(cfg: dict, W, tokens, exit_idx: int, first: int = 0,
           picks=None, fp8: bool = False, skip_layer: int = -1,
           drop_expert: int = -1, bias: bool = True):
    """(float32 logits (B, S - first, V) of exit ``exit_idx`` at positions
    ``first..S-1`` of ``tokens`` (B, S); the picks every MoE layer routed
    with, (layers, B, S, k); the largest margin of those picks, as
    :func:`routing` gives it).  ``picks``, given, routes every MoE layer
    with them in place of its own top ``k``, the weights still the
    reference's scores at them.

    ``fp8`` takes both operands of every projection, expert and of the
    head through float8 (``reference/serve.py``'s ``fp8_round``),
    accumulating in float32, the router staying in float32;
    ``skip_layer`` leaves that layer's output out (its router still
    picks); ``drop_expert`` leaves out the held expert of that index in
    every MoE layer; ``bias`` False picks by the scores alone.  The last
    four exist for the check's control and planted faults."""
    with jax.default_matmul_precision("highest"):
        return _forward(_static(dims(cfg)), W, jnp.asarray(tokens, jnp.int32),
                        int(exit_idx), int(first), bool(fp8),
                        int(skip_layer), int(drop_expert), bool(bias),
                        None if picks is None
                        else jnp.asarray(picks, jnp.int32))

