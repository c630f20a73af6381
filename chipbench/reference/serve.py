"""The plain reference for the served decoder: Qwen1.5 (the ``qwen2``
architecture of Hugging Face ``transformers``) written again in
``jax.numpy`` at float32, every matrix product at ``highest`` precision.

* RMSNorm: ``x * rsqrt(mean(x^2) + eps) * g``.
* RoPE at the configuration's ``rope_theta``, in the published
  ``rotate_half`` form: dimension ``i`` of a head turns with dimension
  ``i + head_dim / 2`` at frequency ``theta^(-2i / head_dim)``.
* Multi-head attention with the q, k and v biases, a causal mask, scores
  scaled by ``head_dim^-0.5``.
* SwiGLU: ``down(silu(gate(x)) * up(x))``.
* Exit ``j``: the RMSNorm and output head of that exit after the first
  ``exit_layers[j]`` layers.

No cache, no batching tricks, nothing of the program.  The weights come
from :func:`weights`, the benchmark's own seeded draw, in the layout of
the published checkpoint (one stacked array per kind of matrix, rows in
``x @ W`` order); the driver maps them into the program's tree.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

#: the weights' draw: matrices are normal with standard deviation
#: fan-in^-1/2; the embedding, the biases and the norms' gains (about 1)
#: take these standard deviations
EMBED_STD = 0.02
BIAS_STD = 0.5
NORM_GAIN_STD = 0.1


def dims(cfg: dict) -> dict:
    """The shapes the forward needs, from the configuration's published
    keys (``head_dim`` is not published: hidden over heads)."""
    D, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {"D": D, "H": H, "K": int(cfg["num_key_value_heads"]),
            "E": D // H, "F": int(cfg["intermediate_size"]),
            "V": int(cfg["vocab_size"]), "L": int(cfg["num_hidden_layers"]),
            "exits": [int(x) for x in cfg["exit_layers"]],
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"])}


def weights(cfg: dict, key):
    """Every weight of the model with all its exits, drawn from ``key``
    (a ``jax.random`` key, or a seed) on the device in one call, in the
    configuration's ``torch_dtype``: ``{"embed": (V, D), "layers": {name:
    (L, ...)}, "exits": [{"norm": (D,), "head": (D, V)}]}``."""
    if not isinstance(key, jax.Array):
        key = jax.random.key(int(key))
    return _draw(_static(cfg), key)


def _static(cfg: dict):
    d = dims(cfg)
    return (d["D"], d["H"], d["K"], d["E"], d["F"], d["V"], d["L"],
            len(d["exits"]), str(cfg["torch_dtype"]))


@partial(jax.jit, static_argnums=(0,))
def _draw(static, key):
    D, H, K, E, F, V, L, X, dtype = static
    dt = jnp.dtype(dtype)
    names = ("embed", "ln1", "wq", "wk", "wv", "bq", "bk", "bv", "wo",
             "ln2", "gate", "up", "down", "exit_norm", "exit_head")
    keys = dict(zip(names, jax.random.split(key, len(names))))
    normal = lambda k, shape, std: (jax.random.normal(k, shape, jnp.float32)
                                    * std).astype(dt)
    gain = lambda k, shape: (1.0 + NORM_GAIN_STD * jax.random.normal(
        k, shape, jnp.float32)).astype(dt)
    layers = {
        "ln1": gain(keys["ln1"], (L, D)),
        "wq": normal(keys["wq"], (L, D, H * E), D ** -0.5),
        "wk": normal(keys["wk"], (L, D, K * E), D ** -0.5),
        "wv": normal(keys["wv"], (L, D, K * E), D ** -0.5),
        "bq": normal(keys["bq"], (L, H * E), BIAS_STD),
        "bk": normal(keys["bk"], (L, K * E), BIAS_STD),
        "bv": normal(keys["bv"], (L, K * E), BIAS_STD),
        "wo": normal(keys["wo"], (L, H * E, D), (H * E) ** -0.5),
        "ln2": gain(keys["ln2"], (L, D)),
        "gate": normal(keys["gate"], (L, D, F), D ** -0.5),
        "up": normal(keys["up"], (L, D, F), D ** -0.5),
        "down": normal(keys["down"], (L, F, D), F ** -0.5),
    }
    norms = gain(keys["exit_norm"], (X, D))
    heads = normal(keys["exit_head"], (X, D, V), D ** -0.5)
    return {"embed": normal(keys["embed"], (V, D), EMBED_STD),
            "layers": layers,
            "exits": [{"norm": norms[j], "head": heads[j]} for j in range(X)]}


# ---------------------------------------------------------------------------
# the forward
# ---------------------------------------------------------------------------

def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], -1)


def rope(x, positions, theta):
    """x: (B, S, heads, E) at ``positions`` (S,); the inverse
    frequencies in float32, as the published code computes them."""
    E = x.shape[-1]
    inv = 1.0 / (np.float32(theta) ** (np.arange(0, E, 2, dtype=np.float32)
                                       / np.float32(E)))
    ang = positions[:, None].astype(jnp.float32) * jnp.asarray(inv)[None, :]
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    return x * jnp.cos(ang) + rotate_half(x) * jnp.sin(ang)


def fp8_round(w):
    """``w`` through float8_e4m3fn with one scale per tensor (its largest
    magnitude at the format's largest finite value, 448), back in
    float32: an operand of a float8 matrix product."""
    s = jnp.max(jnp.abs(w)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _layer(d, x, lw, positions, mm):
    vec = lambda a: a.astype(jnp.float32)
    B, S, _ = x.shape
    H, K, E = d["H"], d["K"], d["E"]
    h = rms_norm(x, vec(lw["ln1"]), d["eps"])
    q = (mm(h, lw["wq"]) + vec(lw["bq"])).reshape(B, S, H, E)
    k = (mm(h, lw["wk"]) + vec(lw["bk"])).reshape(B, S, K, E)
    v = (mm(h, lw["wv"]) + vec(lw["bv"])).reshape(B, S, K, E)
    q, k = rope(q, positions, d["theta"]), rope(k, positions, d["theta"])
    k = jnp.repeat(k, H // K, axis=2)
    v = jnp.repeat(v, H // K, axis=2)
    s = jnp.einsum("bqhe,bkhe->bhqk", q, k) * E ** -0.5
    causal = positions[None, :] <= positions[:, None]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhe->bqhe", jax.nn.softmax(s, -1), v)
    x = x + mm(a.reshape(B, S, H * E), lw["wo"])
    h = rms_norm(x, vec(lw["ln2"]), d["eps"])
    return x + mm(jax.nn.silu(mm(h, lw["gate"])) * mm(h, lw["up"]),
                  lw["down"])


@partial(jax.jit, static_argnums=(0, 3, 4, 5, 6))
def _forward(static, W, tokens, exit_idx, first, fp8, skip):
    d = dict(zip(("D", "H", "K", "E", "F", "V", "L", "eps", "theta"),
                 static[:9]))
    depth = static[9][exit_idx]
    rnd = fp8_round if fp8 else (lambda a: a)
    mm = lambda x, w: rnd(x) @ rnd(w.astype(jnp.float32))
    positions = jnp.arange(tokens.shape[1])

    def body(x, xs):
        i, lw = xs
        y = _layer(d, x, lw, positions, mm)
        return jnp.where(i == skip, x, y), None

    layers = {n: a[:depth] for n, a in W["layers"].items()}
    x = W["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(body, x, (jnp.arange(depth), layers))
    ex = W["exits"][exit_idx]
    h = rms_norm(x[:, first:], ex["norm"].astype(jnp.float32), d["eps"])
    return mm(h, ex["head"])


def logits(cfg: dict, W, tokens, exit_idx: int, first: int = 0,
           fp8: bool = False, skip_layer: int = -1):
    """float32 logits (B, S - first, V) of exit ``exit_idx`` at positions
    ``first..S-1`` of ``tokens`` (B, S), layer by layer.  ``fp8`` takes
    both operands of every projection and of the head through float8
    (:func:`fp8_round`), accumulating in float32, as a float8 matrix unit
    does; ``skip_layer`` leaves that layer out.  Both exist for the check's
    control and its planted fault."""
    d = dims(cfg)
    static = (d["D"], d["H"], d["K"], d["E"], d["F"], d["V"], d["L"],
              d["eps"], d["theta"], tuple(d["exits"]))
    with jax.default_matmul_precision("highest"):
        return _forward(static, W, jnp.asarray(tokens, jnp.int32),
                        int(exit_idx), int(first), bool(fp8),
                        int(skip_layer))
