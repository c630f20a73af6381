"""Latent attention and the expert layer at the Moonlight smoke size:
absorbed decode against the expanded form, the expert share against the
uncut layer, the shared experts alone for a token routed elsewhere, and
no token dropped at a serving batch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import mla, moe
from repro.models.layers import ffn_fwd

CFG = configs.get_smoke("moonlight-16b-a3b")


def test_absorbed_decode_equals_expanded_form():
    """Decode over the latent cache, q taken into the latent and the
    output out of it, gives the expanded attention's output at every
    position."""
    key = jax.random.key(0)
    p = mla.mla_init(key, CFG)
    B, S, T = 2, 12, 16
    x = jax.random.normal(jax.random.fold_in(key, 1), (B, S, CFG.d_model))
    full, _, _ = mla.mla_fwd(CFG, p, x, jnp.arange(S))
    cc = jnp.zeros((B, T, CFG.kv_lora_rank))
    cp = jnp.zeros((B, T, CFG.qk_rope_dim))
    _, cc, cp = mla.mla_prefill(CFG, p, x[:, :4], jnp.arange(4), cc, cp)
    for t in range(4, S):
        out, cc, cp = mla.mla_decode(CFG, p, x[:, t:t + 1], jnp.int32(t),
                                     cc, cp)
        np.testing.assert_allclose(np.asarray(out[:, 0]),
                                   np.asarray(full[:, t]), rtol=2e-5,
                                   atol=2e-5)
    # the cache holds the latent and the rope key of every position
    _, c, k_pe = mla.mla_fwd(CFG, p, x, jnp.arange(S))
    np.testing.assert_allclose(np.asarray(cc[:, :S]), np.asarray(c),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(cp[:, :S]), np.asarray(k_pe),
                               rtol=1e-5, atol=1e-6)
    assert not np.any(np.asarray(cc[:, S:]))


def _layer(cfg, seed=0):
    p = moe.moe_init(jax.random.key(seed), cfg)
    p["bias"] = 0.05 * jax.random.normal(jax.random.key(seed + 1),
                                         p["bias"].shape)
    return p


def _share(p, first, n):
    return {**p, **{k: p[k][first:first + n] for k in ("w1", "w3", "w2")}}


@pytest.mark.parametrize("per_share", [1, 2, 4])
def test_expert_shares_sum_to_the_uncut_layer(per_share):
    """Every share of the routed experts, each computing its own part with
    the router at full width, adds up to the uncut layer, the shared
    experts counted once."""
    full_cfg = CFG.replace(experts_held=())
    p = _layer(full_cfg)
    x = jax.random.normal(jax.random.key(2), (3, 10, CFG.d_model))
    whole, _ = moe.moe_fwd(full_cfg, p, x)
    shared = ffn_fwd(CFG, p["shared"], x)
    parts = shared
    for first in range(0, CFG.n_experts, per_share):
        cfg = CFG.replace(experts_held=(first, per_share))
        out, _ = moe.moe_fwd(cfg, _share(p, first, per_share), x)
        parts = parts + (out - shared)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)


def test_token_routed_to_no_held_expert_gets_only_shared_experts():
    p = _layer(CFG)
    first, n = CFG.held_experts
    x = jax.random.normal(jax.random.key(3), (2, 6, CFG.d_model))
    p["bias"] = p["bias"].at[first:first + n].set(-10.0)
    _, idx, _ = moe.route(CFG, p, x.reshape(-1, CFG.d_model))
    assert not np.any((np.asarray(idx) >= first)
                      & (np.asarray(idx) < first + n))
    out, _ = moe.moe_fwd(CFG, p, x)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ffn_fwd(CFG, p["shared"], x)))


def test_v3_router_weights():
    """Picks by score plus bias, weights the scores at the picks,
    normalised over them and scaled."""
    p = _layer(CFG)
    x = jax.random.normal(jax.random.key(4), (5, CFG.d_model))
    scores, idx, w = moe.route(CFG, p, x)
    s = 1 / (1 + np.exp(-(np.asarray(x) @ np.asarray(p["router"]))))
    sel = np.argsort(-(s + np.asarray(p["bias"])), -1)[:, :CFG.top_k]
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1),
                                  np.sort(sel, -1))
    ws = np.take_along_axis(s, np.asarray(idx), -1)
    np.testing.assert_allclose(
        np.asarray(w), ws / ws.sum(-1, keepdims=True) * CFG.routed_scale,
        rtol=1e-5)


def test_no_token_dropped_at_a_serving_batch():
    """Batch 8 of 2048 tokens: every (token, pick) on a held expert is
    computed, as a plain weighted sum over the held experts shows."""
    p = _layer(CFG)
    B, S, D = 8, 2048, CFG.d_model
    x = jax.random.normal(jax.random.key(5), (B, S, D))
    out, _ = moe.moe_fwd(CFG, p, x)
    xf = x.reshape(-1, D)
    _, idx, w = moe.route(CFG, p, xf)
    first, n = CFG.held_experts
    ref = ffn_fwd(CFG, p["shared"], xf)
    for e in range(n):
        g = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
        h = jax.nn.silu(xf @ p["w1"][e]) * (xf @ p["w3"][e])
        ref = ref + g[:, None] * (h @ p["w2"][e])
    np.testing.assert_allclose(np.asarray(out.reshape(-1, D)),
                               np.asarray(ref), rtol=1e-4, atol=1e-5)
    # the held experts take many tokens each: a capacity would bind
    assert np.min(np.bincount(np.asarray(idx).ravel(),
                              minlength=CFG.n_experts)[first:first + n]) \
        > 2 * B * S * CFG.top_k / CFG.n_experts / 4


def test_rows_past_the_held_groups_are_never_read(monkeypatch):
    """The grouped product does not define the rows of pairs routed to no
    held expert (the CPU writes zeros there, which nothing promises); the
    layer's output does not depend on them, NaN included."""
    p = _layer(CFG)
    x = jax.random.normal(jax.random.key(6), (2, 9, CFG.d_model))
    want, _ = moe.moe_fwd(CFG, p, x)
    real = jax.lax.ragged_dot

    def garbage(lhs, rhs, sizes, **kw):
        out = real(lhs, rhs, sizes, **kw)
        past = jnp.arange(out.shape[0]) >= jnp.sum(sizes)
        return jnp.where(past[:, None], jnp.nan, out)

    monkeypatch.setattr(jax.lax, "ragged_dot", garbage)
    got, _ = moe.moe_fwd(CFG, p, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
