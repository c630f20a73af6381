"""Latent attention and the expert layer at the Moonlight smoke size:
absorbed decode against the expanded form, the expert share against the
uncut layer, the shared experts alone for a token routed elsewhere, no
token dropped at a serving batch, and the held pairs run chunk by chunk
against all rows at once."""
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


def _all_rows(cfg, p, xf, idx, w):
    """The held experts' part with the grouped products over all T x k
    sorted pairs at once, the rows past the held groups selected away."""
    T, D = xf.shape
    k = idx.shape[-1]
    first, n = cfg.held_experts
    local = idx.reshape(-1) - first
    held = (local >= 0) & (local < n)
    eid = jnp.where(held, local, n)
    order = jnp.argsort(eid, stable=True)
    sizes = jnp.zeros((n + 1,), jnp.int32).at[eid].add(1)[:n]
    tok = order // k
    xs = xf[tok]
    dt = xf.dtype
    h = jax.lax.ragged_dot(xs, p["w1"].astype(dt), sizes)
    g = jax.lax.ragged_dot(xs, p["w3"].astype(dt), sizes)
    y = jax.lax.ragged_dot(jax.nn.silu(h) * g, p["w2"].astype(dt), sizes)
    out = jnp.where(held[order][:, None],
                    y.astype(jnp.float32) * w.reshape(-1)[order][:, None],
                    0.0)
    return jnp.zeros((T, D), jnp.float32).at[tok].add(out).astype(dt)


def _two_held_a_token(cfg, T, seed):
    """Picks of exactly two held experts and one other a token: 2 T held
    pairs, which at 256 tokens fill one chunk exactly."""
    first, n = cfg.held_experts
    rng = np.random.default_rng(seed)
    rest = [e for e in range(cfg.n_experts) if not first <= e < first + n]
    return np.stack([np.concatenate([
        first + rng.choice(n, 2, replace=False), rng.choice(rest, 1)])
        for _ in range(T)]).astype(np.int32)


@pytest.mark.parametrize("case, chunks", [
    ("below", 1), ("exact", 1), ("above", 3), ("all_held", 1)])
def test_chunked_held_experts_equal_all_rows(case, chunks, monkeypatch):
    """The held pairs run chunk by chunk give the all-rows formula's
    output with no pair dropped: below one chunk, exactly one, every pick
    on a held expert (three chunks, groups cut at their edges), and every
    expert held, where the one chunk is every pair, bit for bit.  Rows past
    the groups of any chunk are never read, NaN included."""
    T, cfg = 256, CFG
    if case == "above":
        T, cfg = 512, CFG.replace(top_k=2, experts_held=(0, 2))
    if case == "all_held":
        cfg = configs.get_smoke("mixtral-8x7b")
    p = moe.moe_init(jax.random.key(0), cfg) if case == "all_held" \
        else _layer(cfg)
    first, n = cfg.held_experts
    if case == "above":                 # the bias sends every pick here
        p["bias"] = p["bias"].at[first:first + n].set(10.0)
    xf = jax.random.normal(jax.random.key(7), (T, cfg.d_model))
    _, idx, w = moe.route(cfg, p, xf)
    if case == "exact":
        idx = jnp.asarray(_two_held_a_token(cfg, T, 8))
    C = moe.held_rows(cfg, T)
    held = int(np.sum((np.asarray(idx) >= first)
                      & (np.asarray(idx) < first + n)))
    assert {"below": held < C, "exact": held == C < T * cfg.top_k,
            "above": held == T * cfg.top_k > 2 * C,
            "all_held": C == T * cfg.top_k}[case]
    assert int(moe.held_chunks(cfg, idx)) == chunks
    fwd = jax.jit(lambda p, xf, idx, w: moe.held_experts_fwd(cfg, p, xf,
                                                            idx, w))
    want = jax.jit(lambda p, xf, idx, w: _all_rows(cfg, p, xf, idx, w))(
        p, xf, idx, w)
    got = fwd(p, xf, idx, w)
    if case == "all_held":
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
    real = jax.lax.ragged_dot

    def garbage(lhs, rhs, sizes, **kw):
        out = real(lhs, rhs, sizes, **kw)
        past = jnp.arange(out.shape[0]) >= jnp.sum(sizes)
        return jnp.where(past[:, None], jnp.nan, out)

    monkeypatch.setattr(jax.lax, "ragged_dot", garbage)
    fwd = jax.jit(lambda p, xf, idx, w: moe.held_experts_fwd(cfg, p, xf,
                                                            idx, w))
    np.testing.assert_array_equal(np.asarray(fwd(p, xf, idx, w)),
                                  np.asarray(got))


@pytest.mark.parametrize("name, tokens, rows", [
    ("moonlight-16b-a3b", 2048, 1920), ("moonlight-16b-a3b", 4096, 3840),
    ("moonlight-16b-a3b", 8, 48), ("moonlight-16b-a3b", 1, 6),
    ("mixtral-8x7b", 2048, 4096), ("mixtral-8x22b", 8, 16)])
def test_held_rows_at_published_widths(name, tokens, rows):
    """A chunk is 1.25 x the held pairs of uniform routing in whole
    128-row tiles: 1920 of Moonlight's 12288 pairs at 2048 tokens; every
    pair where every expert is held (Mixtral) and at decode.  The chunks
    follow the held pairs: one where none is held, one for every chunk's
    rows where every pick is held."""
    cfg = configs.get_config(name)
    assert moe.held_rows(cfg, tokens) == rows
    first, n = cfg.held_experts
    k = cfg.top_k
    everywhere = np.tile(first + np.arange(k) % n, (2, tokens, 1))
    np.testing.assert_array_equal(
        moe.held_chunks(cfg, everywhere), [-(-tokens * k // rows)] * 2)
    if n < cfg.n_experts:
        nowhere = np.full((tokens, k), first + n)
        assert int(moe.held_chunks(cfg, nowhere)) == 1
