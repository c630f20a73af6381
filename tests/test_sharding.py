"""Distribution layer: spec coverage, divisibility fallbacks, hint no-ops,
HLO analyzer correctness, and a real (tiny-mesh) sharded train step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro import configs
from repro.distribution import sharding as shd
from repro.models import model as M


def fake_mesh(data=16, model=16):
    """Abstract 256-'device' mesh for spec construction only (no compile)."""
    import types
    m = types.SimpleNamespace()
    m.shape = {"data": data, "model": model}
    return m


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_param_specs_cover_and_rank(arch):
    cfg = configs.get_config(arch)
    shapes = jax.eval_shape(lambda: M.init(cfg, jax.random.key(0)))
    specs = shd.param_specs(cfg, fake_mesh(), shapes)
    flat_s, _ = jax.tree_util.tree_flatten(shapes)
    flat_p, _ = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_s) == len(flat_p)
    for leaf, spec in zip(flat_s, flat_p):
        assert isinstance(spec, P)
        assert len(spec) <= len(leaf.shape), (leaf.shape, spec)
        # every sharded dim must divide (or the rule must have fallen back)
        for dim, ax in enumerate(spec):
            if ax == "model":
                assert leaf.shape[dim] % 16 == 0, (arch, leaf.shape, spec, dim)
            if ax == "data":
                assert leaf.shape[dim] % 16 == 0, (arch, leaf.shape, spec, dim)


def test_qwen3_heads_padded_and_sharded():
    """40 heads % 16 != 0 -> §Perf pads q-heads to 48 so wq shards over
    'model' (48·128 = 6144 divides 16); whisper (12 heads, no clean pad
    with K=12) falls back to no 'model' on wq."""
    cfg = configs.get_config("qwen3-14b")
    assert cfg.n_heads_padded == 48
    shapes = jax.eval_shape(lambda: M.init(cfg, jax.random.key(0)))
    specs = shd.param_specs(cfg, fake_mesh(), shapes)
    wq = specs["segments"][0]["attn"]["wq"]
    assert "model" in tuple(wq)
    cfg_w = configs.get_config("whisper-small")
    shapes_w = jax.eval_shape(lambda: M.init(cfg_w, jax.random.key(0)))
    specs_w = shd.param_specs(cfg_w, fake_mesh(), shapes_w)
    assert "model" not in tuple(specs_w["segments"][0]["attn"]["wq"])


def test_padded_heads_outputs_identical():
    """Zero-weight padded heads must not change the model's outputs."""
    import jax.numpy as jnp
    cfg0 = configs.get_smoke("qwen3-14b")
    cfg1 = cfg0.replace(q_head_pad=8)          # 4 -> 8 heads
    k = jax.random.key(0)
    p1 = M.init(cfg1, k)
    # build the unpadded params by slicing the padded ones
    p0 = jax.tree.map(lambda x: x, p1)
    H, Hp, E = cfg0.n_heads, cfg1.n_heads_padded, cfg0.head_dim
    K = cfg0.n_kv_heads
    G, Gp = H // K, Hp // K
    D = cfg0.d_model
    for seg in p0["segments"]:
        wq = seg["attn"]["wq"]                   # (L, D, Hp*E)
        L = wq.shape[0]
        seg["attn"]["wq"] = wq.reshape(L, D, K, Gp, E)[:, :, :, :G] \
            .reshape(L, D, H * E)
        wo = seg["attn"]["wo"]                   # (L, Hp*E, D)
        seg["attn"]["wo"] = wo.reshape(L, K, Gp, E, D)[:, :, :G] \
            .reshape(L, H * E, D)
    batch = {"tokens": jnp.arange(2 * 32).reshape(2, 32) % cfg0.vocab_size}
    l0, _ = M.apply_train(cfg0, p0, batch)
    l1, _ = M.apply_train(cfg1, p1, batch)
    np.testing.assert_allclose(np.asarray(l0[-1]), np.asarray(l1[-1]),
                               atol=1e-5, rtol=1e-5)


def test_hint_is_noop_without_mesh():
    x = jnp.ones((4, 8, 16))
    y = shd.hint_btd(x)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_batch_dim_spec_divisibility():
    m = fake_mesh(4, 2)
    assert shd.batch_dim_spec(m, 8) == ("data",)
    assert shd.batch_dim_spec(m, 1) is None
    assert shd.batch_dim_spec(m, 6) is None


def test_hlo_analyzer_scan_trip_counts():
    from repro.launch.hlo_analysis import analyse_hlo

    def loop(x, w):
        def body(h, _):
            return h @ w, None
        h, _ = jax.lax.scan(body, x, None, length=8)
        return h

    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    hlo = jax.jit(loop).lower(x, w).compile().as_text()
    r = analyse_hlo(hlo)
    assert r["flops"] == pytest.approx(8 * 2 * 256 ** 3, rel=0.01)


def test_hlo_analyzer_collectives():
    from repro.launch.hlo_analysis import analyse_hlo
    if jax.device_count() < 1:
        pytest.skip("no devices")
    # single-device program: no collectives
    hlo = jax.jit(lambda x: x @ x).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile().as_text()
    r = analyse_hlo(hlo)
    assert r["collective_bytes"] == 0


def test_sharded_train_step_tiny_mesh():
    """End-to-end pjit train step on a real 1x1 mesh (CPU) using the
    production sharding rules."""
    from repro.launch.steps import init_train_state, make_train_step
    cfg = configs.get_smoke("stablelm-12b")
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    shapes = jax.eval_shape(lambda: M.init(cfg, jax.random.key(0)))
    pspec = shd.param_specs(cfg, mesh, shapes)
    psh = shd.named(mesh, pspec)
    with jax.set_mesh(mesh):
        state = init_train_state(cfg, jax.random.key(0))
        state = {"params": jax.device_put(state["params"], psh),
                 "opt": state["opt"]}
        batch = {
            "tokens": jnp.zeros((2, 32), jnp.int32),
            "labels": jnp.zeros((2, 32), jnp.int32),
        }
        step = jax.jit(make_train_step(cfg))
        state2, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
