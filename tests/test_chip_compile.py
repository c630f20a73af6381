"""Compiles for a TPU v5e chip that is described, not attached.

The chip's compiler refuses what interpret mode and the CPU accept: a
scatter or an unsupported reshape inside a Mosaic kernel, float64 in a
kernel, a program that outgrows the chip's 16 GB.  These tests compile
the programs of the main path at their real sizes for one v5e chip — the
offline pipeline at the paper's Sec. VII-A window, and qwen1.5-0.5b and
Moonlight-16B-A3B prefill and decode at their published widths — and
Mixtral's train and serve steps sharded over four chips, so such a
refusal fails here, before any chip time is spent.  Nothing runs: a compile that passes
says nothing about results or times.

The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the worker that runs this
file loads the TPU compiler.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

#: one v5e chip's HBM
HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    """Shapes of ``tree`` (arrays or ShapeDtypeStructs) placed on the
    described chip."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=sharding), tree)


def _fits_one_chip(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert used < HBM_BYTES, used
    return used


def test_cocar_offline_pipeline_compiles_for_v5e(one_chip):
    """``cocar_offline_pipeline``, the program the ``sec7a-offline`` cell
    runs, as the grid executor compiles it for one window with no mesh,
    at Sec. VII-A shapes (N=5, U=600, M=8, 4000 PDHG iterations, best of
    8 rounding trials), traced under x64."""
    from repro.core.rounding import draw_rounding_uniforms
    from repro.mec.scenario import MECConfig, Scenario, stack_instances
    from repro.scale import GridSpec
    from repro.scale.executor import _offline_inner

    sc = Scenario(MECConfig())
    inst = sc.instance(0, sc.empty_cache())
    spec = GridSpec(kind="offline", insts=[inst], seed=0, n_seeds=1,
                    best_of=8, pdhg_iters=4000)
    with jax.enable_x64(True):
        args = (stack_instances([inst]).data,
                *draw_rounding_uniforms(0, 8, inst.N, inst.M, inst.U,
                                        inst.H, batch=1))
        args = _on(one_chip, jax.tree.map(np.asarray, args))
        fn = jax.jit(_offline_inner(spec)(), donate_argnums=(0, 1, 2))
        lowered = fn.lower(*args)
        compiled = lowered.compile()
    assert "cocar_offline_pipeline" in lowered.as_text()
    _fits_one_chip(compiled)


def _qwen(one_chip, batch, max_len):
    from repro import configs
    from repro.models import model as M
    from repro.models.config import build_plan

    cfg = configs.get_config("qwen1.5-0.5b")
    plan = build_plan(cfg)
    params = _on(one_chip, jax.eval_shape(
        lambda: M.init(cfg, jax.random.key(0))))
    cache = _on(one_chip, jax.eval_shape(
        lambda: M.cache_init(cfg, batch, max_len, plan)))
    return cfg, plan, params, cache


def test_qwen_prefill_compiles_for_v5e(one_chip):
    """qwen1.5-0.5b prefill at its full exit: batch 8, 128-token prompts."""
    from repro.models import model as M

    cfg, plan, params, cache = _qwen(one_chip, 8, 160)
    tokens = _on(one_chip, jax.ShapeDtypeStruct((8, 128), jnp.int32))
    compiled = jax.jit(
        lambda p, t, c: M.prefill(cfg, p, {"tokens": t}, c, plan=plan)
    ).lower(params, tokens, cache).compile()
    _fits_one_chip(compiled)


def test_qwen_decode_step_compiles_for_v5e(one_chip):
    """One qwen1.5-0.5b decode step at its full exit, batch 8."""
    from repro.models import model as M

    cfg, plan, params, cache = _qwen(one_chip, 8, 160)
    tok = _on(one_chip, jax.ShapeDtypeStruct((8, 1), jnp.int32))
    pos = _on(one_chip, jax.ShapeDtypeStruct((), jnp.int32))
    compiled = jax.jit(
        lambda p, t, i, c: M.decode(cfg, p, t, i, c, plan=plan)
    ).lower(params, tok, pos, cache).compile()
    _fits_one_chip(compiled)


def _moonlight(one_chip, batch, max_len):
    """Moonlight-16B-A3B's submodel at its full exit, as one chip of the
    8-chip pod holds it (8 of 64 routed experts), and its latent cache."""
    from repro import configs
    from repro.models import model as M
    from repro.models import partition
    from repro.models.config import build_plan, submodel_plan

    cfg = configs.get_config("moonlight-16b-a3b")
    plan = build_plan(cfg)
    j = cfg.n_exits - 1
    params = _on(one_chip, partition.submodel_params(cfg, jax.eval_shape(
        lambda: M.init(cfg, jax.random.key(0))), j))
    cache = _on(one_chip, jax.eval_shape(
        lambda: M.cache_init(cfg, batch, max_len, submodel_plan(plan, j))))
    return cfg, plan, params, cache


def test_moonlight_prefill_compiles_for_v5e(one_chip):
    """Moonlight prefill with MLA and the grouped held experts: batch 8,
    2048-token prompts, the latent cache of 2080 positions."""
    from repro.models import model as M

    cfg, plan, params, cache = _moonlight(one_chip, 8, 2080)
    tokens = _on(one_chip, jax.ShapeDtypeStruct((8, 2048), jnp.int32))
    compiled = jax.jit(
        lambda p, t, c: M.prefill(cfg, p, {"tokens": t}, c, plan=plan)
    ).lower(params, tokens, cache).compile()
    _fits_one_chip(compiled)


def test_moonlight_decode_step_compiles_for_v5e(one_chip):
    """One Moonlight decode step over the latent cache, batch 8."""
    from repro.models import model as M

    cfg, plan, params, cache = _moonlight(one_chip, 8, 2080)
    tok = _on(one_chip, jax.ShapeDtypeStruct((8, 1), jnp.int32))
    pos = _on(one_chip, jax.ShapeDtypeStruct((), jnp.int32))
    compiled = jax.jit(
        lambda p, t, i, c: M.decode(cfg, p, t, i, c, plan=plan)
    ).lower(params, tok, pos, cache).compile()
    assert "ragged" in compiled.as_text()
    _fits_one_chip(compiled)


def _mixtral_on_mesh(topo):
    """Mixtral's smoke configuration and the four described chips as a
    2 x 2 (data, model) mesh."""
    from jax.sharding import Mesh

    from repro import configs

    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(2, 2),
                ("data", "model"))
    return configs.get_smoke("mixtral-8x7b"), mesh


def test_mixtral_sharded_train_step_compiles_for_four_v5e(topo):
    """Mixtral's train step, the one MoE layer's grouped product inside,
    under the production sharding rules on four chips (the experts' hidden
    width over "model", the batch over "data")."""
    from repro.distribution import sharding as shd
    from repro.launch.steps import init_train_state, make_train_step

    cfg, mesh = _mixtral_on_mesh(topo)
    state = jax.eval_shape(lambda: init_train_state(cfg, jax.random.key(0)))
    shard = {"params": shd.named(mesh, shd.param_specs(cfg, mesh,
                                                       state["params"])),
             "opt": shd.named(mesh, shd.opt_specs(cfg, mesh,
                                                  state["params"]))}
    bsh = shd.named(mesh, shd.batch_specs(cfg, mesh, 8, "train"))
    batch = {k: jax.ShapeDtypeStruct((8, 64), jnp.int32, sharding=bsh[k])
             for k in ("tokens", "labels")}
    with jax.set_mesh(mesh):
        compiled = jax.jit(make_train_step(cfg)).lower(
            jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=s), state, shard),
            batch).compile()
    assert "ragged" in compiled.as_text()


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_mixtral_sharded_serve_compiles_for_four_v5e(topo, step):
    """Mixtral's prefill and decode step with the serving sharding rules
    and a sharded cache on four chips."""
    from repro.distribution import sharding as shd
    from repro.models import model as M
    from repro.models.config import build_plan

    cfg, mesh = _mixtral_on_mesh(topo)
    plan = build_plan(cfg)
    B, S, L = 8, 32, 48
    shapes = jax.eval_shape(lambda: M.init(cfg, jax.random.key(0)))
    psh = shd.named(mesh, shd.param_specs(cfg, mesh, shapes, mode="serve"))
    params = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=s), shapes, psh)
    cache = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        jax.eval_shape(lambda: M.cache_init(cfg, B, L, plan)),
        shd.named(mesh, shd.cache_specs(cfg, mesh, B, plan)))
    tsh = shd.named(mesh, shd.batch_specs(cfg, mesh, B, "serve"))["tokens"]
    with jax.set_mesh(mesh):
        if step == "prefill":
            tok = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=tsh)
            lowered = jax.jit(lambda p, t, c: M.prefill(
                cfg, p, {"tokens": t}, c, plan=plan)).lower(params, tok,
                                                            cache)
        else:
            tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=tsh)
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            lowered = jax.jit(lambda p, t, i, c: M.decode(
                cfg, p, t, i, c, plan=plan)).lower(params, tok, pos, cache)
        compiled = lowered.compile()
    assert "ragged" in compiled.as_text()
