"""Compiles for a TPU v5e chip that is described, not attached.

The chip's compiler refuses what interpret mode and the CPU accept: a
scatter or an unsupported reshape inside a Mosaic kernel, float64 in a
kernel, a program that outgrows the chip's 16 GB.  These tests compile
the kernels of the main path at their real sizes for one v5e chip — the
fused PDHG sweep at the paper's Sec. VII-A window, and qwen1.5-0.5b
prefill and decode at its published widths — so such a refusal fails
here, before any chip time is spent.  Nothing runs: a compile that passes
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


def test_pdhg_pallas_sweep_compiles_for_v5e(one_chip):
    """The f32 sweep of the fused PDHG engine as a Mosaic kernel, vmapped
    over windows and traced under x64 as the offline pipeline calls it,
    at N=5, U=600, M=8."""
    from repro.core import lp as LP
    from repro.kernels import pdhg_fused as PF
    from repro.mec.scenario import MECConfig, Scenario, stack_instances

    sc = Scenario(MECConfig())
    insts = [sc.instance(w, sc.empty_cache()) for w in range(2)]
    data = _on(one_chip, jax.tree.map(
        lambda a: np.asarray(a, np.float32), stack_instances(insts).data))
    assert isinstance(data, LP.PDHGData)

    def sweep(d):
        _, state = PF._init_state(d, jnp.float32)
        return PF._pallas_phase(d, state, 3 * PF.PALLAS_BLOCK + 3,
                                jnp.float32, interpret=False)

    with jax.enable_x64(True):
        compiled = jax.jit(jax.vmap(sweep)).lower(data).compile()
    assert "tpu_custom_call" in compiled.as_text()
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
