"""Dynamic-DNN partitioning invariants (hypothesis property tests included):
submodel sizes are monotone, Δ-chains telescope, catalogs are consistent."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import configs
from repro.models import partition
from repro.models.config import build_plan, submodel_plan


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_sizes_monotone(arch):
    cfg = configs.get_config(arch)
    sizes = [partition.submodel_bytes(cfg, j) for j in range(cfg.n_exits)]
    assert all(a < b for a, b in zip(sizes, sizes[1:]))


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_delta_chain_telescopes(arch):
    """Σ Δ(i->i+1) + cold(h1) == full size: the paper's incremental
    download chain covers exactly the whole model."""
    cfg = configs.get_config(arch)
    total = partition.delta_bytes(cfg, -1, 0)
    for j in range(1, cfg.n_exits):
        total += partition.delta_bytes(cfg, j - 1, j)
    assert total == partition.submodel_bytes(cfg, cfg.n_exits - 1)


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_flops_monotone(arch):
    cfg = configs.get_config(arch)
    f = [partition.submodel_flops_per_token(cfg, j) for j in range(cfg.n_exits)]
    assert all(a < b for a, b in zip(f, f[1:]))


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_submodel_plan_prefix(arch):
    cfg = configs.get_config(arch)
    plan = build_plan(cfg)
    for j in range(cfg.n_exits):
        sub = submodel_plan(plan, j)
        assert sub.segments == plan.segments[: plan.exit_after[j] + 1]
        # backbone depth at the cut matches the configured exit layer
        assert sub.segments[-1].depth_end == cfg.exit_layers[j]


def test_shrink_is_free():
    cfg = configs.get_config("qwen1.5-0.5b")
    assert partition.delta_bytes(cfg, 2, 1) == 0
    assert partition.delta_bytes(cfg, 2, 2) == 0


@settings(max_examples=20, deadline=None)
@given(n_layers=st.integers(3, 24),
       cuts=st.lists(st.integers(1, 24), min_size=1, max_size=5))
def test_plan_exits_any_cut_set(n_layers, cuts):
    """Property: any valid exit set produces a plan whose exits land at the
    requested depths and whose segments partition the backbone."""
    from repro.models.config import ModelConfig
    cuts = sorted({min(c, n_layers) for c in cuts} | {n_layers})
    cfg = ModelConfig(name="t", family="dense", n_layers=n_layers,
                      d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
                      vocab_size=64, exit_layers=tuple(cuts))
    plan = build_plan(cfg)
    assert sum(s.n_layers for s in plan.segments) == n_layers
    for j, seg_idx in enumerate(plan.exit_after):
        assert plan.segments[seg_idx].depth_end == cuts[j]


def test_zoo_catalog_consistent():
    from repro.mec.catalog import make_catalog
    archs = ["qwen1.5-0.5b", "xlstm-125m"]
    cat = make_catalog("zoo", arch_ids=archs)
    assert cat.source == "zoo" and cat.n_models == len(archs)
    assert cat.names == tuple(archs)
    assert np.all(cat.sizes[:, 0] == 0) and np.all(cat.prec[:, 0] == 0)
    assert np.all(np.diff(cat.sizes[:, 1:], axis=1) > 0)
    assert np.all(np.diff(cat.prec[:, 1:], axis=1) > 0)
    # upgrades cost time, downgrades are cheap
    assert cat.loadD[0, 0, 1] > cat.loadD[0, 2, 1]
    assert cat.load_seconds(0, 0, 1) == cat.loadD[0, 0, 1]
