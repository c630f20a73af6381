"""The online cell at a small size on the CPU: a sound run is correct and
replays its slots exactly; the program with the routine download update
left out is not; the control and the planted fault against the limits;
the slot traffic is keyed on (seed, slot)."""
import numpy as np
import pytest

from chipbench.drivers import online_slots as D
from chipbench.tests import small

CELL = "sec7a-online"
small.SMALL.setdefault(CELL, {"files": ("sec7a", "poisson-zipf-slots"),
                              "config": {}, "traffic": {"check_slots": 60}})


def test_sound_run_is_correct():
    result, ctx = small.run(CELL, seconds=1.0)
    assert result["correct"], result["checks"]
    assert result["attempted"] == len(ctx.state.qoe) >= 60
    assert ctx.info["compiles_in_window"] == 0
    assert ctx.info["check_slots"] == 60
    # the window's slots serve requests once downloads finish
    assert max(ctx.state.qoe) > 0


def test_routine_update_skipped_is_not_correct(monkeypatch):
    """The program with its routine download update left out: nothing
    downloaded ever arrives."""
    from repro.traces import engine

    monkeypatch.setattr(engine, "_routine_update", lambda p, st: st)
    engine._compiled.cache_clear()
    try:
        result, _ = small.run(CELL, seconds=1.0)
    finally:
        engine._compiled.cache_clear()
    assert not result["correct"], result["checks"]


def test_float32_control_and_planted_fault_fail_the_limits():
    result, ctx = small.run(CELL, seconds=1.0)
    assert result["correct"]
    got = D.control(ctx, ctx.state)
    lim = ctx.traffic["limits"]
    for name in ("f32", "no_routine"):
        assert got[f"{name}:states_differing"] > lim["states_differing"] \
            or got[f"{name}:slot_qoe_rel_error"] > lim["slot_qoe_rel_error"]


def test_slot_traffic_keyed_on_seed_and_slot():
    mix = {"rounds": 3, "pop_change_every": 20, "requests_per_slot": 100}
    cfg = {"n_bs": 5, "n_models": 8, "zipf": 0.8}
    a, b = D.SlotTraffic(mix, cfg, 2**40 + 3), D.SlotTraffic(mix, cfg,
                                                             2**40 + 3)
    counts, adjusted = a.slots(0, 60)
    # drawn in any order, a slot is the same
    np.testing.assert_array_equal(b.counts(41), counts[41])
    np.testing.assert_array_equal(b.slots(10, 12)[1], adjusted[10:12])
    assert counts.shape == (60, 5, 8) and adjusted.shape == (60, 3)
    assert counts.sum(axis=(1, 2)).mean() == pytest.approx(100, rel=0.05)
    assert adjusted.min() >= 0 and adjusted.max() < 5
    # popularity is permuted anew every 20 slots, the same within them
    pops = [a.popularity(e) for e in range(3)]
    assert not np.array_equal(pops[0], pops[1])
    assert np.allclose(np.sort(pops[0], -1), np.sort(pops[2], -1))
    c = D.SlotTraffic(mix, cfg, 2**40 + 4)
    assert not np.array_equal(c.slots(0, 60)[0], counts)
