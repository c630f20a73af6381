"""A whole run of the offline cell at a small size on the CPU, sound
and with the decision path broken underneath: the check has to call
the sound run correct and every broken one not."""
import numpy as np
import pytest

from chipbench.tests import small

CELL = "sec7a-offline"


def _patch_cocar(monkeypatch, alter):
    from repro.core import cocar

    real = cocar.cocar_grid

    def broken(insts, **kw):
        out = real(insts, **kw)
        x, A, info = out[0][0]
        x, A = alter(insts[0], np.array(x), np.array(A))
        return [[(x, A, info)]]

    monkeypatch.setattr(cocar, "cocar_grid", broken)


def test_sound_run_is_correct():
    result, ctx = small.run(CELL)
    assert result["correct"], result["checks"]
    assert result["attempted"] == len(ctx.state.decided) >= 3
    assert ctx.info["compiles_in_window"] == 0


def _answer_altered(inst, x, A):
    return np.roll(x, 1, axis=0), A          # each BS gets another's cache


def _state_unchanged(inst, x, A):
    return np.array(inst.x_prev), A


def _half_left_out(inst, x, A):
    A[:, A.shape[1] // 2:, :] = 0.0
    return x, A


@pytest.mark.parametrize("alter", [_answer_altered, _state_unchanged,
                                   _half_left_out],
                         ids=["answer_altered", "state_unchanged",
                              "half_of_batch_left_out"])
def test_broken_decisions_are_not_correct(monkeypatch, alter):
    _patch_cocar(monkeypatch, alter)
    result, _ = small.run(CELL)
    assert not result["correct"], result["checks"]


def test_float32_control_fails_the_limits():
    """The control: the reference with its LP and rounding draws in
    float32, in the program's place, reads above the LP objective's
    limit and the decisions' limit on every window sampled."""
    from chipbench.drivers import offline_windows as D

    result, ctx = small.run(CELL)
    limit = ctx.config["limits"]["lp_obj_rel_gap"]
    for i in D.sample(ctx, len(ctx.state.decided)):
        d = dict(ctx.state.decided[i])
        w64 = D.compare(ctx.config, ctx.state.topo_seed, d)
        assert w64["lp_obj_rel_gap"] <= limit
        from chipbench.reference import offline as R

        w = R.Window(ctx.config, ctx.state.topo_seed, *d["reqs"], d["x_prev"])
        x32, A32, lp32 = R.decide(w, d["seed"], ctx.config["pdhg_iters"],
                                  ctx.config["best_of"], np.float32)
        d.update(x=x32, A=A32, lp_obj=lp32)
        got = D.compare(ctx.config, ctx.state.topo_seed, d)
        assert got["lp_obj_rel_gap"] > limit
        assert got["decisions_differing"] > \
            ctx.config["limits"]["decisions_differing"]


def test_decision_matched_against_every_tied_trial():
    """A decision equal to another of the reference's trials counts as
    differing unless that trial's routed precision ties the best: which
    tied trial wins rests on the last bit of a sum."""
    from chipbench.drivers import offline_windows as D
    from chipbench.reference import offline as R

    _, ctx = small.run(CELL)
    cfg = dict(ctx.config)
    d = dict(ctx.state.decided[0])
    w = R.Window(cfg, ctx.state.topo_seed, *d["reqs"], d["x_prev"])
    out, _ = R.trials(w, d["seed"], cfg["pdhg_iters"], cfg["best_of"])
    assert D.compare(cfg, ctx.state.topo_seed, d)["decisions_differing"] == 0
    best = max(v for v, _, _ in out)
    other = next(t for t, (v, x, A) in enumerate(out)
                 if v < best and (np.any(x != d["x"]) or np.any(A != d["A"])))
    d.update(x=out[other][1], A=out[other][2])
    assert D.compare(cfg, ctx.state.topo_seed, d)["decisions_differing"] > 0
    cfg["objective_tie_rel"] = 1.0              # every trial tied
    assert D.compare(cfg, ctx.state.topo_seed, d)["decisions_differing"] == 0
