"""The serving cell at a small size on the CPU: a whole run is correct,
and the same run with the serving path broken underneath is not; the
reference against the program's float32 forward; the control and the
planted fault against the limits; the arrivals; the roofline's counts."""
import numpy as np
import pytest

from chipbench import generator as G
from chipbench import harness as H
from chipbench.reference import serve as R
from chipbench.tests import small

CELL = "edge4-qwen-poisson"


def test_sound_run_is_correct():
    result, ctx = small.run(CELL)
    st = ctx.state
    assert result["correct"], result["checks"]
    assert result["attempted"] == len(st.arrivals) >= 8
    assert result["failed"] == 0
    assert ctx.info["compiles_in_window"] == 0
    assert result["metrics"]["req_p90_ms"]["value"] > 0
    assert sum(len(b["ids"]) for b in st.batches) == len(st.arrivals)
    assert ctx.info["check_sequences"] >= 1


def _serve_batch(monkeypatch, alter):
    from repro.serving.engine import EdgePod

    real = EdgePod.serve_batch

    def broken(self, model, reqs, now):
        outs, secs = real(self, model, reqs, now)
        return alter(outs), secs

    monkeypatch.setattr(EdgePod, "serve_batch", broken)


def _wrong_token(monkeypatch):
    def alter(outs):
        outs[0][1] = (outs[0][1] + 1) % 500
        return outs

    _serve_batch(monkeypatch, alter)


def _half_of_batch_left_out(monkeypatch):
    def alter(outs):
        half = len(outs) // 2
        return outs[:len(outs) - half] + outs[:half]

    _serve_batch(monkeypatch, alter)


def _skipped_layer(monkeypatch):
    """The pods' submodels with their first layer's output projections
    zeroed: the layer adds nothing to the residual stream."""
    import jax

    from repro.models import partition

    real = partition.submodel_params

    def skipped(cfg, params, j, plan=None):
        out = real(cfg, params, j, plan)
        seg = out["segments"][0]
        if not isinstance(seg["attn"]["wo"], jax.Array):
            return out
        seg = {**seg, "attn": {**seg["attn"],
                               "wo": seg["attn"]["wo"].at[0].set(0)},
               "ffn": {**seg["ffn"], "w2": seg["ffn"]["w2"].at[0].set(0)}}
        return {**out, "segments": [seg] + out["segments"][1:]}

    monkeypatch.setattr(partition, "submodel_params", skipped)


def _decode(monkeypatch, wrap):
    from repro.models import model as M

    real = M.decode
    monkeypatch.setattr(M, "decode", lambda cfg, p, t, pos, c, **kw:
                        wrap(real, cfg, p, t, pos, c, **kw))


def _decode_position_off_by_one(monkeypatch):
    _decode(monkeypatch, lambda real, cfg, p, t, pos, c, **kw:
            real(cfg, p, t, pos + 1, c, **kw))


def _kv_state_unchanged(monkeypatch):
    _decode(monkeypatch, lambda real, cfg, p, t, pos, c, **kw:
            (real(cfg, p, t, pos, c, **kw)[0], c))


FAULTS = {"wrong_served_token": (_wrong_token, {}),
          "skipped_layer": (_skipped_layer, {}),
          "decode_position_off_by_one": (_decode_position_off_by_one, {}),
          "kv_state_unchanged": (_kv_state_unchanged, {}),
          "half_of_batch_left_out": (_half_of_batch_left_out,
                                     {"rate_per_s": 200.0})}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_serving_is_not_correct(monkeypatch, fault):
    plant, overrides = FAULTS[fault]
    plant(monkeypatch)
    result, ctx = small.run(CELL, **overrides)
    if fault == "half_of_batch_left_out":
        assert max(len(b["ids"]) for b in ctx.state.batches) > 1
    assert not result["correct"], result["checks"]


def test_reference_matches_program_float32_forward_at_every_exit():
    """The program's float32 forward, from the reference's weights mapped
    into its tree, gives the reference's logits at every exit and every
    position."""
    import jax
    import jax.numpy as jnp

    from chipbench.drivers import serve_poisson as D
    from repro.models import model as M

    cfg = small.resolved(CELL)["config"]
    mcfg = D.model_config(cfg)
    params = D.program_params(cfg, mcfg, 77)
    W = R.weights(cfg, 77)
    tokens = np.random.default_rng(0).integers(1, mcfg.vocab_size, (2, 24))
    with jax.default_matmul_precision("highest"):
        outs, _ = M.apply_train(mcfg, params, {"tokens": jnp.asarray(
            tokens, jnp.int32)})
    for j in range(mcfg.n_exits):
        ref = np.asarray(R.logits(cfg, W, tokens, j))
        got = np.asarray(outs[j])[..., :mcfg.vocab_size]
        assert np.linalg.norm(got - ref) <= 1e-5 * np.linalg.norm(ref), j
        # the padded vocabulary's logits are 0 and never served
        assert not np.any(np.asarray(outs[j])[..., mcfg.vocab_size:])


def test_float8_control_and_skipped_layer_fail_the_limits():
    from chipbench.drivers import serve_poisson as D

    result, ctx = small.run(CELL)
    assert result["correct"]
    got = D.control(ctx, ctx.state)
    lim = ctx.config["limits"]
    assert got["fp8:logits_rel_l2"] > lim["logits_rel_l2"]
    assert got["skip_layer:logits_rel_l2"] > lim["logits_rel_l2"]
    assert got["skip_layer:logit_gap"] > lim["logit_gap"]


MIX = {"rate_per_s": 8.0, "prompt_tokens": 128, "schedule_seed": 7}


def test_poisson_arrivals_reproducible_and_differ_between_seeds():
    a = G.PoissonArrivals(MIX, 151936, 4, 30.0, 2**40 + 1)
    b = G.PoissonArrivals(MIX, 151936, 4, 30.0, 2**40 + 1)
    c = G.PoissonArrivals(MIX, 151936, 4, 30.0, 2**40 + 2)
    for x, y in ((a.at, b.at), (a.prompts, b.prompts), (a.homes, b.homes)):
        assert np.array_equal(x, y)
    assert not np.array_equal(a.prompts, c.prompts)
    assert not np.array_equal(a.homes, c.homes)
    # every seed: the mix's one schedule of 240 arrivals in the window
    assert np.array_equal(a.at, c.at)
    assert len(a) == 240 and a.prompts.shape == (240, 128)
    assert 0 < a.at[0] and a.at[-1] < 30.0
    assert a.at[-1] == pytest.approx(30.0 * 239.5 / 240)
    d = G.PoissonArrivals(dict(MIX, schedule_seed=8), 151936, 4, 30.0, 5)
    gaps = lambda x: np.sort(np.diff(np.concatenate([[0.0], x.at])))
    assert not np.array_equal(a.at, d.at)
    assert np.allclose(gaps(a), gaps(d))
    assert a.prompts.min() >= 1 and a.prompts.max() < 151936
    assert set(np.unique(a.homes)) == {0, 1, 2, 3}
    # exponential gaps: the median gap is ln 2 of the mean
    assert np.median(gaps(a)) == pytest.approx(np.log(2) / 8.0, rel=0.02)


def test_req_p90_counts_a_failed_request_as_a_miss():
    """A request refused or never answered ranks behind every request
    served, at the horizon: refusing the slowest never lowers the p90."""
    from types import SimpleNamespace

    from chipbench.drivers import serve_poisson as D

    ctx = SimpleNamespace(window_length=30.0,
                          traffic={"drain_limit_s": 60.0})
    at = np.arange(20, dtype=float)
    end = at + np.linspace(0.1, 2.0, 20)
    st = SimpleNamespace(arrivals=SimpleNamespace(at=at), end=end)
    served = D.end_to_end(ctx, st)["req_p90_ms"]
    assert served == pytest.approx(1e3 * H.quantile(end - at, 0.9))
    st.end = end.copy()
    st.end[-3:] = np.nan                  # the three slowest refused
    lat = D.latencies(ctx, st)
    assert np.array_equal(lat[-3:], 90.0 - at[-3:])
    assert D.end_to_end(ctx, st)["req_p90_ms"] > served


def test_serve_roofline_counts_by_hand():
    roof = H.load_module("metrics", "serve_roofline")
    d = {"D": 8, "H": 2, "K": 2, "E": 4, "F": 16, "V": 32}
    matrix = 8 * 8 + 2 * 8 * 8 + 8 * 8 + 3 * 8 * 16      # 576
    vector = 8 + 2 * 8 + 2 * 8                           # 40
    assert roof.layer_params(d) == (matrix, vector)
    # prefill: 2 layers, 3 sequences of 5 tokens, 15 causal pairs each
    flops = 2 * 3 * (2 * 5 * matrix + 4 * 2 * 4 * 15) + 2 * 3 * 8 * 32
    elems = (2 * (matrix + vector) + 8 + 8 * 32 + 3 * 5 * 8
             + 2 * 2 * 3 * 5 * 2 * 4 + 3 * 32)
    assert roof.call_counts(d, 2, 3, 5, 0) == (flops, 2 * elems)
    # decode: one token against 7 cached, 8 pairs
    flops = 2 * 3 * (2 * matrix + 4 * 2 * 4 * 8) + 2 * 3 * 8 * 32
    elems = (2 * (matrix + vector) + 8 + 8 * 32 + 3 * 8
             + 2 * 2 * 3 * 8 * 2 * 4 + 3 * 32)
    assert roof.call_counts(d, 2, 3, 1, 7) == (flops, 2 * elems)
    calls = list(roof.batch_calls(d, 2, 3, 5, 4))
    assert len(calls) == 5 and calls[1] == roof.call_counts(d, 2, 3, 1, 5)


def test_per_layer_readers_on_a_run():
    result, ctx = small.run(CELL)
    st = ctx.state
    read = lambda m: H.load_module("metrics", m).read(ctx, st)
    assert read("submit_ms") == pytest.approx(
        1e3 * np.mean(ctx.spans["submit"]))
    assert 0 <= read("queue_ms") < 1e3 * ctx.window_seconds
    ctx.info["busy_s"], ctx.info["window_s"] = 0.25, 1.0
    assert read("device_idle.serve") == pytest.approx(75.0)
    assert read("serve_roofline") > 0 and read("serve_mfu") > 0
    ctx.info["busy_s"] = None
    assert read("serve_roofline") is None
