"""The latent-attention serving cell at a small size on the CPU: a whole run
is correct, and the same run with the serving path broken underneath is
not; prefill and decode through the latent cache against the reference at
every exit; the control and the planted faults against the limits; the
roofline's counts by hand."""
import numpy as np
import pytest

from chipbench import harness as H
from chipbench.reference import serve_mla as R
from chipbench.tests import small

CELL = "ep8-moonlight-poisson"
small.SMALL.setdefault(CELL, {
    "files": ("ep8-moonlight-16b-a3b", "poisson-serve-2k"),
    "config": {"hidden_size": 64, "intermediate_size": 160,
               "num_attention_heads": 4, "num_key_value_heads": 4,
               "kv_lora_rank": 32, "qk_nope_head_dim": 16,
               "qk_rope_head_dim": 8, "v_head_dim": 16,
               "moe_intermediate_size": 32, "n_routed_experts": 8,
               "experts_held": [0, 4],
               "num_experts_per_tok": 3, "num_hidden_layers": 5,
               "vocab_size": 500, "exit_layers": [2, 4, 5],
               "torch_dtype": "float32"},
    "traffic": {"prompt_tokens": 24, "new_tokens": 4, "rate_per_s": 16.0,
                "batch_sizes": [1, 2], "check_batches": 3}})


def test_sound_run_is_correct():
    import time

    import jax

    from chipbench.harness import COMPILE_EVENTS

    seen = []

    def on_event(event, secs, **kw):
        if event in COMPILE_EVENTS:
            seen.append((time.perf_counter(), kw.get("fun_name")))

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        result, ctx = small.run(CELL)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    st = ctx.state
    assert result["correct"], result["checks"]
    assert result["attempted"] == len(st.arrivals) >= 8
    assert result["failed"] == 0
    in_window = [f for t, f in seen if ctx.window_t0 <= t <= ctx.window_t1]
    assert ctx.info["compiles_in_window"] == 0, in_window
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


def _submodel(monkeypatch, change):
    """The pods' submodels with ``change`` applied to their first MoE
    segment's first layer."""
    import jax

    from repro.models import partition

    real = partition.submodel_params

    def changed(cfg, params, j, plan=None):
        out = real(cfg, params, j, plan)
        seg = out["segments"][1]
        if not isinstance(seg["attn"]["wo"], jax.Array):
            return out
        segs = list(out["segments"])
        segs[1] = change(seg)
        return {**out, "segments": segs}

    monkeypatch.setattr(partition, "submodel_params", changed)


def _skipped_layer(monkeypatch):
    """The layer's attention and expert outputs zeroed: it adds nothing to
    the residual stream."""
    def change(seg):
        m = seg["moe"]
        return {**seg, "attn": {**seg["attn"],
                                "wo": seg["attn"]["wo"].at[0].set(0)},
                "moe": {**m, "w2": m["w2"].at[0].set(0),
                        "shared": {**m["shared"],
                                   "w2": m["shared"]["w2"].at[0].set(0)}}}

    _submodel(monkeypatch, change)


def _no_selection_bias(monkeypatch):
    """Experts picked by their scores alone, without the correction bias,
    in every MoE layer."""
    from repro.models import moe

    real = moe.route
    monkeypatch.setattr(moe, "route", lambda cfg, p, xf: real(
        cfg, {**p, "bias": 0 * p["bias"]}, xf))


def _latent_cache_unwritten(monkeypatch):
    """Prefill attends but leaves the latent cache as it was."""
    from repro.models import mla

    real = mla.mla_prefill
    monkeypatch.setattr(mla, "mla_prefill", lambda cfg, p, x, pos, cc, cp:
                        (real(cfg, p, x, pos, cc, cp)[0], cc, cp))


def _held_expert_dropped(monkeypatch):
    """The first held expert's output left out of every MoE layer."""
    import jax.numpy as jnp

    from repro.models import moe

    real = moe.route

    def route(cfg, p, xf):
        scores, idx, w = real(cfg, p, xf)
        return scores, idx, jnp.where(idx == cfg.held_experts[0], 0.0, w)

    monkeypatch.setattr(moe, "route", route)


def _nan_logits(monkeypatch):
    """A decode step whose logits are NaN: the greedy token is then the
    first in the vocabulary, in the window and in the replay alike."""
    import jax.numpy as jnp

    from repro.models import model as M

    real = M.decode

    def decode(cfg, p, t, pos, c, **kw):
        lg, *rest = real(cfg, p, t, pos, c, **kw)
        return (jnp.where(pos == 25, jnp.nan, lg), *rest)

    monkeypatch.setattr(M, "decode", decode)


FAULTS = {"wrong_served_token": _wrong_token,
          "nan_logits": _nan_logits,
          "skipped_layer": _skipped_layer,
          "latent_cache_unwritten": _latent_cache_unwritten,
          "held_expert_dropped": _held_expert_dropped,
          "selection_without_bias": _no_selection_bias}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_serving_is_not_correct(monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    result, _ = small.run(CELL)
    assert not result["correct"], result["checks"]


def test_prefill_and_latent_decode_match_reference_at_every_exit():
    """The program's prefill, then greedy decode through the latent cache,
    from the reference's weights mapped into its tree, give the
    reference's full-forward logits at every exit and every step."""
    import jax
    import jax.numpy as jnp

    from chipbench.drivers import serve_poisson_mla as D
    from repro.models import model as M
    from repro.models.config import build_plan

    cfg = small.resolved(CELL)["config"]
    mcfg = D.model_config(cfg)
    plan = build_plan(mcfg)
    params = D.program_params(cfg, mcfg, 77)
    W = R.weights(cfg, 77)
    B, P, N = 2, 20, 5
    prompts = np.random.default_rng(0).integers(1, mcfg.vocab_size, (B, P))
    with jax.default_matmul_precision("highest"):
        for j in range(mcfg.n_exits):
            cache = M.cache_init(mcfg, B, P + N, plan)
            lg, cache = M.prefill(mcfg, params, {"tokens": jnp.asarray(
                prompts, jnp.int32)}, cache, exit_idx=j, plan=plan)
            got, toks = [lg], []
            for k in range(N - 1):
                toks.append(jnp.argmax(got[-1], -1)[:, None].astype(
                    jnp.int32))
                lg, cache = M.decode(mcfg, params, toks[-1],
                                     jnp.int32(P + k), cache, exit_idx=j,
                                     plan=plan)
                got.append(lg)
            got = np.stack([np.asarray(g) for g in got], 1)
            seq = np.concatenate([prompts] + [np.asarray(t) for t in toks],
                                 1)
            ref = np.asarray(R.routed(cfg, W, seq, j, first=P - 1)[0])
            assert np.linalg.norm(got[..., :mcfg.vocab_size] - ref) \
                <= 1e-5 * np.linalg.norm(ref), j


def test_float8_control_and_planted_faults_fail_the_limits():
    from chipbench.drivers import serve_poisson_mla as D

    result, ctx = small.run(CELL)
    assert result["correct"]
    got = D.control(ctx, ctx.state)
    lim = ctx.config["limits"]
    for name in D.CONTROLS:
        assert any(got[f"{name}:{k}"] > lim[k] for k in (
            "logits_rel_l2", "logit_gap", "pick_margin")), (name, got)


def test_serve_roofline_mla_counts_by_hand():
    roof = H.load_module("metrics", "serve_roofline.mla")
    d = {"D": 8, "H": 2, "R": 4, "Dn": 2, "Dr": 2, "Dv": 3, "F": 16,
         "Fe": 5, "Fs": 10, "E": 8, "n": 2, "k": 2, "dense": 1, "V": 32}
    attn = 8 * 2 * 4 + 8 * 6 + 4 * 2 * 5 + 2 * 3 * 8             # 200
    dense, moe = attn + 3 * 8 * 16, attn + 8 * 8 + 3 * 8 * 10
    assert roof.layer_params(d, "dense") == (dense, 20)
    assert roof.layer_params(d, "moe") == (moe, 28)
    assert roof.expected_experts(d, 1) == pytest.approx(2 * 2 / 8)
    expert = 3 * 8 * 5
    # prefill: a dense and a MoE layer, 3 sequences of 5 tokens, 15 causal
    # pairs each, attention expanded: 2 * heads * (2 + 2 + 3) a pair
    flops = (2 * 3 * 8 * 32
             + 2 * 15 * dense + 3 * 15 * 2 * 2 * 7
             + 2 * 15 * moe + 3 * 15 * 2 * 2 * 7
             + 2 * 15 * 2 * 2 / 8 * expert)
    elems = (8 + 8 * 32 + 15 * 8 + 3 * 32
             + dense + 20 + 3 * 5 * 6 + moe + 28 + 3 * 5 * 6
             + 2 * (1 - 0.75 ** 15) * expert)
    f, b = roof.call_counts(d, 2, 3, 5, 0)
    assert f == pytest.approx(flops) and b == pytest.approx(2 * elems)
    # decode: one token against 7 latents cached, 8 pairs over the latent,
    # 2 * heads * (2 * 4 + 2) a pair; q into the latent and out of it
    absorb = 2 * 2 * 4 * (2 + 3)
    flops = (2 * 3 * 8 * 32
             + 2 * 3 * (dense + absorb) + 3 * 8 * 2 * 2 * 10
             + 2 * 3 * (moe + absorb) + 3 * 8 * 2 * 2 * 10
             + 2 * 3 * 2 * 2 / 8 * expert)
    elems = (8 + 8 * 32 + 3 * 8 + 3 * 32
             + dense + 20 + 3 * 8 * 6 + moe + 28 + 3 * 8 * 6
             + 2 * (1 - 0.75 ** 3) * expert)
    f, b = roof.call_counts(d, 2, 3, 1, 7)
    assert f == pytest.approx(flops) and b == pytest.approx(2 * elems)
    calls = list(roof.batch_calls(d, 2, 3, 5, 4))
    assert len(calls) == 5 and calls[1] == roof.call_counts(d, 2, 3, 1, 5)


def test_per_layer_readers_on_a_run():
    result, ctx = small.run(CELL)
    st = ctx.state
    read = lambda m: H.load_module("metrics", m).read(ctx, st)
    ctx.window_t1 = ctx.window_t0 + ctx.window_seconds
    assert read("prefill_ms") > 0 and read("decode_step_ms") > 0
    assert read("decode_step_ms") < read("submit_ms")
    ctx.info["busy_s"], ctx.info["window_s"] = 0.25, 1.0
    assert read("serve_roofline.mla") > 0 and read("serve_mfu.mla") > 0
    ctx.info["busy_s"] = None
    assert read("serve_roofline.mla") is None


def test_picks_recorded_are_those_the_program_routed_with():
    """The reference routed with the program's recorded picks reads the
    program's own logits, and the picks' margin is 0 where they are the
    reference's own; with the picks of another router (no correction
    bias) the logits still agree, and the margin reads the gap skipped."""
    from chipbench.drivers import serve_poisson_mla as D

    result, ctx = small.run(CELL)
    st = ctx.state
    checks = {c["name"]: c["value"] for c in result["checks"]}
    assert result["correct"] and checks["pick_margin"] < 1e-6, checks
    W = R.weights(st.cfg, ctx.sub_seed("weights"))
    P = int(ctx.traffic["prompt_tokens"])
    seq = np.concatenate([st.arrivals.prompts[0], st.outputs[0, :-1]])[None]
    ex = st.batches[0]["exit"]
    own, pk, m0 = R.routed(st.cfg, W, seq, ex, first=P - 1)
    lg, nb, _ = R.routed(st.cfg, W, seq, ex, first=P - 1, bias=False)
    assert float(m0) == 0.0 and np.any(nb != pk)
    again, used, m1 = R.routed(st.cfg, W, seq, ex, first=P - 1, picks=nb)
    assert np.array_equal(used, nb) and float(m1) > 0
    assert np.allclose(again, lg, rtol=1e-5, atol=1e-5)
    lim = st.cfg["limits"]
    assert float(m1) > lim["pick_margin"]


@pytest.mark.parametrize("step", [0, 3])
def test_a_fault_at_one_step_fails_the_check(step):
    """Every step counts: the prefill's logits (step 0) or the last decode
    step's, far from the reference alone, fail the limits."""
    from chipbench.drivers import serve_poisson_mla as D

    lim = small.resolved(CELL)["config"]["limits"]
    rng = np.random.default_rng(0)
    ref = rng.normal(size=(4, 64))
    got = ref + 1e-4 * rng.normal(size=ref.shape)
    rel, gap = D.compare(ref, got, got.argmax(-1))
    assert rel.max() <= lim["logits_rel_l2"] and gap.max() <= lim["logit_gap"]
    got[step] = rng.normal(size=64)
    rel, gap = D.compare(ref, got, got.argmax(-1))
    assert rel.max() > lim["logits_rel_l2"] or gap.max() > lim["logit_gap"]
