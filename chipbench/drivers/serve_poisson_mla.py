"""Moonlight-16B-A3B (latent attention, sigmoid-routed experts with shared
experts) served by one chip of an expert-parallel pod under open-loop
Poisson arrivals.

The cell runs as ``serve_poisson`` does, whose window, latencies,
end-to-end metric, sample of batches, replay and comparison it imports:
set-up draws the weights from the seed (``reference/serve_mla.py``,
mapped into the program's tree by :func:`program_params`), plans the one
pod's caching with a CoCaR window over the ``measured`` catalog, completes
the loads, and serves one batch of every size up to the mix's largest
through ``EdgeCluster.submit``, which compiles every program the window
can run.  The window, ``req_p90_ms`` and the check's numbers are those of
``serve_poisson``; the reference is ``reference/serve_mla.py``, run after
the program's state is freed, since the chip holds one copy of the
weights and not two.

The check compares every step of every compared sequence, as
``serve_poisson`` does, with the reference routed by the program's own
expert picks (teacher-forced routing).  A token whose top 6 of 64 has a
near tie takes another expert when the residual stream is rounded to
bfloat16; the reference, picking for itself in float32, would then compute
another layer, and read as far as a fault does.  So the program's
prefill and decode are compiled again to give every MoE layer's picks,
run on the served tokens, and the reference routes with those picks and
weighs them by its own scores.  A separate number, ``pick_margin``, holds
the picks to the reference's router: at every layer and position, the
reference's k-th largest score-plus-bias less the least of the program's
picks, 0 where the picks are the reference's own.  A near tie reads
little; a pick the router would not make reads the gap it skipped.
"""
from __future__ import annotations

import gc
import time
from functools import partial

import numpy as np

from chipbench.drivers.serve_poisson import (  # noqa: F401  (the cell's)
    State, _requests, _sequences, compare, end_to_end, free, latencies,
    replay, sample, window)
from chipbench.reference import serve_mla as R


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for the configuration file's published
    keys: MLA without a q LoRA, leading dense layers, the V3 router over
    the published experts, of which ``experts_held`` live here."""
    from repro.models.config import ModelConfig

    d = R.dims(cfg)
    return ModelConfig(
        name=cfg["model_name"], family="moe", n_layers=d["L"],
        d_model=d["D"], n_heads=d["H"], n_kv_heads=d["H"], d_ff=d["F"],
        vocab_size=d["V"], attn_kind="mla", kv_lora_rank=d["R"],
        qk_nope_dim=d["Dn"], qk_rope_dim=d["Dr"], v_head_dim=d["Dv"],
        rope_theta=d["theta"], norm_eps=d["eps"], n_experts=d["E"],
        top_k=d["k"], moe_d_ff=d["Fe"],
        n_shared_experts=int(cfg["n_shared_experts"]),
        first_dense_layers=d["dense"], router="sigmoid_bias",
        routed_scale=d["scale"], experts_held=(d["first"], d["n"]),
        exit_layers=tuple(d["exits"]), dtype=d["dtype"],
        param_dtype=d["dtype"], remat=False)


def _layers(lw: dict) -> dict:
    """One segment of the reference's stacked layers in the program's
    tree.  The checkpoint's RoPE pairs are the program's (``models/mla.py``),
    so no column moves."""
    out = {"ln1": lw["ln1"], "ln2": lw["ln2"],
           "attn": {"wq": lw["q_proj"], "wkv_a": lw["kv_a_proj"],
                    "kv_norm": lw["kv_a_norm"], "wkv_b": lw["kv_b_proj"],
                    "wo": lw["o_proj"]}}
    if "gate" in lw:
        out["ffn"] = {"w1": lw["gate"], "w3": lw["up"], "w2": lw["down"]}
    else:
        import jax.numpy as jnp

        out["moe"] = {"router": lw["router"].astype(jnp.float32),
                      "bias": lw["router_bias"], "w1": lw["expert_gate"],
                      "w3": lw["expert_up"], "w2": lw["expert_down"],
                      "shared": {"w1": lw["shared_gate"],
                                 "w3": lw["shared_up"],
                                 "w2": lw["shared_down"]}}
    return out


def program_params(cfg: dict, mcfg, seed: int):
    """The reference's weights for ``seed`` in the program's parameter tree
    (segments split at the exits, the vocabulary padded with zeros), drawn
    part by part so that the device never holds more than one copy."""
    import jax
    import jax.numpy as jnp

    from repro.models.config import build_plan

    d = R.dims(cfg)
    static, key = R._static(d), jax.random.key(int(seed))
    pad = mcfg.padded_vocab - d["V"]
    segments = [_layers(jax.block_until_ready(R.draw_layers(
        static, key, s.depth_end - s.n_layers, s.depth_end)))
        for s in build_plan(mcfg).segments]
    exits = [R.draw_end(static, key, j + 1) for j in range(len(d["exits"]))]
    return {"embed": {"tok": jnp.pad(R.draw_end(static, key, 0),
                                     ((0, pad), (0, 0)))},
            "segments": segments,
            "exits": [{"norm": e["norm"],
                       "head": jnp.pad(e["head"], ((0, 0), (0, pad)))}
                      for e in exits]}


def setup(ctx) -> State:
    import jax

    t = time.perf_counter()
    from repro.core.cocar import cocar_grid
    from repro.mec.catalog import make_catalog
    from repro.mec.scenario import MECConfig, Scenario
    from repro.serving.engine import EdgeCluster
    from repro.serving.loader import WeightStore
    from repro.serving.plan import catalog_precisions, plan_from_offline

    ctx.phase("program_import", time.perf_counter() - t)
    cfg, tr = ctx.config, ctx.traffic
    mcfg = model_config(cfg)
    name = mcfg.name
    P, N = int(tr["prompt_tokens"]), int(tr["new_tokens"])

    # the control plane: one CoCaR window over the measured catalog
    t = time.perf_counter()
    cat = make_catalog("measured", cfgs={name: mcfg}, tokens=P + N)
    sc = Scenario(MECConfig(
        n_bs=int(cfg["pods"]), n_models=1,
        mem_capacity_mb=float(cfg["pod_capacity_mb"]),
        compute_gflops=float(cfg["pod_compute_gflops"]),
        ddl_s=float(cfg["deadline_s"]), seed=ctx.sub_seed("topology"),
        **cfg["plan"]), catalog=cat)
    x, _, _ = cocar_grid([sc.instance(0, sc.empty_cache())],
                         seed=ctx.sub_seed("plan"))[0][0]
    plan = plan_from_offline(x, [name], catalog=cat)
    ctx.phase("plan", time.perf_counter() - t)

    t = time.perf_counter()
    store = WeightStore({name: mcfg}, lazy=True)
    store.set_params(name, jax.block_until_ready(
        program_params(cfg, mcfg, ctx.sub_seed("weights"))))
    ctx.phase("weights", time.perf_counter() - t)

    cluster = EdgeCluster(
        store, n_pods=int(cfg["pods"]),
        capacity_bytes=int(float(cfg["pod_capacity_mb"]) * 1e6),
        bandwidth_Bps=cat.bandwidth_MBps * 1e6,
        compute_flops=float(cfg["pod_compute_gflops"]) * 1e9,
        precisions=catalog_precisions(cat, [name]))
    cluster.apply_caching(plan.residency)
    cluster.tick(plan.max_load_s())
    if any(pod.cache.loading for pod in cluster.pods):
        raise RuntimeError("loads still in flight after the plan's own "
                           "load time")
    st = State(cfg=cfg, mcfg=mcfg, cluster=cluster, new_tokens=N)

    # every batch shape the window can serve, through the timed path: the
    # router's refusals inside a submit can leave a batch of any size below
    # the one chosen
    t = time.perf_counter()
    rng = np.random.default_rng(ctx.sub_seed("warm-up"))
    for b in range(1, max(tr["batch_sizes"]) + 1):
        prompts = rng.integers(1, mcfg.vocab_size, size=(b, P),
                               dtype=np.int32)
        st.sim0 = cluster.now
        reqs = _requests(st, range(b), prompts, np.zeros(b),
                         np.zeros(b, int))
        if cluster.submit(reqs) != b:
            raise RuntimeError(f"warm-up: a batch of {b} was not served")
    ctx.phase("warm_up", time.perf_counter() - t)
    ctx.info["placed_exits"] = {n: r.get(name, -1)
                                for n, r in plan.residency.items()}
    return st


def routed_fns(mcfg, exit_idx: int):
    """The pod's prefill and decode for ``exit_idx``, compiled again to
    also give every MoE layer's picks."""
    import jax

    from repro.models import model as M
    from repro.models.config import build_plan

    plan = build_plan(mcfg)
    kw = dict(exit_idx=exit_idx, plan=plan, with_picks=True)
    return (jax.jit(partial(M.prefill, mcfg, **kw)),
            jax.jit(partial(M.decode, mcfg, **kw)), plan)


def routed_replay(st: State, batch: dict, P: int, fns: tuple):
    """:func:`serve_poisson.replay` through ``fns``, the batch's exit's
    :func:`routed_fns`: (logits (B, N, V) float32, picks (B, layers,
    P + N - 1, top_k) int32 of the MoE layers of the served prefix, at
    every position the reference reads)."""
    import jax.numpy as jnp

    from repro.models import model as M
    from repro.models.config import submodel_plan

    ids, N, ex = batch["ids"], st.new_tokens, batch["exit"]
    B = len(ids)
    params = st.cluster.pods[batch["pod"]].cache.params[st.mcfg.name]
    pf, dc, plan = fns
    cache = M.cache_init(st.mcfg, B, P + N, submodel_plan(plan, ex))
    served = st.outputs[ids]
    lg, kv, pk = pf(params, {"tokens": jnp.asarray(st.arrivals.prompts[ids])},
                    cache)
    out = [lg]
    # each step's picks (layers, B, positions, k), over the MoE segments
    steps = [np.concatenate([np.asarray(a).reshape(len(a), B, P, -1)
                             for a in pk])]
    for k in range(N - 1):
        lg, kv, pk = dc(params, jnp.asarray(served[:, k:k + 1]),
                        jnp.int32(P + k), kv)
        out.append(lg)
        steps.append(np.concatenate([np.asarray(a).reshape(len(a), B, 1, -1)
                                     for a in pk]))
    picks = np.concatenate(steps, 2).transpose(1, 0, 2, 3)
    return np.stack([np.asarray(x, np.float32) for x in out], 1), picks


def check(ctx, st: State) -> list:
    P = int(ctx.traffic["prompt_tokens"])
    V = st.mcfg.vocab_size
    batches = sample(ctx, st)
    differing, program, picks, fns = 0, {}, {}, {}
    for bi in batches:
        ids, ex = st.batches[bi]["ids"], st.batches[bi]["exit"]
        lg = replay(st, st.batches[bi], P)
        differing += int(np.sum(lg.argmax(-1) != st.outputs[ids]))
        # the programs that give the picks serve the same tokens too
        if ex not in fns:
            fns[ex] = routed_fns(st.mcfg, ex)
        lg, picks[bi] = routed_replay(st, st.batches[bi], P, fns[ex])
        differing += int(np.sum(lg.argmax(-1) != st.outputs[ids]))
        program[bi] = lg[:, :, :V]
    st.cluster = None
    gc.collect()
    W = R.weights(st.cfg, ctx.sub_seed("weights"))
    # a run that served nothing has nothing to compare, and is not correct;
    # a NaN anywhere reads NaN, which no limit admits
    worst = dict.fromkeys(("rel", "gap", "margin"),
                          0.0 if batches else float("inf"))
    for bi, row, i, seq in _sequences(st, batches, P):
        ref, _, margin = R.routed(st.cfg, W, seq, st.batches[bi]["exit"],
                                  first=P - 1, picks=picks[bi][row][:, None])
        rel, gap = compare(np.asarray(ref[0]), program[bi][row],
                           st.outputs[i])
        for k, v in (("rel", rel), ("gap", gap), ("margin", margin)):
            worst[k] = float(np.max([np.max(v), worst[k]]))
    ctx.info["check_sequences"] = sum(len(st.batches[b]["ids"])
                                      for b in batches)
    lim = st.cfg["limits"]
    return [{"name": "tokens_replayed_differing", "value": float(differing),
             "limit": lim["tokens_replayed_differing"]},
            {"name": "logits_rel_l2", "value": worst["rel"],
             "limit": lim["logits_rel_l2"]},
            {"name": "logit_gap", "value": worst["gap"],
             "limit": lim["logit_gap"]},
            {"name": "pick_margin", "value": worst["margin"],
             "limit": lim["pick_margin"]},
            {"name": "requests_unanswered",
             "value": float(st.failed - st.refused),
             "limit": lim["requests_unanswered"]}]


#: the control and the planted faults: the reference's arguments for each
CONTROLS = {"fp8": {"fp8": True}, "skip_layer": {"skip_layer": None},
            "drop_expert": {"drop_expert": 0}, "no_bias": {"bias": False}}


def control(ctx, st: State) -> dict:
    """The control's and the planted faults' readings on the sampled
    batches, each read as the check reads the program: run on the served
    tokens with its own picks, against the float32 reference routed with
    those picks.  ``fp8``: the reference computed in float8 (the precision
    below the configuration's bfloat16: both operands of every projection,
    every expert and the head); ``skip_layer``: the middle layer of the
    served prefix left out; ``drop_expert``: held expert 0 left out of
    every MoE layer; ``no_bias``: experts picked without the correction
    bias.  The gap is that of the token each puts first."""
    P = int(ctx.traffic["prompt_tokens"])
    W = R.weights(st.cfg, ctx.sub_seed("weights"))
    worst = {}
    for bi, row, i, seq in _sequences(st, sample(ctx, st), P):
        ex = st.batches[bi]["exit"]
        for name, kw in CONTROLS.items():
            if "skip_layer" in kw:
                kw = {"skip_layer": int(st.cfg["exit_layers"][ex]) // 2}
            lg, pk, _ = R.routed(st.cfg, W, seq, ex, first=P - 1, **kw)
            ref, _, margin = R.routed(st.cfg, W, seq, ex, first=P - 1,
                                      picks=pk)
            lg, ref = np.asarray(lg[0]), np.asarray(ref[0])
            rel, gap = compare(ref, lg, lg.argmax(-1))
            for k, v in (("logits_rel_l2", rel), ("logit_gap", gap),
                         ("pick_margin", margin)):
                key = f"{name}:{k}"
                worst[key] = max(worst.get(key, 0.0), float(np.max(v)))
    return worst
