"""An early-exit LM served by the edge pods of one chip under open-loop
Poisson arrivals, as a MEC site serves short assistant prompts.

Set-up draws the weights from the seed (``reference/serve.py``, mapped
into the program's tree by :func:`program_params`), decides the pods'
caching with one CoCaR window over the ``measured`` catalog
(``cocar_grid``, ``plan_from_offline``, ``EdgeCluster.apply_caching``),
completes the loads, and serves one batch of every size up to the mix's
largest through ``EdgeCluster.submit``: that compiles every program the
window can run, since the router's refusals inside a submit can leave a
batch of any size below the one chosen.

The window: requests arrive at their scheduled times
(``generator.PoissonArrivals``).  Whenever requests wait, one
``EdgeCluster.submit`` (span ``submit``) serves the largest of
``batch_sizes`` not above the number waiting, oldest first, with the
cluster's clock at the window's wall clock and each request's deadline
the configuration's ``deadline_s`` after its arrival.  Arrivals stop at
the window's end; the requests still waiting are then served, for at
most ``drain_limit_s`` more.  ``req_p90_ms`` is the 90th percentile, over
every request of the window, of its time from its scheduled arrival to
its last token on the host (:func:`latencies`).  A request the router
refuses counts in ``failed`` and misses: its time runs to the horizon,
the window's end plus the drain limit.  So does one still unanswered
when the drain ends, which the check counts too.

The check, after the window: on up to ``check_batches`` served batches
drawn from the seed (the first and a largest always among them), the
serving pod's own compiled prefill and decode are run again with the
served tokens fed back (``tokens_replayed_differing``: greedy tokens of
that rerun that differ from those served).  Then, with the program's
state freed, the reference runs each served sequence whole:
``logits_rel_l2`` is the worst relative L2 distance, per sequence and
step, of the rerun's logits from the reference's, and ``logit_gap`` the
worst amount by which a served token's reference logit lies below the
reference's largest.
"""
from __future__ import annotations

import gc
import time
from collections import deque
from dataclasses import dataclass, field

import numpy as np

from chipbench import generator as G
from chipbench import harness as H
from chipbench.reference import serve as R


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for the configuration file's
    published keys (a qwen2 decoder: QKV bias, SwiGLU, untied exits)."""
    from repro.models.config import ModelConfig

    d = R.dims(cfg)
    return ModelConfig(
        name=cfg["model_name"], family="dense", n_layers=d["L"],
        d_model=d["D"], n_heads=d["H"], n_kv_heads=d["K"], d_ff=d["F"],
        vocab_size=d["V"], head_dim=d["E"], qkv_bias=True,
        rope_theta=d["theta"], norm_eps=d["eps"],
        exit_layers=tuple(d["exits"]), dtype=cfg["torch_dtype"],
        param_dtype=cfg["torch_dtype"], remat=False)


def rope_permutation(E: int) -> np.ndarray:
    """Columns of a head in the program's RoPE order, which turns
    dimensions ``2i`` and ``2i + 1`` together, taken from the published
    order, which turns ``i`` and ``i + E/2``: a checkpoint's q and k
    projections are permuted so, and the attention scores are unchanged."""
    half = E // 2
    return np.stack([np.arange(half), np.arange(half) + half], -1).reshape(E)


def program_params(cfg: dict, mcfg, seed: int):
    """The reference's weights for ``seed`` in the program's parameter
    tree (segments split at the exits, q and k in the program's RoPE
    order, the vocabulary padded with zeros), made in one jitted call."""
    import jax
    import jax.numpy as jnp

    from repro.models.config import build_plan

    d = R.dims(cfg)
    perm = rope_permutation(d["E"])
    pad = mcfg.padded_vocab - d["V"]
    bounds = [(s.depth_end - s.n_layers, s.depth_end)
              for s in build_plan(mcfg).segments]

    def heads(a, n):
        return a.reshape(a.shape[:-1] + (n, d["E"]))[..., perm].reshape(
            a.shape)

    def tree(key):
        W = R.weights(cfg, key)
        Lw = W["layers"]
        segments = []
        for lo, hi in bounds:
            s = {k: v[lo:hi] for k, v in Lw.items()}
            segments.append({
                "ln1": s["ln1"], "ln2": s["ln2"],
                "attn": {"wq": heads(s["wq"], d["H"]),
                         "wk": heads(s["wk"], d["K"]), "wv": s["wv"],
                         "wo": s["wo"], "bq": heads(s["bq"], d["H"]),
                         "bk": heads(s["bk"], d["K"]), "bv": s["bv"]},
                "ffn": {"w1": s["gate"], "w3": s["up"], "w2": s["down"]}})
        return {"embed": {"tok": jnp.pad(W["embed"], ((0, pad), (0, 0)))},
                "segments": segments,
                "exits": [{"norm": e["norm"],
                           "head": jnp.pad(e["head"], ((0, 0), (0, pad)))}
                          for e in W["exits"]]}

    # the key is an argument, not a constant: every seed runs one program
    return jax.jit(tree)(jax.random.key(int(seed)))


@dataclass
class State:
    cfg: dict
    mcfg: object
    cluster: object
    new_tokens: int
    sim0: float = 0.0
    #: the window's requests, and per request when its batch started and
    #: ended (s into the window), the pod that served it and its tokens
    arrivals: object = None
    start: np.ndarray = None
    end: np.ndarray = None
    pod: np.ndarray = None
    outputs: np.ndarray = None
    batches: list = field(default_factory=list)
    attempted: int = 0
    refused: int = 0
    failed: int = 0


def _requests(st, ids, prompts, at, homes):
    from repro.serving.engine import Request

    return [Request(rid=int(i), model=st.mcfg.name, tokens=prompts[i],
                    max_new=st.new_tokens, home=int(homes[i]),
                    deadline=st.sim0 + at[i] + float(st.cfg["deadline_s"]))
            for i in ids]


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

    # every batch shape the window can serve, through the timed path
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


def window(ctx, st: State):
    tr = ctx.traffic
    arr = st.arrivals = G.PoissonArrivals(
        tr, st.mcfg.vocab_size, int(st.cfg["pods"]), ctx.window_length,
        ctx.sub_seed("traffic"))
    n, N = len(arr), st.new_tokens
    st.start, st.end = np.full(n, np.nan), np.full(n, np.nan)
    st.outputs = np.zeros((n, N), np.int32)
    st.pod = np.full(n, -1)
    sizes = sorted(int(b) for b in tr["batch_sizes"])
    stop = ctx.window_length + float(tr["drain_limit_s"])
    cluster = st.cluster
    st.sim0 = cluster.now
    queue, nxt, late = deque(), 0, 0.0
    st.refused = 0
    while True:
        now = ctx.now()
        while nxt < n and arr.at[nxt] <= now:
            queue.append(nxt)
            nxt += 1
        if not queue:
            if nxt == n:
                break
            time.sleep(arr.at[nxt] - now)
            late = max(late, ctx.now() - arr.at[nxt])
            continue
        if now > stop:
            break
        b = max(s for s in sizes if s <= len(queue))
        ids = [queue.popleft() for _ in range(b)]
        reqs = _requests(st, ids, arr.prompts, arr.at, arr.homes)
        t0 = ctx.now()
        cluster.now = st.sim0 + t0
        with ctx.span("submit"):
            cluster.submit(reqs)
        t1 = ctx.now()
        groups = {}
        for i, r in zip(ids, reqs):
            st.refused += r.missed
            if not r.done or len(r.output) != N:
                continue
            st.start[i], st.end[i], st.pod[i] = t0, t1, r.served_by
            st.outputs[i] = r.output
            groups.setdefault(r.served_by, []).append(i)
        for pod, g in groups.items():
            st.batches.append({"ids": g, "pod": int(pod), "exit": int(
                cluster.pods[pod].cache.serveable(st.mcfg.name))})
    done = ~np.isnan(st.end)
    st.attempted, st.failed = n, int(n - done.sum())
    ctx.info["refused"] = st.refused
    ctx.count("requests_served", int(done.sum()))
    ctx.count("batches", len(st.batches))
    ctx.info["generator_late_ms"] = 1e3 * late
    ctx.info["served_by_pod"] = {int(p): int((st.pod == p).sum())
                                 for p in np.unique(st.pod[done])}
    ctx.info["batch_sizes_served"] = {
        int(b): int(c) for b, c in zip(*np.unique(
            [len(x["ids"]) for x in st.batches], return_counts=True))}
    ctx.info["served_exits"] = sorted({x["exit"] for x in st.batches})
    ctx.info["drained_s"] = max(0.0, float(np.nanmax(st.end, initial=0.0))
                                - ctx.window_length)


def latencies(ctx, st: State) -> np.ndarray:
    """Every request's time from its scheduled arrival to its last token,
    in s.  A request refused or never answered misses: it reads as served
    at the horizon, the window's end plus the drain limit, so it ranks
    behind every request served and fewer answers never read faster."""
    horizon = ctx.window_length + float(ctx.traffic["drain_limit_s"])
    return np.where(np.isnan(st.end), horizon, st.end) - st.arrivals.at


def end_to_end(ctx, st: State) -> dict:
    return {"req_p90_ms": 1e3 * H.quantile(latencies(ctx, st), 0.9)}


def free(st: State):
    """Nothing is freed before the check: it reruns the pods' compiled
    programs first, then frees them (:func:`check`)."""


def sample(ctx, st: State) -> list:
    """The served batches the check compares: ``check_batches`` of them
    drawn from the seed, the first and the first of the largest always
    among them."""
    sizes = [len(b["ids"]) for b in st.batches]
    if not sizes:
        return []
    keep = sorted({0, sizes.index(max(sizes))})
    rest = [i for i in range(len(sizes)) if i not in keep]
    k = max(0, min(int(ctx.traffic["check_batches"]) - len(keep), len(rest)))
    rng = np.random.default_rng(ctx.sub_seed("check"))
    return sorted(keep + [int(i) for i in rng.choice(rest, k,
                                                     replace=False)])


def replay(st: State, batch: dict, P: int) -> np.ndarray:
    """The serving pod's compiled prefill and decode at the batch's own
    size, run again with the served tokens fed back: logits (B, N, V) in
    float32, V the padded vocabulary the program computes."""
    import jax.numpy as jnp

    from repro.models import model as M
    from repro.models.config import submodel_plan

    ids, N = batch["ids"], st.new_tokens
    pod = st.cluster.pods[batch["pod"]]
    params = pod.cache.params[st.mcfg.name]
    pf, dc, mplan = pod._fns(st.mcfg.name, batch["exit"], len(ids), P + N)
    cache = M.cache_init(st.mcfg, len(ids), P + N,
                         submodel_plan(mplan, batch["exit"]))
    served = st.outputs[ids]
    lg, kv = pf(params, {"tokens": jnp.asarray(st.arrivals.prompts[ids])},
                cache)
    out = [lg]
    for k in range(N - 1):
        lg, kv = dc(params, jnp.asarray(served[:, k:k + 1]),
                    jnp.int32(P + k), kv)
        out.append(lg)
    return np.stack([np.asarray(x, np.float32) for x in out], 1)


def compare(ref: np.ndarray, logits: np.ndarray, tokens: np.ndarray):
    """(relative L2 distance of ``logits`` from ``ref`` per step, the gap
    of each token's reference logit below the reference's largest), for
    one sequence: ``ref`` and ``logits`` (N, V), ``tokens`` (N,)."""
    rel = np.linalg.norm(logits - ref, axis=-1) / np.linalg.norm(ref,
                                                                  axis=-1)
    gap = ref.max(-1) - ref[np.arange(len(tokens)), tokens]
    return rel, gap


def _sequences(st: State, batches, P: int):
    """(batch, row, request id, tokens fed to the reference) of every
    sequence of the sampled batches: the prompt and the served tokens
    but the last."""
    for bi in batches:
        for row, i in enumerate(st.batches[bi]["ids"]):
            yield bi, row, i, np.concatenate(
                [st.arrivals.prompts[i], st.outputs[i, :-1]])[None]


def check(ctx, st: State) -> list:
    P = int(ctx.traffic["prompt_tokens"])
    batches = sample(ctx, st)
    differing, program = 0, {}
    for bi in batches:
        lg = replay(st, st.batches[bi], P)
        ids = st.batches[bi]["ids"]
        differing += int(np.sum(lg.argmax(-1) != st.outputs[ids]))
        program[bi] = lg[:, :, :st.mcfg.vocab_size]
    st.cluster = None
    gc.collect()
    W = R.weights(st.cfg, ctx.sub_seed("weights"))
    # a run that served nothing has nothing to compare, and is not correct
    rel_max = gap_max = 0.0 if batches else float("inf")
    for bi, row, i, seq in _sequences(st, batches, P):
        ref = np.asarray(R.logits(st.cfg, W, seq, st.batches[bi]["exit"],
                                  first=P - 1)[0])
        rel, gap = compare(ref, program[bi][row], st.outputs[i])
        rel_max, gap_max = max(rel_max, rel.max()), max(gap_max, gap.max())
    ctx.info["check_sequences"] = sum(len(st.batches[b]["ids"])
                                      for b in batches)
    lim = st.cfg["limits"]
    return [{"name": "tokens_replayed_differing", "value": float(differing),
             "limit": lim["tokens_replayed_differing"]},
            {"name": "logits_rel_l2", "value": float(rel_max),
             "limit": lim["logits_rel_l2"]},
            {"name": "logit_gap", "value": float(gap_max),
             "limit": lim["logit_gap"]},
            {"name": "requests_unanswered",
             "value": float(st.failed - st.refused),
             "limit": lim["requests_unanswered"]}]


def control(ctx, st: State) -> dict:
    """The control's and the planted fault's readings on the sampled
    batches, against the reference: ``fp8``, the reference computed in
    float8 (the precision below the configuration's bfloat16: both
    operands of every projection and of the head), and ``skip_layer``,
    the reference with the middle layer of the served prefix left out.
    Each teacher-forced on the served tokens; the gap is that of the
    token each puts first."""
    P = int(ctx.traffic["prompt_tokens"])
    W = R.weights(st.cfg, ctx.sub_seed("weights"))
    worst = {}
    for bi, row, i, seq in _sequences(st, sample(ctx, st), P):
        ex = st.batches[bi]["exit"]
        ref = np.asarray(R.logits(st.cfg, W, seq, ex, first=P - 1)[0])
        depth = int(st.cfg["exit_layers"][ex])
        for name, kw in (("fp8", {"fp8": True}),
                         ("skip_layer", {"skip_layer": depth // 2})):
            lg = np.asarray(R.logits(st.cfg, W, seq, ex, first=P - 1,
                                     **kw)[0])
            rel, gap = compare(ref, lg, lg.argmax(-1))
            for k, v in (("logits_rel_l2", rel.max()),
                         ("logit_gap", gap.max())):
                key = f"{name}:{k}"
                worst[key] = max(worst.get(key, 0.0), float(v))
    return worst
