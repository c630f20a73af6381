"""Edge serving engine: batched prefill+decode over cached submodels, with
deadline-aware routing, straggler re-routing, and BS-failure handling.

The cluster advances a simulated clock (transfer/compute latencies come from
the catalog model) while *actually executing* generation with the cached
submodel parameters — so functional outputs are real and timing is
controllable on CPU.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as M
from repro.models import moe, partition
from repro.models.config import build_plan, submodel_plan
from repro.obs.tracing import TRACER
from repro.serving.loader import PodCache, WeightStore


@dataclass
class Request:
    rid: int
    model: str
    tokens: list
    max_new: int
    home: int
    deadline: float            # absolute sim-time deadline
    arrival: float = 0.0
    output: list = field(default_factory=list)
    served_by: int = -1
    precision: float = 0.0
    done: bool = False
    missed: bool = False


class EdgePod:
    def __init__(self, idx: int, store: WeightStore, capacity_bytes: int,
                 bandwidth_Bps: float, compute_flops: float):
        self.idx = idx
        self.cache = PodCache(store, capacity_bytes, bandwidth_Bps)
        self.compute = compute_flops
        self.failed = False
        self.busy_until = 0.0
        self._decode_fns = {}

    # -- actual execution ------------------------------------------------
    def _fns(self, model: str, exit_idx: int, batch: int, max_len: int):
        """(prefill, decode, plan) compiled for one batch shape; the
        entry's fourth program makes that shape's empty cache."""
        key = (model, exit_idx, batch, max_len)
        if key not in self._decode_fns:
            cfg = self.cache.store.cfgs[model]
            plan = build_plan(cfg)
            pf = jax.jit(lambda p, b, c: M.prefill(cfg, p, b, c,
                                                   exit_idx=exit_idx,
                                                   plan=plan))
            dc = jax.jit(lambda p, t, pos, c: M.decode(cfg, p, t, pos, c,
                                                       exit_idx=exit_idx,
                                                       plan=plan))
            sub = submodel_plan(plan, exit_idx)
            mk = jax.jit(lambda: M.cache_init(cfg, batch, max_len, sub))
            self._decode_fns[key] = (pf, dc, plan, mk)
        return self._decode_fns[key][:3]

    def serve_batch(self, model: str, reqs: list, now: float):
        """Run real generation for a batch of same-model requests."""
        cfg = self.cache.store.cfgs[model]
        exit_idx = self.cache.serveable(model)
        assert exit_idx >= 0, "model not resident"
        params = self.cache.params[model]
        prompt = max(len(r.tokens) for r in reqs)
        max_new = max(r.max_new for r in reqs)
        B = len(reqs)
        max_len = prompt + max_new
        pf, dc, plan = self._fns(model, exit_idx, B, max_len)
        # the cache is made by a compiled program and the decode position
        # passed as a NumPy scalar: no eager op of the serving loop is
        # traced again if JAX's dispatch caches miss
        cache = self._decode_fns[(model, exit_idx, B, max_len)][3]()
        toks = np.zeros((B, prompt), np.int32)
        for i, r in enumerate(reqs):
            toks[i, -len(r.tokens):] = r.tokens     # left-pad with 0
        batch = {"tokens": jnp.asarray(toks)}
        if cfg.family == "encdec":
            batch["frames"] = jnp.zeros((B, cfg.encoder_len, cfg.d_model),
                                        jnp.dtype(cfg.dtype))
        if cfg.family == "vlm":
            batch["patches"] = jnp.zeros((B, cfg.frontend_len, cfg.d_model),
                                         jnp.dtype(cfg.dtype))
        # program spans: the prefill until its token is on the host, and
        # the decode steps until the last one's token is (``max_new``
        # steps, of which the last one's token is not served); a MoE
        # model's prefill also names the rows of its held experts' chunk
        attrs = dict(batch=B, tokens=B * prompt, exit=exit_idx)
        if cfg.n_experts:
            attrs["moe_rows"] = moe.held_rows(cfg, B * prompt)
        with TRACER.span("serve.prefill", count_retraces=False, **attrs):
            logits, kv = pf(params, batch, cache)
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            outs = [[t] for t in np.asarray(tok)[:, 0].tolist()]
        with TRACER.span("serve.decode", count_retraces=False,
                         steps=max_new):
            for step in range(max_new):
                logits, kv = dc(params, tok, np.int32(prompt + step), kv)
                tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
                for o, t in zip(outs, np.asarray(tok)[:, 0].tolist()):
                    o.append(t)
        outs = [o[:max_new] for o in outs]

        # simulated service time from the catalog's FLOPs model
        c_h = partition.submodel_flops_per_token(cfg, exit_idx, ctx=prompt)
        secs = (B * (prompt + max_new) * c_h) / self.compute
        self.busy_until = max(self.busy_until, now) + secs
        return outs, secs


class EdgeCluster:
    """Pods + control plane: routing, straggler re-route, failure handling."""

    def __init__(self, store: WeightStore, n_pods: int, capacity_bytes: int,
                 bandwidth_Bps: float = 100e9, compute_flops: float = 197e12,
                 precisions: dict = None):
        self.store = store
        self.pods = [EdgePod(i, store, capacity_bytes, bandwidth_Bps,
                             compute_flops) for i in range(n_pods)]
        self.now = 0.0
        self.log = []
        # measured/assumed per-(model, exit) precision ladder
        self.precisions = precisions or {}

    def precision_of(self, model, exit_idx):
        cfg = self.store.cfgs[model]
        if (model, exit_idx) in self.precisions:
            return self.precisions[(model, exit_idx)]
        frac = cfg.exit_layers[exit_idx] / cfg.n_layers
        return 0.99 * (1 - 0.45 * (1 - frac) ** 1.5)

    def apply_caching(self, decisions):
        """decisions: {pod_idx: {model: exit_idx or -1}} from the control
        plane (CoCaR / CoCaR-OL output)."""
        for pi, models in decisions.items():
            pod = self.pods[pi]
            for model, j in models.items():
                if j < 0:
                    pod.cache.evict(model)
                else:
                    pod.cache.request_load(model, j, self.now)

    def tick(self, dt: float):
        self.now += dt
        for pod in self.pods:
            if not pod.failed:
                pod.cache.tick(self.now)

    def fail_pod(self, idx: int):
        self.pods[idx].failed = True
        self.log.append(("fail", idx, self.now))

    def recover_pod(self, idx: int):
        self.pods[idx].failed = False
        self.log.append(("recover", idx, self.now))

    def route(self, req: Request):
        """Pick the pod maximizing precision subject to deadline slack;
        straggler mitigation = skip pods whose queue would miss the
        deadline, falling back to the next-best pod."""
        best, best_score = None, -1.0
        for pod in self.pods:
            if pod.failed:
                continue
            j = pod.cache.serveable(req.model)
            if j < 0:
                continue
            eta = max(pod.busy_until, self.now)
            if eta > req.deadline:
                continue                       # would straggle -> re-route
            score = self.precision_of(req.model, j)
            if score > best_score:
                best, best_score = pod, score
        return best

    def submit(self, reqs: list):
        """Route and execute a batch of requests; returns served count."""
        by_key = {}
        for r in reqs:
            r.arrival = self.now
            pod = self.route(r)
            if pod is None:
                r.missed = True
                self.log.append(("cloud", r.rid, self.now))
                continue
            by_key.setdefault((pod.idx, r.model), []).append(r)
        served = 0
        for (pi, model), group in by_key.items():
            pod = self.pods[pi]
            outs, secs = pod.serve_batch(model, group, self.now)
            j = pod.cache.serveable(model)
            for r, o in zip(group, outs):
                r.output = o
                r.served_by = pi
                r.precision = self.precision_of(model, j)
                r.done = True
                served += 1
        return served
