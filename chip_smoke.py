"""Chip smoke test: the main path, from CoCaR decisions to served
requests, on a TPU.

    python chip_smoke.py              # one chip: phases 1-3
    python chip_smoke.py --chips 4    # the sharded grid executor on four
                                      # chips against the one-chip dispatch

Phase 1 decides one CoCaR window at the paper's Sec. VII-A deployment
(``MECConfig()``: N=5, U=600, M=8, 500 MB, Zipf 0.8) on both LP backends
and checks the decisions: feasible, identical to the NumPy oracle run on
the device's own fractional solution, and identical across backends.
Phase 2 runs CoCaR-OL for 20 slots on the scan engine against the NumPy
engine.  Phase 3 serves qwen1.5-0.5b at its published widths (random
weights from ``--seed``) from a CoCaR plan on four pods of the chip and
checks the served prefill logits against a plain float32 forward.

Each phase prints its set-up seconds (first call, compile included) and
the wall seconds of one warm repeat.  The last line, printed only when
every check passed, is the JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Without a TPU
the script exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: phase 3: four pods of one chip, each a quarter of its 16 GB, serving
#: one batch of 8 requests
N_PODS, POD_CAPACITY_MB, N_REQUESTS = 4, 4000.0, 8

#: phase 3: relative L2 error of the served bf16 prefill logits against
#: the float32 forward (bf16 keeps 8 mantissa bits, ~0.4% per rounding;
#: 24 layers of it stay within a few percent of the logits' norm)
LOGIT_REL_TOL = 5e-2


def _timed(fn):
    """(result, wall seconds), the clock stopped once the result is ready."""
    import jax

    t = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t


def _phase_line(name, setup_s, warm_s, **info):
    print(json.dumps({"phase": name, "setup_s": setup_s, "warm_s": warm_s,
                      **info}), flush=True)


def _first_diff(a, b):
    """First differing leading (row, column) index of two decision
    arrays, or None."""
    import numpy as np

    diff = np.argwhere(np.asarray(a) != np.asarray(b))
    return None if not len(diff) else tuple(int(i) for i in diff[0][:2])


def _check_identical(what, x_a, A_a, t_a, x_b, A_b, t_b, inst, frac_a,
                     frac_b, u_cat, u_phi):
    """Decision identity of two (x, A, best trial) triples.  On a
    mismatch, print the first differing (BS, model) / (BS, user) and the
    rounding margins of the fractional solutions behind them, then fail."""
    import numpy as np

    same = (np.array_equal(x_a, x_b) and np.array_equal(A_a, A_b)
            and t_a == t_b)
    if same:
        return
    from harness import decision_margin, threshold_shift_certificate

    oh = inst.onehot_mu()
    N, U = inst.N, inst.U
    uc, up = u_cat[0, :, :N], u_phi[0, :, :N, :U]
    print(json.dumps({
        "identity_failed": what,
        "first_bs_model": _first_diff(x_a, x_b),
        "first_bs_user": _first_diff(A_a, A_b),
        "best_trial": [int(t_a), int(t_b)],
        "margin": decision_margin(*frac_a, oh, uc, up),
        "certificate": threshold_shift_certificate(*frac_a, *frac_b, oh,
                                                   uc, up)},
        default=float), flush=True)
    raise SystemExit(f"{what}: decisions differ")


# ---------------------------------------------------------------------------
# phase 1: offline control plane
# ---------------------------------------------------------------------------

def _has_mosaic_kernel(stacked, u_cat, u_phi, pdhg_iters):
    """Whether the compiled pallas-backend pipeline holds the Mosaic
    PDHG kernel (a ``tpu_custom_call``)."""
    import jax

    from repro.core import cocar as CC

    with jax.enable_x64(True):
        hlo = CC._pipeline_jitted("pallas").lower(
            stacked.data, u_cat, u_phi, pdhg_iters, 1).compile().as_text()
    return "tpu_custom_call" in hlo


def phase_offline(seed: int, cfg=None, pdhg_iters: int = 4000,
                  best_of: int = 8):
    from repro.core import cocar as CC
    from repro.core.jdcr import check_feasible
    from repro.mec.scenario import MECConfig, Scenario, stack_instances

    cfg = cfg or MECConfig(seed=seed)
    sc = Scenario(cfg)
    inst = sc.instance(0, sc.empty_cache())
    stacked = stack_instances([inst])
    N, U = inst.N, inst.U
    u_cat, u_phi = CC.offline_uniforms(stacked, seed, 1, best_of)
    decided = {}
    for backend in ("reference", "pallas"):
        def decide():
            return CC.cocar_grid([inst], seed=seed, pdhg_iters=pdhg_iters,
                                 best_of=best_of, lp_backend=backend)

        grid, setup_s = _timed(decide)
        _, warm_s = _timed(decide)
        x, A, info = grid[0][0]
        dev = CC.offline_pipeline_device(stacked, u_cat, u_phi, pdhg_iters,
                                         1, lp_backend=backend)
        frac = (dev["x_frac"][0, :N], dev["A_frac"][0, :N, :U])
        _check_identical(f"cocar_grid vs offline_pipeline_device "
                         f"({backend})", x, A, info["best_t"],
                         dev["x"][0, 0, :N], dev["A"][0, 0, :N, :U],
                         int(dev["best_t"][0, 0]), inst, frac, frac,
                         u_cat, u_phi)
        feas = check_feasible(inst, x, A)
        if not feas["ok"]:
            raise SystemExit(f"phase 1 ({backend}): infeasible {feas}")
        xh, Ah, ih = CC.offline_pipeline_host(stacked, dev["x_frac"],
                                              dev["A_frac"], u_cat,
                                              u_phi)[0][0]
        _check_identical(f"device vs NumPy oracle ({backend})", x, A,
                         info["best_t"], xh, Ah, ih["best_t"], inst, frac,
                         frac, u_cat, u_phi)
        line = {}
        if backend == "pallas":
            line["tpu_custom_call"] = _has_mosaic_kernel(
                stacked, u_cat, u_phi, pdhg_iters)
            if not line["tpu_custom_call"]:
                raise SystemExit("phase 1: the pallas LP backend compiled "
                                 "without its Mosaic kernel")
        decided[backend] = (x, A, info["best_t"], frac)
        _phase_line(f"offline:{backend}", setup_s, warm_s,
                    window=[N, U, inst.M], feasible=True,
                    identical_to_numpy_oracle=True,
                    avg_precision=info["metrics"]["avg_precision"],
                    lp_obj=info["lp_obj"], **line)
    r, p = decided["reference"], decided["pallas"]
    _check_identical("reference vs pallas LP backend", r[0], r[1], r[2],
                     p[0], p[1], p[2], inst, r[3], p[3], u_cat, u_phi)
    print(json.dumps({"phase": "offline", "identical_across_backends":
                      True}), flush=True)


# ---------------------------------------------------------------------------
# phase 2: online control plane
# ---------------------------------------------------------------------------

def phase_online(seed: int, cfg=None, n_slots: int = 20):
    from repro.core.online import OnlineConfig, run_online
    from repro.mec.scenario import MECConfig
    from repro.traces.registry import default_workload

    cfg = cfg or MECConfig(seed=seed)
    ocfg = OnlineConfig(n_slots=n_slots)
    wl = default_workload(cfg, ocfg)

    def scan():
        return run_online(wl, "cocar-ol", cfg=cfg, ocfg=ocfg,
                          record_states=True)

    out, setup_s = _timed(scan)
    _, warm_s = _timed(scan)
    ref = run_online(wl, "cocar-ol", cfg=cfg, ocfg=ocfg, engine="numpy",
                     record_states=True)
    gap = abs(out["avg_qoe"] - ref["avg_qoe"])
    if not gap <= 1e-9:
        raise SystemExit(f"phase 2: scan avg_qoe {out['avg_qoe']!r} vs "
                         f"numpy {ref['avg_qoe']!r}")
    _phase_line("online", setup_s, warm_s, slots=n_slots,
                avg_qoe=out["avg_qoe"], avg_qoe_numpy=ref["avg_qoe"],
                qoe_gap=gap)


# ---------------------------------------------------------------------------
# phase 3: data plane
# ---------------------------------------------------------------------------

def _f32_prefill_logits(model_cfg, params, tokens, exit_idx):
    """Last-position logits at exit ``exit_idx`` from the plain training
    forward, in float32 with float32 parameters and full-precision
    matmuls — the reference the served prefill is checked against."""
    import jax
    import jax.numpy as jnp

    from repro.models import model as M
    from repro.models.layers import exit_head_fwd

    cfg32 = model_cfg.replace(dtype="float32", param_dtype="float32")

    @jax.jit
    def fwd(p, tokens):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)

        def head(j, h):
            if j != exit_idx:
                return None
            return exit_head_fwd(cfg32, p["exits"][j], h[:, -1:])

        outs, _ = M.apply_train(cfg32, p, {"tokens": tokens}, consume=head)
        return outs[exit_idx][:, 0]

    with jax.default_matmul_precision("highest"):
        return fwd(params, tokens)


def phase_serving(seed: int, model_cfg=None, prompt: int = 128,
                  new_tokens: int = 32):
    import jax.numpy as jnp
    import numpy as np

    from repro import configs
    from repro.core.cocar import cocar_grid
    from repro.mec.catalog import make_catalog
    from repro.mec.scenario import MECConfig, Scenario
    from repro.models import model as M
    from repro.models.config import submodel_plan
    from repro.serving.engine import EdgeCluster, Request
    from repro.serving.loader import WeightStore
    from repro.serving.plan import catalog_precisions, plan_from_offline

    model_cfg = model_cfg or configs.get_config("qwen1.5-0.5b")
    name = model_cfg.name
    cfgs = {name: model_cfg}
    # control plane: one CoCaR window over the measured catalog
    cat = make_catalog("measured", cfgs=cfgs, tokens=prompt + new_tokens)
    sc = Scenario(MECConfig(n_bs=N_PODS, n_models=1,
                            mem_capacity_mb=POD_CAPACITY_MB, seed=seed),
                  catalog=cat)
    inst = sc.instance(0, sc.empty_cache())
    x, _, _ = cocar_grid([inst], seed=seed)[0][0]
    plan = plan_from_offline(x, [name], catalog=cat)
    placed = {n: res.get(name, -1) for n, res in plan.residency.items()}

    # data plane: the plan applied to four pods of the chip
    store = WeightStore(cfgs, seed=seed)
    cluster = EdgeCluster(store, n_pods=N_PODS,
                          capacity_bytes=int(POD_CAPACITY_MB * 1e6),
                          bandwidth_Bps=cat.bandwidth_MBps * 1e6,
                          precisions=catalog_precisions(cat, [name]))
    cluster.apply_caching(plan.residency)
    cluster.tick(plan.max_load_s())
    if any(pod.cache.loading for pod in cluster.pods):
        raise SystemExit("phase 3: loads still in flight after the plan's "
                         "own load time")
    prompts = np.random.default_rng(seed).integers(
        1, model_cfg.vocab_size, size=(N_REQUESTS, prompt), dtype=np.int32)

    def serve():
        reqs = [Request(rid=i, model=name, tokens=list(prompts[i]),
                        max_new=new_tokens, home=i % N_PODS,
                        deadline=cluster.now + 60.0)
                for i in range(N_REQUESTS)]
        return cluster.submit(reqs), reqs

    (served, reqs), setup_s = _timed(serve)
    if served != N_REQUESTS or any(len(r.output) != new_tokens
                                   for r in reqs):
        raise SystemExit(f"phase 3: served {served}/{N_REQUESTS}, tokens "
                         f"{[len(r.output) for r in reqs]}")
    _, warm_s = _timed(serve)

    # the serving pod's own compiled prefill/decode, timed and checked
    pod = cluster.pods[reqs[0].served_by]
    j = pod.cache.serveable(name)
    max_len = prompt + new_tokens
    pf, dc, mplan = pod._fns(name, j, N_REQUESTS, max_len)
    params = pod.cache.params[name]
    tokens = jnp.asarray(prompts)

    def prefill():
        cache = M.cache_init(model_cfg, N_REQUESTS, max_len,
                             submodel_plan(mplan, j))
        return pf(params, {"tokens": tokens}, cache)

    (logits, kv), prefill_s = _timed(prefill)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)

    def decode():
        t, c = tok, kv
        for step in range(new_tokens):
            lg, c = dc(params, t, jnp.int32(prompt + step), c)
            t = jnp.argmax(lg, -1)[:, None].astype(jnp.int32)
        return t

    _, decode_s = _timed(decode)
    ref = np.asarray(_f32_prefill_logits(model_cfg, store.params[name],
                                         tokens, j))
    got = np.asarray(logits, np.float32)
    rel = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
    if not (np.isfinite(got).all() and rel <= LOGIT_REL_TOL):
        raise SystemExit(f"phase 3: served logits off the f32 reference: "
                         f"rel L2 {rel!r} > {LOGIT_REL_TOL}")
    _phase_line("serving", setup_s, warm_s, model=name,
                placed_exits=placed, served=served, served_by=pod.idx,
                served_exit=j, new_tokens=new_tokens,
                prefill_s=prefill_s, decode_s_per_token=decode_s
                / new_tokens, logits_rel_l2=rel,
                logits_rel_l2_tol=LOGIT_REL_TOL)


# ---------------------------------------------------------------------------
# --chips 4: the sharded grid executor
# ---------------------------------------------------------------------------

def phase_sharded(seed: int, n_chips: int, base=None,
                  pdhg_iters: int = 4000, best_of: int = 8):
    import numpy as np

    from repro.core.cocar import cocar_grid
    from repro.experiments.sweep import DEFAULT_AXES
    from repro.mec.scenario import MECConfig, Scenario, config_grid
    from repro.obs import TRACER

    insts = []
    for c in config_grid(base or MECConfig(seed=seed), DEFAULT_AXES):
        sc = Scenario(c)
        insts.append(sc.instance(0, sc.empty_cache()))
    kw = dict(seed=seed, pdhg_iters=pdhg_iters, best_of=best_of)

    def sharded():
        return cocar_grid(insts, backend="sharded", devices=n_chips, **kw)

    out, setup_s = _timed(sharded)
    t_warm = time.perf_counter()
    _, warm_s = _timed(sharded)
    devices = sorted({d for sp in TRACER.since(t_warm)
                      if sp.name == "chunk" for d in sp.attrs["devices"]})
    def one_chip():
        return cocar_grid(insts, backend="device", **kw)

    one, one_setup_s = _timed(one_chip)
    _, one_warm_s = _timed(one_chip)
    for w, (per_s, per_o) in enumerate(zip(out, one)):
        (xs, As, i_s), (xo, Ao, i_o) = per_s[0], per_o[0]
        if not (np.array_equal(xs, xo) and np.array_equal(As, Ao)
                and i_s["best_t"] == i_o["best_t"]):
            raise SystemExit(f"--chips {n_chips}: window {w} decisions "
                             f"differ; first (BS, model) "
                             f"{_first_diff(xs, xo)}, (BS, user) "
                             f"{_first_diff(As, Ao)}")
    if len(devices) != n_chips:
        raise SystemExit(f"--chips {n_chips}: the grid ran on devices "
                         f"{devices}")
    _phase_line("sharded", setup_s, warm_s, windows=len(insts),
                devices=devices, one_chip_setup_s=one_setup_s,
                one_chip_warm_s=one_warm_s, identical_to_one_chip=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded grid executor on four "
                         "chips and the one-chip dispatch it must match")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()
    if dev[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (found {dev[0].platform})")
    if len(dev) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but "
                         f"{len(dev)} device(s)")
    sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(ROOT, "tests")]
    from repro.compile_cache import enable_compile_cache

    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    print(json.dumps({"device": device,
                      "compile_cache": enable_compile_cache()}), flush=True)
    if args.chips == 1:
        phase_offline(args.seed)
        phase_online(args.seed)
        phase_serving(args.seed)
    else:
        phase_sharded(args.seed, args.chips)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
