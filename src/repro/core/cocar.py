"""CoCaR — the offline algorithm (paper Alg. 1 + Sec. V-D) and the
window-by-window offline driver.

``cocar_window`` handles one window on the host.  For grids, the whole
offline pipeline — LP (PDHG) → randomized rounding → repair → trial
argmax → window metrics — is a single jitted/vmapped device dispatch over
(windows × rounding seeds × best_of trials): ``offline_pipeline_device``,
run through the ``repro.scale`` grid executor by ``cocar_grid``,
``cocar_windows_batched`` and the sweep harness
(``repro.experiments.sweep``).

``offline_pipeline_host`` is the NumPy reference of the same computation
(per-window Python loops over seeds and trials).  Both consume the same
pre-drawn rounding uniforms and make decision-identical choices — the
offline counterpart of the PR-2 online-engine equivalence
(``docs/algorithms.md`` Sec. 7; asserted in
``tests/test_offline_batched.py`` / ``benchmarks/bench_offline.py``).
"""
from __future__ import annotations

import functools

import numpy as np

from repro.core import lp as LP
from repro.core.jdcr import JDCRInstance, objective_sel
from repro.core.rounding import (draw_rounding_uniforms, repair,
                                 repair_device, round_from_uniforms)
from repro.mec import metrics as MET
from repro.mec.scenario import MECConfig, Scenario, StackedWindows
from repro.obs.diagnostics import lp_diag_summary
from repro.obs.tracing import register_jit


def _round_and_repair(inst: JDCRInstance, x_f, A_f, seed: int, best_of: int):
    """All ``best_of`` Alg. 1 draws from one batched RNG op, then repair
    each and keep the feasible solution with the highest objective — every
    draw satisfies Thm 1's guarantee, so the max only tightens it (and cuts
    the repair losses from unlucky memory-overflow draws; draws are
    microseconds next to the LP solve)."""
    T = max(best_of, 1)
    u_cat, u_phi = draw_rounding_uniforms(seed, T, inst.N, inst.M, inst.U,
                                          inst.H)
    x_r, A_r = round_from_uniforms(np.asarray(x_f, np.float64),
                                   np.asarray(A_f, np.float64),
                                   inst.onehot_mu(), u_cat, u_phi)
    prec_u = inst.prec[inst.m_u, 1:]
    best = None
    for x_i, A_i in zip(x_r, A_r):
        x, A = repair(inst, x_i, A_i)
        val = objective_sel(prec_u, A)
        if best is None or val > best[0]:
            best = (val, x, A)
    _, x, A = best
    return x, A


def cocar_window(inst: JDCRInstance, seed: int = 0, solver: str = "scipy",
                 pdhg_iters: int = 4000, best_of: int = 8):
    """One observation window: LP -> randomized rounding -> repair."""
    if solver == "pdhg":
        res = LP.solve_lp_pdhg(inst, iters=pdhg_iters)
        x_f, A_f, obj = res.x, res.A, res.obj
    else:
        x_f, A_f, obj = LP.solve_lp_scipy(inst)
    x, A = _round_and_repair(inst, x_f, A_f, seed, best_of)
    return x, A, {"lp_obj": obj}


# ---------------------------------------------------------------------------
# the fused offline pipeline (one dispatch over windows × seeds × trials)
# ---------------------------------------------------------------------------

def _pipeline_kernel(data, u_cat, u_phi, iters, n_seeds,
                     diagnostics: bool = False):
    """One padded window through LP → round → repair → argmax → metrics,
    entirely in jnp.  ``u_cat (S·T, N, M)`` / ``u_phi (S·T, N, U, H)``
    carry ``n_seeds`` independent rounding seeds of ``best_of`` trials
    each; the best trial *per seed* is selected on device.
    ``diagnostics=True`` adds the solver's residual/objective curves
    under ``"lp_diag"`` without changing any decision bit."""
    import jax
    import jax.numpy as jnp

    # the stages are named scopes, so that their operations carry the
    # stage's name in the compiled program and the profiler's trace
    with jax.named_scope("lp"):
        lp_out = LP._pdhg_kernel(data, iters, diagnostics=diagnostics)
    x_f, A_f = lp_out[0], lp_out[1]
    with jax.named_scope("round"):
        x_r, A_r = round_from_uniforms(x_f, A_f, data.onehot_mu, u_cat,
                                       u_phi)
    with jax.named_scope("repair"):
        x_p, A_p = jax.vmap(repair_device, in_axes=(None, 0, 0))(data, x_r,
                                                                 A_r)
    with jax.named_scope("select"):
        objs = jax.vmap(lambda a: objective_sel(data.prec_u, a))(A_p)
        T = objs.shape[0] // n_seeds
        objs = objs.reshape(n_seeds, T)
        best_t = jnp.argmax(objs, axis=1)                   # (S,)
        idx = jnp.arange(n_seeds) * T + best_t
        x_b, A_b = x_p[idx], A_p[idx]                       # (S, ...)
    with jax.named_scope("metrics"):
        met = jax.vmap(
            lambda xx, aa: MET.window_metrics_device(data, xx, aa))(x_b, A_b)
        lp_obj = jnp.einsum("nuh,uh->", A_f, data.prec_u)
    out = {"x_frac": x_f, "A_frac": A_f, "x": x_b, "A": A_b,
           "trial_objs": objs, "best_t": best_t, "metrics": met,
           "lp_obj": lp_obj}
    if diagnostics:
        out["lp_diag"] = lp_out[2]
    return out


@functools.cache
def _pipeline_jitted(diagnostics: bool = False):
    import jax
    fn = jax.vmap(functools.partial(_pipeline_kernel,
                                    diagnostics=diagnostics),
                  in_axes=(0, 0, 0, None, None))
    jitted = jax.jit(fn, static_argnums=(3, 4))
    return register_jit(
        f"cocar:pipeline:diag={int(bool(diagnostics))}", jitted)


def offline_uniforms(stacked: StackedWindows, seed: int, n_seeds: int,
                     best_of: int):
    """The rounding randomness both pipeline engines share: one batched
    draw at the padded stack shape, ``(B, S·T, ...)``."""
    B = len(stacked)
    N, U, H = stacked.data.T.shape[1:]
    M = stacked.data.sizes.shape[1]
    return draw_rounding_uniforms(seed, n_seeds * max(best_of, 1),
                                  N, M, U, H, batch=B)


def offline_pipeline_device(stacked: StackedWindows, u_cat, u_phi,
                            pdhg_iters: int = 4000, n_seeds: int = 1,
                            diagnostics: bool = False):
    """The whole offline grid in ONE jitted/vmapped f64 dispatch.

    Returns a dict of padded numpy arrays: fractional solutions
    ``x_frac (B,N,M,H+1)`` / ``A_frac``, best-per-seed integral solutions
    ``x (B,S,...)`` / ``A``, per-trial objectives ``trial_objs (B,S,T)``,
    the winning trial indices ``best_t (B,S)``, window ``metrics`` (dict of
    (B,S) arrays), and ``lp_obj (B,)`` — plus batched solver curves under
    ``lp_diag`` when ``diagnostics`` is on.
    """
    import jax

    with jax.enable_x64(True):
        out = _pipeline_jitted(bool(diagnostics))(
            stacked.data, u_cat, u_phi, int(pdhg_iters), int(n_seeds))
    return {k: ({kk: np.asarray(vv) for kk, vv in v.items()}
                if isinstance(v, dict) else np.asarray(v))
            for k, v in out.items()}


def offline_pipeline_host(stacked: StackedWindows, x_frac, A_frac,
                          u_cat, u_phi, n_seeds: int = 1):
    """NumPy reference of ``offline_pipeline_device``'s round → repair →
    argmax → metrics stages: per-(window, seed, trial) Python loops over
    the *same* fractional solution and uniforms.  This is both the
    correctness oracle and the host-loop path the offline benchmark
    measures against.

    Returns ``results[b][s] = (x, A, info)`` at true (unpadded) shapes,
    with ``info = {lp_obj, obj, best_t, trial_objs, metrics}``.
    """
    T = u_cat.shape[1] // n_seeds
    results = []
    for i, (inst, (xf, Af)) in enumerate(
            zip(stacked.insts, stacked.unstack(x_frac, A_frac))):
        onehot_mu = inst.onehot_mu()
        prec_u = inst.prec[inst.m_u, 1:]
        xf = np.asarray(xf, np.float64)
        Af = np.asarray(Af, np.float64)
        lp_obj = float(inst.objective(Af))
        per_seed = []
        for s in range(n_seeds):
            sl = slice(s * T, (s + 1) * T)
            uc = u_cat[i, sl, :inst.N]
            up = u_phi[i, sl, :inst.N, :inst.U]
            x_r, A_r = round_from_uniforms(xf, Af, onehot_mu, uc, up)
            best = None
            vals = []
            for t in range(T):
                x_t, A_t = repair(inst, x_r[t], A_r[t])
                val = objective_sel(prec_u, A_t)
                vals.append(float(val))
                if best is None or val > best[0]:
                    best = (val, t, x_t, A_t)
            _, t_b, x_b, A_b = best
            info = {"lp_obj": lp_obj, "obj": float(best[0]), "best_t": t_b,
                    "trial_objs": np.asarray(vals),
                    "metrics": MET.window_metrics(inst, x_b, A_b)}
            per_seed.append((x_b, A_b, info))
        results.append(per_seed)
    return results


# ---------------------------------------------------------------------------
# the fused POLICY grid: CoCaR + all four Sec. VII-B baselines, one dispatch
# ---------------------------------------------------------------------------

#: Policy order of the fused comparison grid (paper Sec. VII-B zoo).
OFFLINE_POLICIES = ("cocar", "spr3", "greedy", "random", "gatmarl")


def _eval_policy(data, x, A):
    """Uniform evaluation stage: execution-time enforcement + window
    metrics, both on-device (identical thresholds to the host path)."""
    A_e = MET.enforce_device(data, x, A)
    return MET.window_metrics_device(data, x, A_e)


def _policy_kernel(data, u_cat, u_phi, u_cat_s, u_phi_s, u_perm, u_h,
                   u_route, gat_params, gat_feats, gat_adj, iters, n_seeds,
                   diagnostics: bool = False):
    """One padded window through ALL five policies, entirely in jnp.

    CoCaR runs the fused LP → round → repair → argmax pipeline
    (``_pipeline_kernel``); SPR³ runs the *same* LP + rounding + repair
    kernels on the relaxed pytree (one trial per seed); Greedy and the
    GatMARL rollout are deterministic (computed once, broadcast across the
    seed axis); Random consumes one pre-drawn uniform set per seed.  Every
    policy then passes through the same enforcement + metrics stage.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import baselines as BL

    S = n_seeds
    out = {}

    # repaired CoCaR solutions already satisfy the execution-time checks
    # (enforce is an identity post-repair, asserted in
    # tests/test_offline_batched.py), so the pipeline's own metrics stand
    coc = _pipeline_kernel(data, u_cat, u_phi, iters, n_seeds,
                           diagnostics=diagnostics)
    out["cocar"] = {"x": coc["x"], "A": coc["A"], "metrics": coc["metrics"]}
    out["lp_obj"] = coc["lp_obj"]
    out["cocar_frac"] = {"x": coc["x_frac"], "A": coc["A_frac"]}
    if diagnostics:
        out["lp_diag"] = coc["lp_diag"]

    relaxed = BL.spr3_relax_device(data)
    xs_f, As_f = LP._pdhg_kernel(relaxed, iters)
    xs_r, As_r = round_from_uniforms(xs_f, As_f, relaxed.onehot_mu,
                                     u_cat_s, u_phi_s)
    xs, As = jax.vmap(repair_device, in_axes=(None, 0, 0))(relaxed,
                                                           xs_r, As_r)
    out["spr3"] = {"x": xs, "A": As,
                   "metrics": jax.vmap(
                       lambda xx, aa: _eval_policy(data, xx, aa))(xs, As)}
    out["spr3_frac"] = {"x": xs_f, "A": As_f}

    def once(x1, A1):
        met = _eval_policy(data, x1, A1)
        return {"x": jnp.broadcast_to(x1, (S,) + x1.shape),
                "A": jnp.broadcast_to(A1, (S,) + A1.shape),
                "metrics": jax.tree.map(
                    lambda v: jnp.broadcast_to(v, (S,)), met)}

    out["greedy"] = once(*BL.greedy_device(data))
    out["gatmarl"] = once(*BL.gat_rollout_device(data, gat_params,
                                                 gat_feats, gat_adj))

    xr, Ar = jax.vmap(BL.random_device, in_axes=(None, 0, 0, 0))(
        data, u_perm, u_h, u_route)
    out["random"] = {"x": xr, "A": Ar,
                     "metrics": jax.vmap(
                         lambda xx, aa: _eval_policy(data, xx, aa))(xr, Ar)}
    return out


@functools.cache
def _policy_jitted(diagnostics: bool = False):
    import jax
    fn = jax.vmap(functools.partial(_policy_kernel,
                                    diagnostics=diagnostics),
                  in_axes=(0,) * 11 + (None, None))
    jitted = jax.jit(fn, static_argnums=(11, 12))
    return register_jit(
        f"cocar:policy:diag={int(bool(diagnostics))}", jitted)


def policy_uniforms(stacked: StackedWindows, seed: int, n_seeds: int,
                    best_of: int):
    """All the randomness of one policy-grid run, pre-drawn at the padded
    stack shape and shared verbatim by both engines: CoCaR's rounding
    uniforms (``n_seeds × best_of`` trials), SPR³'s (one trial per seed),
    and the Random baseline's permutation/pick/route uniforms."""
    B, N, U, M, H = stacked.signature
    return policy_uniforms_dims((B, N, M, U, H), seed, n_seeds, best_of)


def policy_uniforms_dims(dims, seed, n_seeds: int, best_of: int):
    """``policy_uniforms`` from bare grid dimensions ``(B, N, M, U, H)``
    — same key splits, same draws.  The ``repro.scale`` executor draws
    these ONCE at the grid's global max shape and slices them per size
    bucket, so bucketed dispatches consume exactly the uniforms the
    max-padded single dispatch would."""
    import jax

    from repro.core import baselines as BL

    B, N, M, U, H = dims
    k_coc, k_spr, k_bl = jax.random.split(jax.random.PRNGKey(seed), 3)
    u_cat, u_phi = draw_rounding_uniforms(k_coc, n_seeds * max(best_of, 1),
                                          N, M, U, H, batch=B)
    u_cat_s, u_phi_s = draw_rounding_uniforms(k_spr, n_seeds, N, M, U, H,
                                              batch=B)
    u_perm, u_h, u_route = BL.draw_baseline_uniforms(k_bl, N, M, U,
                                                     n_seeds=n_seeds,
                                                     batch=B)
    return (u_cat, u_phi, u_cat_s, u_phi_s, u_perm, u_h, u_route)


def gat_grid_policies(stacked: StackedWindows, seed: int = 0,
                      episodes: int = 150):
    """Host-side GatMARL training for every window in the stack (cached
    per topology/catalog shape), stacked for the vmapped rollout: a
    params pytree with a leading batch axis + padded features/adjacency.
    """
    from repro.core import baselines as BL

    n_pad = stacked.data.R.shape[1]
    params, feats, adjs = [], [], []
    for inst in stacked.insts:
        params.append(BL.gat_policy(inst, seed, episodes))
        feats.append(BL.gat_features(inst, n_pad=n_pad))
        adjs.append(BL.gat_adj(inst, n_pad=n_pad))
    stacked_params = {k: np.stack([p[k] for p in params])
                      for k in params[0]}
    return stacked_params, np.stack(feats), np.stack(adjs)


def policy_grid_device(stacked: StackedWindows, seed: int = 0,
                       pdhg_iters: int = 4000, best_of: int = 8,
                       n_seeds: int = 1, episodes: int = 150,
                       uniforms=None, gat=None,
                       diagnostics: bool = False):
    """CoCaR + the four baselines over (windows × seeds) in ONE jitted/
    vmapped f64 dispatch (GatMARL training excepted — host-side, cached).

    Returns nested numpy: ``out[policy] = {x (B,S,...), A (B,S,...),
    metrics {k: (B,S)}}`` plus ``lp_obj (B,)`` and SPR³'s fractional
    solution (``spr3_frac``) for the host oracle — plus CoCaR's batched
    solver curves under ``lp_diag`` when ``diagnostics`` is on.
    """
    import jax

    uniforms = uniforms if uniforms is not None else \
        policy_uniforms(stacked, seed, n_seeds, best_of)
    gat = gat if gat is not None else \
        gat_grid_policies(stacked, seed, episodes)
    gat_params, gat_feats, gat_adj = gat
    with jax.enable_x64(True):
        out = _policy_jitted(bool(diagnostics))(
            stacked.data, *uniforms, gat_params, gat_feats, gat_adj,
            int(pdhg_iters), int(n_seeds))

    def to_np(tree):
        if isinstance(tree, dict):
            return {k: to_np(v) for k, v in tree.items()}
        return np.asarray(tree)

    return to_np(out)


def policy_grid_host(stacked: StackedWindows, uniforms, gat,
                     x_frac, A_frac, spr3_frac, n_seeds: int = 1):
    """NumPy reference of ``policy_grid_device``: per-(window, seed)
    Python loops over the *same* fractional LP solutions, rounding
    uniforms, and trained GatMARL params.  This is both the correctness
    oracle and (driven per-instance) the host-loop path
    ``benchmarks/bench_baselines.py`` measures against.

    Returns ``results[policy][b][s] = (x, A, metrics)`` at true shapes.
    """
    from repro.core import baselines as BL

    u_cat, u_phi, u_cat_s, u_phi_s, u_perm, u_h, u_route = uniforms
    gat_params, gat_feats, gat_adj = gat
    results = {p: [] for p in OFFLINE_POLICIES}

    coc = offline_pipeline_host(stacked, x_frac, A_frac, u_cat, u_phi,
                                n_seeds=n_seeds)
    spr_fracs = stacked.unstack(spr3_frac["x"], spr3_frac["A"])
    for i, inst in enumerate(stacked.insts):
        N, U = inst.N, inst.U
        results["cocar"].append([
            (x, A, info["metrics"]) for x, A, info in coc[i]])

        xs_f, As_f = spr_fracs[i]
        xs, As = BL.spr3_from_fractional(
            inst, xs_f, As_f, u_cat_s[i, :, :N], u_phi_s[i, :, :N, :U])
        results["spr3"].append([
            (xs[s], As[s], MET.window_metrics(inst, xs[s], As[s]))
            for s in range(n_seeds)])

        xg, Ag = BL.greedy(inst)
        mg = MET.window_metrics(inst, xg, Ag)
        results["greedy"].append([(xg, Ag, mg)] * n_seeds)

        per_rand = []
        for s in range(n_seeds):
            xr, Ar = BL.random_from_uniforms(
                inst, u_perm[i, s, :N], u_h[i, s, :N], u_route[i, s, :U])
            per_rand.append((xr, Ar, MET.window_metrics(inst, xr, Ar)))
        results["random"].append(per_rand)

        params_i = {k: v[i] for k, v in gat_params.items()}
        xm, Am = BL.gat_rollout_host(inst, params_i, feats=gat_feats[i],
                                     adj=gat_adj[i])
        mm = MET.window_metrics(inst, xm, Am)
        results["gatmarl"].append([(xm, Am, mm)] * n_seeds)
    return results


def export_cache_plans(out, stacked: StackedWindows, seed_idx: int = 0):
    """Slice a ``policy_grid_device`` output into per-policy, per-window
    decision arrays at true (unpadded) shapes — the control-plane export
    the serving bridge (``repro.serving.plan.plan_from_offline``)
    consumes.

    Returns ``{policy: [{"x": (N, M, H+1), "A": (N, U, H),
    "metrics": {...}} per window]}`` for one rounding seed — the actual
    integral caching/routing decisions each policy committed to, never a
    hand-constructed residency profile.
    """
    plans = {}
    for p in OFFLINE_POLICIES:
        per_window = []
        for i, inst in enumerate(stacked.insts):
            per_window.append({
                "x": np.asarray(out[p]["x"][i, seed_idx, :inst.N]),
                "A": np.asarray(out[p]["A"][i, seed_idx,
                                            :inst.N, :inst.U]),
                "metrics": {k: float(v[i, seed_idx])
                            for k, v in out[p]["metrics"].items()}})
        plans[p] = per_window
    return plans


def improvement_ratio(metrics_by_policy, key: str = "avg_precision"):
    """The paper's headline number (Sec. VII-B): grid-mean CoCaR ``key``
    over the best baseline's.  ``metrics_by_policy[p]`` is any array of
    per-(window, seed) values."""
    means = {p: float(np.mean(np.asarray(v, dtype=np.float64)))
             for p, v in metrics_by_policy.items()}
    best_val = max(v for p, v in means.items() if p != "cocar")
    best = next(p for p, v in means.items()
                if p != "cocar" and v == best_val)
    return {"ratio": means["cocar"] / max(best_val, 1e-12),
            "best_baseline": best, "means": means}


def _unstack_device(stacked: StackedWindows, out, n_seeds: int):
    """Slice the padded device pipeline outputs back into the
    ``results[b][s] = (x, A, info)`` shape of the host reference.  When
    the dispatch carried the diagnostics tap, each info dict gains the
    window's ``lp_diag``: the sampled curves plus their host summary
    (curves are per-window, so every seed shares the same record)."""
    results = []
    for i, inst in enumerate(stacked.insts):
        lp_diag = None
        if "lp_diag" in out:
            curves = {k: np.asarray(v[i]) for k, v in out["lp_diag"].items()}
            lp_diag = {**curves, "summary": lp_diag_summary(curves)}
        per_seed = []
        for s in range(n_seeds):
            info = {"lp_obj": float(out["lp_obj"][i]),
                    "obj": float(out["trial_objs"][i, s,
                                                   out["best_t"][i, s]]),
                    "best_t": int(out["best_t"][i, s]),
                    "trial_objs": out["trial_objs"][i, s],
                    "metrics": {k: float(v[i, s])
                                for k, v in out["metrics"].items()}}
            if lp_diag is not None:
                info["lp_diag"] = lp_diag
            per_seed.append((out["x"][i, s, :inst.N],
                             out["A"][i, s, :inst.N, :inst.U], info))
        results.append(per_seed)
    return results


def cocar_grid(insts, seed: int = 0, pdhg_iters: int = 4000,
               best_of: int = 8, n_seeds: int = 1, devices: int = None,
               chunk_size: int = 0, max_buckets: int = 1,
               diagnostics: bool = False):
    """CoCaR over a grid of independent windows × rounding seeds: the
    fused LP → rounding → repair → metrics pipeline through the
    ``repro.scale`` grid executor.  ``devices=None`` runs it on one
    device; ``devices=k`` partitions the grid across a k-device host
    mesh (decision-identical — see ``repro.scale.executor``).
    ``chunk_size``/``max_buckets`` tune the executor's streaming chunk
    and size-bucket count (the default ``max_buckets=1`` is the classic
    one-padded-shape dispatch).  ``diagnostics`` threads the jit-safe
    solver tap through.  Returns ``results[b][s] = (x, A, info)``."""
    from repro.scale import GridSpec, run_grid

    spec = GridSpec(
        kind="offline", insts=list(insts), seed=seed, n_seeds=n_seeds,
        best_of=best_of, pdhg_iters=pdhg_iters, devices=devices,
        chunk_size=chunk_size, max_buckets=max_buckets,
        diagnostics=diagnostics)
    return run_grid(spec).results


def cocar_windows_batched(insts, seed: int = 0, pdhg_iters: int = 4000,
                          best_of: int = 8):
    """CoCaR over a stack of independent windows (scenario-grid variants,
    seeds, parallel traces) — one rounding seed per window, aligned with
    ``insts``.  Returns a list of (x, A, info) triples.

    Instances may differ in N and U (padded inside ``stack_instances``)
    but must share the catalog shape (M, H).
    """
    grid = cocar_grid(insts, seed=seed, pdhg_iters=pdhg_iters,
                      best_of=best_of, n_seeds=1)
    return [per_seed[0] for per_seed in grid]


def lr_window(inst: JDCRInstance):
    """The LR upper bound (fractional optimum, paper's 'LR')."""
    _, _, obj = LP.solve_lp_scipy(inst)
    return obj


def run_offline(cfg: MECConfig, algo: str = "cocar", solver: str = "scipy",
                seed: int = 0, scenario: Scenario = None):
    """Runs `algo` over cfg.n_windows windows; returns aggregate metrics.

    algo in {cocar, lr, greedy, random, spr3, gatmarl}.
    """
    from repro.core import baselines as BL

    sc = scenario or Scenario(cfg)
    x_prev = sc.empty_cache()
    results, lr_objs = [], []
    for w in range(cfg.n_windows):
        inst = sc.instance(w, x_prev)
        if algo == "cocar":
            x, A, _ = cocar_window(inst, seed=seed * 1000 + w, solver=solver)
        elif algo == "lr":
            lr_objs.append(lr_window(inst) / inst.U)
            # LR is an upper bound, not a deployable policy: carry greedy
            # caching forward so later windows stay comparable
            x, A, _ = cocar_window(inst, seed=seed * 1000 + w, solver=solver)
        elif algo == "greedy":
            x, A = BL.greedy(inst)
        elif algo == "random":
            x, A = BL.random_policy(inst, seed=seed * 1000 + w)
        elif algo == "spr3":
            x, A = BL.spr3(inst, seed=seed * 1000 + w)
        elif algo == "gatmarl":
            x, A = BL.gatmarl(inst, seed=seed)
        else:
            raise ValueError(algo)
        results.append(MET.window_metrics(inst, x, A))
        x_prev = x
    agg = MET.aggregate(results)
    if algo == "lr":
        agg["lr_bound"] = float(np.mean(lr_objs))
    return agg
