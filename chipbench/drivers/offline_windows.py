"""Offline CoCaR windows decided back to back, as an operator runs it.

Each step draws the next window's requests from the traffic, builds the
window's ``JDCRInstance`` with the cache state chained from the last
decision (span ``build``), and decides it with ``cocar_grid([inst])``
(span ``pipeline``) at the configuration's PDHG iterations and rounding
trials, every other option at the program's default.  ``decide_ms`` is the
window's wall time over the decisions it completed.

The check redoes a sample of the completed windows, drawn from the seed,
with the plain reference (``reference/offline.py``), from the same
requests, cache state and rounding seed, and compares the LP objective,
every caching and routing decision (against the reference's trials tied
for the best), and the decision's feasibility.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from chipbench import generator as G

#: the MECConfig fields a configuration file sets
_MEC_KEYS = ("n_bs", "n_users", "n_models", "window_s", "zipf",
             "mem_capacity_mb", "compute_gflops", "wireless_mbps",
             "wired_mbps", "hop_latency_s", "er_prob", "data_mb", "ddl_s")


@dataclass
class State:
    scenario: object
    traffic: object
    topo_seed: int
    x_prev: np.ndarray
    decided: list = field(default_factory=list)
    attempted: int = 0
    failed: int = 0


def _instance(sc, m_u, home, s_u, x_prev):
    """The window's JDCR instance: the scenario's deployment arrays with
    the drawn requests, as ``Scenario.instance`` builds it."""
    from repro.core.jdcr import JDCRInstance

    cfg = sc.cfg
    U = len(m_u)
    wired = np.where(np.isinf(sc.wired), 1e12, sc.wired)
    return JDCRInstance(
        sizes=sc.sizes, prec=sc.prec, flops=sc.flops, loadD=sc.loadD,
        R=sc.R, C=sc.C, phi=sc.phi, wired=wired, lam=sc.lam, m_u=m_u,
        d_u=np.full(U, cfg.data_mb), ddl=np.full(U, cfg.ddl_s), s_u=s_u,
        home=home, x_prev=np.asarray(x_prev, dtype=np.float64))


def _decide(sc, cfg: dict, traffic, x_prev, seed: int, ctx=None):
    from repro.core.cocar import cocar_grid

    m_u, home, s_u = traffic.next()
    kw = dict(seed=seed, pdhg_iters=int(cfg["pdhg_iters"]),
              best_of=int(cfg["best_of"]))
    if ctx is None:
        inst = _instance(sc, m_u, home, s_u, x_prev)
        return cocar_grid([inst], **kw)[0][0]
    with ctx.span("build"):
        inst = _instance(sc, m_u, home, s_u, x_prev)
    with ctx.span("pipeline"):
        x, A, info = cocar_grid([inst], **kw)[0][0]
    return (m_u, home, s_u), x, A, info


def setup(ctx) -> State:
    import time

    t = time.perf_counter()
    from repro.core import cocar  # noqa: F401
    from repro.mec.scenario import MECConfig, Scenario

    ctx.phase("program_import", time.perf_counter() - t)
    cfg = ctx.config
    topo_seed = ctx.sub_seed("topology")
    sc = Scenario(MECConfig(**{k: cfg[k] for k in _MEC_KEYS},
                            seed=topo_seed))
    # warm the one shape every window has, on a window of its own
    t = time.perf_counter()
    warm = G.ZipfWindows(ctx.traffic, cfg, ctx.sub_seed("warm-up"))
    _decide(sc, cfg, warm, sc.empty_cache(), ctx.sub_seed("warm-up"))
    ctx.phase("warm_window", time.perf_counter() - t)
    return State(scenario=sc, topo_seed=topo_seed, x_prev=sc.empty_cache(),
                 traffic=G.ZipfWindows(ctx.traffic, cfg,
                                       ctx.sub_seed("traffic")))


def window(ctx, st: State):
    import time

    steps = []
    while time.perf_counter() < ctx.deadline:
        t, c = time.perf_counter(), time.thread_time()
        k = len(st.decided)
        seed = ctx.sub_seed(f"round:{k}")
        st.attempted += 1
        reqs, x, A, info = _decide(st.scenario, ctx.config, st.traffic,
                                   st.x_prev, seed, ctx)
        st.decided.append({"seed": seed, "reqs": reqs, "x_prev": st.x_prev,
                           "x": x, "A": A, "lp_obj": info["lp_obj"],
                           "best_t": info["best_t"],
                           "trial_objs": info["trial_objs"],
                           "precision": info["metrics"]["avg_precision"]})
        st.x_prev = x
        steps.append((time.perf_counter() - t, t - ctx.window_t0,
                      time.thread_time() - c))
    ctx.count("decisions", len(st.decided))
    # the slowest whole steps, traffic draw included: where in the window
    # they fell and how much of each the thread spent on its own CPU
    ctx.info["slowest_steps"] = [
        {"wall_s": w, "at_s": a, "thread_cpu_s": c}
        for w, a, c in sorted(steps)[-3:]]
    ctx.info["mean_precision"] = float(np.mean(
        [d["precision"] for d in st.decided]))


def end_to_end(ctx, st: State) -> dict:
    return {"decide_ms": 1e3 * ctx.window_seconds / len(st.decided)}


def free(st: State):
    pass


def sample(ctx, n_done: int) -> list:
    """The windows the check redoes: ``check_windows`` of them, drawn
    from the seed, the first always among them."""
    k = min(int(ctx.traffic["check_windows"]), n_done)
    rng = np.random.default_rng(ctx.sub_seed("check"))
    rest = rng.choice(np.arange(1, n_done), size=k - 1, replace=False) \
        if k > 1 else []
    return [0] + sorted(int(i) for i in rest)


def compare(cfg: dict, topo_seed: int, d: dict, dtype=np.float64,
            diffs: list = None) -> dict:
    """One window redone by the reference (in ``dtype``) against what the
    program decided: the LP objective's relative gap, the number of
    caching and routing entries that differ from the nearest of the
    reference's trials tied for the best routed precision, and the
    program decision's excess over the constraints.  Trials whose
    routed precisions differ by under ``objective_tie_rel`` of the best
    are tied: which of them wins rests on the last bit of a sum, and each
    is the window's decision.  Where entries differ and ``diffs`` is
    given, what each differing entry was is appended to it."""
    from chipbench.reference import offline as R

    w = R.Window(cfg, topo_seed, *d["reqs"], d["x_prev"])
    out, lp_obj = R.trials(w, d["seed"], int(cfg["pdhg_iters"]),
                           int(cfg["best_of"]), dtype)
    best = max(v for v, _, _ in out)
    tie = float(cfg["objective_tie_rel"]) * abs(best)
    tied = [t for t, (v, _, _) in enumerate(out) if v >= best - tie]
    n_diff = {t: int(np.sum(out[t][1] != d["x"])
                     + np.sum(out[t][2] != d["A"])) for t in tied}
    t_near = min(tied, key=lambda t: n_diff[t])
    if n_diff[tied[0]] and diffs is not None:
        # not the reference's own choice (the first of the tied trials):
        # say why, even where another tied trial matches
        diffs.append(dict(_describe(w, out, t_near, d),
                          differing_by_tied_trial=n_diff))
    return {"lp_obj_rel_gap": abs(d["lp_obj"] - lp_obj) / abs(lp_obj),
            "decisions_differing": n_diff[t_near],
            "infeasibility": R.infeasibility(w, d["x"], d["A"])}


def _describe(w, out, t, d) -> dict:
    """Every trial's routed precision by the reference and by the
    program, the differing caching rows against the reference's trial
    ``t``, and the routes of the users whose routing differs, with each
    such user's latency and load budgets."""
    _, x, A = out[t]
    rows = sorted({(int(n), int(m)) for n, m, _ in np.argwhere(x != d["x"])})
    users = sorted({int(u) for _, u, _ in np.argwhere(A != d["A"])})
    return {"window_seed": d["seed"], "reference_trial": t,
            "reference_trials": [float(v) for v, _, _ in out],
            "program_trial": d.get("best_t"),
            "program_trials": [float(v) for v in d.get("trial_objs", [])],
            "caching": [{"bs": n, "model": m,
                         "program": int(np.argmax(d["x"][n, m])),
                         "reference": int(np.argmax(x[n, m]))}
                        for n, m in rows],
            "routes": [{"user": u, "model": int(w.m_u[u]),
                        "program": np.argwhere(d["A"][:, u] > 0).tolist(),
                        "reference": np.argwhere(A[:, u] > 0).tolist(),
                        "latency": w.T[:, u].tolist(),
                        "load": w.L[:, u].tolist(),
                        "ddl": float(w.ddl[u]), "s_u": float(w.s_u[u])}
                       for u in users[:8]]}


def check(ctx, st: State) -> list:
    cfg = ctx.config
    worst, diffs = {}, []
    for i in sample(ctx, len(st.decided)):
        for k, v in compare(cfg, st.topo_seed, st.decided[i],
                            diffs=diffs).items():
            worst[k] = max(worst.get(k, 0.0), v)
    if diffs:
        ctx.info["decision_diffs"] = diffs
    return [{"name": k, "value": float(v), "limit": cfg["limits"][k]}
            for k, v in worst.items()]


def control(ctx, st: State) -> dict:
    """The control's readings: on the same sampled windows, the
    reference computed in float32 in the program's place, against the
    reference in float64."""
    from chipbench.reference import offline as R

    cfg = ctx.config
    worst = {}
    for i in sample(ctx, len(st.decided)):
        d = dict(st.decided[i])
        w = R.Window(cfg, st.topo_seed, *d["reqs"], d["x_prev"])
        x, A, lp_obj = R.decide(w, d["seed"], int(cfg["pdhg_iters"]),
                                int(cfg["best_of"]), np.float32)
        d.update(x=x, A=A, lp_obj=lp_obj)
        for k, v in compare(cfg, st.topo_seed, d).items():
            worst[k] = max(worst.get(k, 0.0), v)
    return worst
