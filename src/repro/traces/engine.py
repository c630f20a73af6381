"""Device-resident online engine: CoCaR-OL (Alg. 2) and the online
baselines as one ``jax.lax.scan`` over slots.

The NumPy ``repro.core.online.OnlineSim`` runs one (scenario, policy) at a
time in Python — a per-slot state machine.  This module re-implements the
same math as a pure function of a state pytree:

  * :class:`OnlineState` — ``lvl (N, M)`` cached-submodel index (the
    one-hot ``X`` of Eqs. 35–37 stored as its argmax), ``O (N, M, H)``
    remaining download MB per Δ component, ``target (N, M)`` in-flight
    download targets, ``hist (P, N, M)`` request-count ring buffer
    (the ΔT^P window of Eq. 45);
  * ``_routine_update`` — the download state machine (Eqs. 35–37);
  * ``_qoe_best`` — QoE (Eq. 40) + argmax-QoE routing (Eq. 41);
  * ``_adjust_bs`` — expected-future-gain caching (Eqs. 45–47) with the
    greedy multi-choice knapsack fit and immediate shrink (Eq. 49),
    evaluated for the whole (M, H+1) candidate grid at once;
  * ``_lfu_step`` / ``_random_step`` — the online baselines.

Every slot consumes only aggregated tensors (the workload's per-slot
``(N, M)`` request counts and the pre-drawn
:class:`~repro.traces.generators.DecisionStream`), so a whole run is ONE
``lax.scan`` dispatch, and ``run_online_grid`` vmaps it across
(scenario × workload × seed × policy) — a 64-element online grid is a
single XLA program instead of 64 Python slot loops.  ``run_workload``
streams a :class:`~repro.traces.workloads.Workload` through the scan in
bounded chunks, carrying ``OnlineState`` across chunk boundaries: the
scan is a strict fold over slots, so chunking cannot change any decision,
and peak memory is O(chunk) — a million-user Poisson workload runs
without ever materializing a ``(T, U)`` or even full ``(T, N, M)``
tensor.

Numerics: the engine mirrors ``OnlineSim`` op-for-op (same stable sort
orders, same thresholds) and runs in float64 (``jax.enable_x64``), so
per-slot QoE and final cache state match the NumPy engine to ~1e-12 —
asserted in ``tests/test_traces.py``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

from repro.traces.generators import DecisionStream, check_trace, default_stream

POLICIES = ("cocar-ol", "lfu", "lfu-mad", "random")
LFU_MAD_DECAY = 0.8              # matches online._freq_weighted


class OnlineParams(NamedTuple):
    """Static per-scenario arrays (all float64/int — vmappable leading
    batch axis in ``run_online_grid``)."""
    sizes: object                # (M, H+1) MB
    prec: object                 # (M, H+1)
    flops: object                # (M, H+1) GFLOP per MB (c_h)
    comm: object                 # (N, N) comm latency home->target (Eq. 39)
    C: object                    # (N,) GFLOPS
    R: object                    # (N,) MB
    W: object                    # (N,) MB/s cloud->BS
    adj1: object                 # (N, N) 1.0 where hops <= 1 (LFU pooling)
    theta: object                # () Eq. 40 normalizer
    ddl: object                  # ()
    alpha: object                # ()
    gamma: object                # ()
    dT_future: object            # ()
    data_mb: object              # ()
    slot_s: object               # ()
    n_users: object              # () QoE scale of Eq. 46
    partition: object            # () bool — dynamic-DNN switching enabled


class OnlineState(NamedTuple):
    lvl: object                  # (N, M) int32 cached submodel index
    O: object                    # (N, M, H) remaining download MB
    target: object               # (N, M) int32 download target
    hist: object                 # (P, N, M) request-count ring buffer


def make_params(cfg, ocfg, sc=None) -> OnlineParams:
    """Extract the engine's arrays from a scenario (host numpy, float64)."""
    from repro.mec.scenario import Scenario

    sc = sc or Scenario(cfg)
    N = cfg.n_bs
    d = cfg.data_mb
    comm = (d / sc.phi)[:, None] \
        + np.where(np.eye(N, dtype=bool), 0.0, d / (cfg.wired_mbps / 8.0)) \
        + sc.lam
    infer_min = (sc.flops[:, 1] * d / sc.C.max()).min()
    theta = d / sc.phi.min() + 2 * cfg.hop_latency_s + infer_min
    return OnlineParams(
        sizes=np.asarray(sc.sizes, np.float64),
        prec=np.asarray(sc.prec, np.float64),
        flops=np.asarray(sc.flops, np.float64),
        comm=np.asarray(comm, np.float64),
        C=np.asarray(sc.C, np.float64),
        R=np.asarray(sc.R, np.float64),
        W=np.full(N, cfg.cloud_mbps / 8.0),
        adj1=(sc.hops <= 1).astype(np.float64),
        theta=np.float64(theta),
        ddl=np.float64(cfg.ddl_s),
        alpha=np.float64(ocfg.alpha),
        gamma=np.float64(ocfg.gamma),
        dT_future=np.float64(ocfg.dT_future),
        data_mb=np.float64(d),
        slot_s=np.float64(ocfg.slot_s),
        n_users=np.float64(cfg.n_users),
        partition=np.bool_(ocfg.partition))


def init_state(params: OnlineParams, dT_past: int) -> OnlineState:
    M, Hp1 = np.shape(params.sizes)[-2:]
    N = np.shape(params.R)[-1]
    return OnlineState(
        lvl=np.zeros((N, M), np.int32),
        O=np.zeros((N, M, Hp1 - 1), np.float64),
        target=np.zeros((N, M), np.int32),
        hist=np.zeros((dT_past, N, M), np.float64))


# ---------------------------------------------------------------------------
# kernels (pure jnp functions of (params, state))
# ---------------------------------------------------------------------------

def _routine_update(p, st):
    """Eqs. 35–37: each BS spends W_n·Δt on its (m, h)-ordered download
    queue; every finished Δ switches the cache to h+1."""
    import jax.numpy as jnp

    N, M, H = st.O.shape
    budget = p.W * p.slot_s
    O = st.O.reshape(N, M * H)
    before = jnp.cumsum(O, axis=1) - O
    take = jnp.clip(budget[:, None] - before, 0.0, O)
    O_new = O - take
    finished = (O > 0) & (O_new <= 1e-12)
    O_new = jnp.where(finished, 0.0, O_new)
    fin = finished.reshape(N, M, H)
    done = fin.any(-1)
    h_top = (H - 1) - jnp.argmax(fin[:, :, ::-1], axis=-1)
    lvl = jnp.where(done, h_top.astype(jnp.int32) + 1, st.lvl)
    return st._replace(lvl=lvl, O=O_new.reshape(N, M, H))


def _qoe_best(p, lvl):
    """Eqs. 39–41: per-(home BS, model) best QoE over routing targets."""
    import jax.numpy as jnp

    M = lvl.shape[-1]
    ms = jnp.arange(M)
    P = p.prec[ms[None, :], lvl]                       # (N, M)
    c = p.flops[ms[None, :], lvl]
    infer = c * p.data_mb / p.C[:, None]               # (N_tgt, M)
    lat = p.comm[:, :, None] + infer[None]             # (Nh, Nt, M)
    q = P[None] * jnp.clip(1.0 - (lat - p.theta) * p.alpha, 0.0, None)
    q = jnp.where((P[None] > 0) & (lat <= p.ddl), q, 0.0)
    return q.max(axis=1)                               # (Nh, M)


def _seq_sum(rows, mask=None):
    """Left-to-right sequential accumulation (static Python loop).

    Decision-critical sums are accumulated in exactly the order the NumPy
    engine uses — identical f64 values added in identical order are
    bit-exact, so threshold/sort decisions cannot diverge between the two
    engines.  ``mask`` rows contribute an exact +0.0 (a no-op), matching
    NumPy's boolean-subset sums.
    """
    acc = rows[0] * (mask[0] if mask is not None else 1.0)
    for i in range(1, rows.shape[0]):
        acc = acc + rows[i] * (mask[i] if mask is not None else 1.0)
    return acc


def _freq(st):
    """Eq. 45: request proportions over the ΔT^P window."""
    import jax.numpy as jnp

    tot = st.hist.sum()
    return st.hist.sum(0) / jnp.maximum(tot, 1.0)


def _slot_qoe(p, freqNM, lvl):
    """Expected one-slot total QoE under cache state ``lvl`` (Eq. 46)."""
    return (freqNM * _qoe_best(p, lvl)).sum() * p.n_users


def _adjust_bs(p, st, n):
    """Alg. 2 lines 15–21 at BS n: evaluate the whole (M, H+1) candidate
    grid — action-space filter, knapsack fit, expected future gain — and
    apply the argmax candidate (first-wins on ties, like the Python loop).
    """
    import jax
    import jax.numpy as jnp

    N, M = st.lvl.shape
    H = st.O.shape[-1]
    K = M * (H + 1)
    ms = jnp.arange(M)
    freqNM = _freq(st)
    fM = _seq_sum(freqNM)                              # (M,) demand weight
    cur = st.lvl[n]                                    # (M,)
    dl = st.O[n].sum(-1) > 0                           # (M,)
    dlbudget = p.W[n] * p.slot_s

    cand_m = jnp.repeat(ms, H + 1)                     # (K,)
    cand_h = jnp.tile(jnp.arange(H + 1), M).astype(jnp.int32)
    cur_k = cur[cand_m]
    shrink = cand_h < cur_k
    enlarge = cand_h > cur_k
    # Sec. VI-B action space: enlargements up to (and incl.) the first
    # whose cumulative Δ overruns one slot budget
    sz_prev = p.sizes[cand_m, jnp.maximum(cand_h - 1, 0)]
    enl_ok = jnp.where(p.partition,
                       sz_prev - p.sizes[cand_m, cur_k] <= dlbudget,
                       cand_h == H)
    valid = (~dl[cand_m]) & (cand_h >= 1) & (shrink | (enlarge & enl_ok))

    # ---- _fit: greedy multi-choice knapsack, all candidates at once ----
    need = p.sizes[cand_m, cand_h]
    locked = dl[None, :] & (ms[None, :] != cand_m[:, None])      # (K, M)
    locked_sz = p.sizes[ms, st.target[n]]
    budget0 = p.R[n] - need
    for m2 in range(M):                                # sequential, like _fit
        budget0 = budget0 - jnp.where(locked[:, m2], locked_sz[m2], 0.0)
    feasible = budget0 >= 0
    order = jnp.argsort(-fM)                           # stable, high f first

    choice0 = jnp.where(locked, cur[None, :], 0)

    def knap_step(carry, m2):
        budget, choice = carry
        is_free = (m2 != cand_m) & (~dl[m2])           # (K,)
        cur2 = cur[m2]
        fits = p.sizes[m2][None, :] <= budget[:, None] + 1e-9
        h2_part = jnp.clip(jnp.minimum(cur2, fits.sum(-1) - 1), 0)
        h2_full = jnp.where((cur2 == H) & (p.sizes[m2, H] <= budget + 1e-9),
                            H, 0)
        h2 = jnp.where(p.partition, h2_part, h2_full)
        h2 = jnp.where(is_free, h2, choice[:, m2]).astype(jnp.int32)
        budget = budget - jnp.where(is_free, p.sizes[m2, h2], 0.0)
        return (budget, choice.at[:, m2].set(h2)), None

    (_, choice), _ = jax.lax.scan(knap_step, (budget0, choice0), order)

    k_idx = jnp.arange(K)
    lvl_hyp = choice.at[k_idx, cand_m].set(cand_h)     # (K, M) rows at n
    lvl_dur = choice.at[k_idx, cand_m].set(cur_k)      # upgrade pending

    # Eq. 46/47 matched-horizon discounted gain
    delta = jnp.where(p.partition,
                      p.sizes[cand_m, cand_h] - p.sizes[cand_m, cur_k],
                      p.sizes[cand_m, cand_h])
    delay = jnp.where(enlarge, jnp.ceil(delta / dlbudget), 0.0)

    full = jnp.broadcast_to(st.lvl, (K, N, M))
    g_cur = _slot_qoe(p, freqNM, st.lvl)
    g_hyp = jax.vmap(lambda L: _slot_qoe(p, freqNM, L))(
        full.at[k_idx, n].set(lvl_hyp))
    g_dur = jax.vmap(lambda L: _slot_qoe(p, freqNM, L))(
        full.at[k_idx, n].set(lvl_dur))
    gam = p.gamma
    geo = lambda D: gam * (1 - gam ** D) / (1 - gam)   # sum_{k=1}^D gam^k
    gain = geo(delay) * (g_dur - g_cur) \
        + gam ** delay * geo(p.dT_future) * (g_hyp - g_cur)

    gains = jnp.where(valid & feasible, gain, -jnp.inf)
    k_best = jnp.argmax(gains)
    act = gains[k_best] > 1e-9
    mb, hb = cand_m[k_best], cand_h[k_best]
    curb = cur[mb]
    row = choice[k_best].at[mb].set(jnp.where(hb < curb, hb, curb))
    lvl = st.lvl.at[n].set(jnp.where(act, row, st.lvl[n]))

    enl = act & (hb > curb)                            # Eq. 48 downloads
    h_axis = jnp.arange(1, H + 1)
    Orow = jnp.where(p.partition,
                     jnp.where((h_axis > curb) & (h_axis <= hb),
                               p.sizes[mb, 1:] - p.sizes[mb, :-1], 0.0),
                     jnp.where(h_axis == hb, p.sizes[mb, hb], 0.0))
    O = st.O.at[n, mb].set(jnp.where(enl, Orow, st.O[n, mb]))
    target = st.target.at[n, mb].set(
        jnp.where(enl, hb, st.target[n, mb]))
    return st._replace(lvl=lvl, O=O, target=target)


def _lfu_step(p, st, n, mad):
    """LFU / LFU-MAD at BS n: enlarge the most frequent non-downloading
    model (pooling 1-hop neighbour demand), shrink least-frequent to fit."""
    import jax
    import jax.numpy as jnp

    N, M = st.lvl.shape
    H = st.O.shape[-1]
    P = st.hist.shape[0]
    ms = jnp.arange(M)
    if mad:
        w = LFU_MAD_DECAY ** (P - 1 - jnp.arange(P))
        fW = _seq_sum(st.hist * w[:, None, None])
    else:
        fW = st.hist.sum(0)                            # integer-exact
    f = _seq_sum(fW, mask=p.adj1[n])                   # (M,) 1-hop pooling
    order = jnp.argsort(-f)                            # stable
    dl = st.O[n].sum(-1) > 0
    free_in_order = ~dl[order]
    exists = free_in_order.any()
    top = order[jnp.argmax(free_in_order)]
    cur = st.lvl[n, top]
    tgt = jnp.where(p.partition, jnp.minimum(cur + 1, H), H)
    act0 = exists & (tgt != cur)
    used = _seq_sum(p.sizes[ms, st.lvl[n]]) + jnp.maximum(
        p.sizes[top, tgt] - p.sizes[top, cur] * (cur > 0), 0.0)

    def shrink_step(carry, m2):
        used, lvln = carry
        c2 = lvln[m2]
        cond = act0 & (used > p.R[n]) & (m2 != top) & (c2 > 0)
        new2 = jnp.where(p.partition, c2 - 1, 0)
        used = used - jnp.where(cond,
                                p.sizes[m2, c2] - p.sizes[m2, new2], 0.0)
        return (used, lvln.at[m2].set(jnp.where(cond, new2, c2))), None

    (used, lvln), _ = jax.lax.scan(shrink_step, (used, st.lvl[n]),
                                   jnp.argsort(f))
    fin = act0 & (used <= p.R[n])
    delta = p.sizes[top, tgt] - jnp.where(p.partition & (cur > 0),
                                          p.sizes[top, cur], 0.0)
    O = st.O.at[n, top, tgt - 1].set(
        jnp.where(fin, jnp.maximum(delta, 0.0), st.O[n, top, tgt - 1]))
    target = st.target.at[n, top].set(
        jnp.where(fin, tgt.astype(jnp.int32), st.target[n, top]))
    return st._replace(lvl=st.lvl.at[n].set(lvln), O=O, target=target)


def _random_step(p, st, n, u_m, perm, u_shr):
    """Random baseline at BS n, driven by the pre-drawn uniforms."""
    import jax
    import jax.numpy as jnp

    N, M = st.lvl.shape
    H = st.O.shape[-1]
    ms = jnp.arange(M)
    dl = st.O[n].sum(-1) > 0
    free = ~dl
    n_free = free.sum()
    idx = jnp.minimum((u_m * n_free).astype(jnp.int32),
                      jnp.maximum(n_free - 1, 0))
    m = jnp.argmax((jnp.cumsum(free) - 1 == idx) & free)
    cur = st.lvl[n, m]
    tgt = jnp.where(p.partition, jnp.minimum(cur + 1, H), H)
    act0 = (n_free > 0) & (tgt != cur)
    used = _seq_sum(p.sizes[ms, st.lvl[n]]) + p.sizes[m, tgt] \
        - jnp.where(cur > 0, p.sizes[m, cur], 0.0)

    def shrink_step(carry, m2):
        used, lvln = carry
        c2 = lvln[m2]
        cond = act0 & (m2 != m) & (used > p.R[n]) & (c2 > 0)
        new2 = jnp.where(p.partition,
                         jnp.minimum((u_shr[m2] * c2).astype(jnp.int32),
                                     jnp.maximum(c2 - 1, 0)), 0)
        used = used - jnp.where(cond,
                                p.sizes[m2, c2] - p.sizes[m2, new2], 0.0)
        return (used, lvln.at[m2].set(jnp.where(cond, new2, c2))), None

    (used, lvln), _ = jax.lax.scan(shrink_step, (used, st.lvl[n]), perm)
    fin = act0 & (used <= p.R[n])
    delta = p.sizes[m, tgt] - jnp.where(p.partition & (cur > 0),
                                        p.sizes[m, cur], 0.0)
    O = st.O.at[n, m, tgt - 1].set(
        jnp.where(fin, jnp.maximum(delta, 0.0), st.O[n, m, tgt - 1]))
    target = st.target.at[n, m].set(
        jnp.where(fin, tgt.astype(jnp.int32), st.target[n, m]))
    return st._replace(lvl=st.lvl.at[n].set(lvln), O=O, target=target)


# ---------------------------------------------------------------------------
# the scan
# ---------------------------------------------------------------------------

def _slot_step(p, policy, st, xs, diagnostics: bool = False,
               record_states: bool = False):
    """One slot: downloads -> routing/QoE -> history push -> policy.

    With ``diagnostics`` (static) the emission grows a per-slot telemetry
    dict — cache-hit rate, downloads in flight, evictions this slot,
    cached MB — computed purely from values the step already produces, so
    the state trajectory (and every decision) is bit-identical either
    way; off, the dict is empty and compiles out entirely.

    With ``record_states`` (static) the emission additionally carries
    the slot's *serving* cache state — ``(lvl, dl, target)`` right after
    the download update, i.e. exactly the state Eq. 41 routes against
    this slot.  This is the per-slot export the serving bridge
    (``repro.serving.plan``) turns into residency schedules; a submodel
    mid-download (``dl`` true) is still at its pre-download ``lvl``, so
    it can never be exposed as resident at its target.  Decision-inert,
    like diagnostics: off, nothing extra is compiled or carried."""
    import jax
    import jax.numpy as jnp

    counts, ns, u_model, perms, u_shrink = xs
    st = _routine_update(p, st)
    rec = ()
    if record_states:
        rec = (st.lvl, st.O.sum(-1) > 0, st.target)
    best = _qoe_best(p, st.lvl)
    qoe = (counts * best).sum()
    hits = (counts * (best > 0)).sum()
    st = st._replace(hist=jnp.concatenate([st.hist[1:], counts[None]]))
    lvl_before = st.lvl
    rounds = ns.shape[0]
    js = jnp.arange(rounds)

    def rounds_scan(step_fn):
        def run(s):
            return jax.lax.scan(lambda s_, j: (step_fn(s_, j), None),
                                s, js)[0]
        return run

    st = jax.lax.switch(policy, [
        rounds_scan(lambda s, j: _adjust_bs(p, s, ns[j])),
        rounds_scan(lambda s, j: _lfu_step(p, s, ns[j], mad=False)),
        rounds_scan(lambda s, j: _lfu_step(p, s, ns[j], mad=True)),
        rounds_scan(lambda s, j: _random_step(p, s, ns[j], u_model[j],
                                              perms[j], u_shrink[j])),
    ], st)
    diag = {}
    if diagnostics:
        ms = jnp.arange(st.lvl.shape[-1])
        diag = {
            "hit_rate": hits / jnp.maximum(counts.sum(), 1.0),
            "dl_in_flight": (st.O.sum(-1) > 0).sum(),
            "evictions": (st.lvl < lvl_before).sum(),
            "cache_mb": p.sizes[ms[None, :], st.lvl].sum(),
        }
    return st, (qoe, hits, diag, rec)


def _scan_run(p, st0, counts, ns, u_model, perms, u_shrink, policy,
              diagnostics: bool = False, record_states: bool = False):
    """Whole-trace scan.  Always returns ``(stF, qoe, hits, diag, rec)``;
    ``diag`` is a dict of per-slot curves when ``diagnostics`` (static)
    is on and ``rec`` the per-slot ``(lvl, dl, target)`` trajectory when
    ``record_states`` is on — otherwise both are empty (nothing extra
    compiled or carried)."""
    import jax

    def step(st, xs):
        return _slot_step(p, policy, st, xs, diagnostics=diagnostics,
                          record_states=record_states)

    stF, (qoe, hits, diag, rec) = jax.lax.scan(
        step, st0, (counts, ns, u_model, perms, u_shrink))
    return stF, qoe, hits, diag, rec


@functools.cache
def _compiled(diagnostics: bool = False, record_states: bool = False):
    """The single-scenario scan (``run_scan``).  Grid runs go through the
    ``repro.scale`` executor, which jits its own vmapped ``_scan_run``."""
    import jax

    from repro.obs.tracing import register_jit

    fn = functools.partial(_scan_run, diagnostics=diagnostics,
                           record_states=record_states)
    return register_jit(f"online:scan:diag={int(bool(diagnostics))}"
                        f":rec={int(bool(record_states))}",
                        jax.jit(fn))


def _policy_id(algo: str) -> int:
    try:
        return POLICIES.index(algo)
    except ValueError:
        raise ValueError(f"unknown online policy {algo!r}; "
                         f"one of {POLICIES}")


def run_scan(params: OnlineParams, counts, stream: DecisionStream,
             algo: str = "cocar-ol", dT_past: int = 10,
             diagnostics: bool = False, record_states: bool = False):
    """One scenario through the compiled scan.  Returns the summary dict of
    ``run_online`` plus per-slot arrays and the final state — and, with
    ``diagnostics``, the engine's per-slot telemetry curves (decision-
    inert: same compiled step math, extra emissions only), and, with
    ``record_states``, the per-slot serving cache states under
    ``"states"`` (the serving bridge's input)."""
    import jax

    st0 = init_state(params, dT_past)
    with jax.enable_x64(True):
        stF, qoe, hits, diag, rec = _compiled(
            bool(diagnostics), bool(record_states))(
            params, st0, np.asarray(counts, np.float64),
            stream.adjust_ns, stream.u_model, stream.perms, stream.u_shrink,
            _policy_id(algo))
    # pull to host BEFORE reducing: np.sum on a device array would
    # re-enter jnp outside the x64 context and downcast to f32
    qoe, hits = np.asarray(qoe), np.asarray(hits)
    total = float(np.asarray(counts).sum())
    out = {
        "avg_qoe": float(qoe.sum()) / max(total, 1.0),
        "hit_rate": float(hits.sum()) / max(total, 1.0),
        "slot_qoe": qoe,
        "slot_hits": hits,
        "final_state": OnlineState(*(np.asarray(x) for x in stF)),
    }
    if diagnostics:
        out["diagnostics"] = {k: np.asarray(v) for k, v in diag.items()}
    if record_states:
        out["states"] = {"lvl": np.asarray(rec[0]),
                         "dl": np.asarray(rec[1]),
                         "target": np.asarray(rec[2])}
    return out


def run_workload(params: OnlineParams, workload, stream: DecisionStream,
                 algo: str = "cocar-ol", dT_past: int = 10,
                 diagnostics: bool = False, chunk_slots: int = 0,
                 record_states: bool = False):
    """Stream a :class:`~repro.traces.workloads.Workload` through the
    compiled scan in bounded chunks.

    ``chunk_slots`` <= 0 defers to the workload's own preference (whole
    horizon for small exact families, a bounded default for streaming
    ones).  The ``OnlineState`` carry crosses chunk boundaries, so the
    slot trajectory — and every cache decision — is identical to the
    one-shot scan; at most two chunk lengths (full + tail) ever compile.
    Returns the ``run_scan`` summary dict.
    """
    import jax

    st = init_state(params, dT_past)
    fn = _compiled(bool(diagnostics), bool(record_states))
    pid = _policy_id(algo)
    qoes, hitss, diags, recs, total = [], [], [], [], 0.0
    with jax.enable_x64(True):
        for t0, t1, counts in workload.iter_chunks(chunk_slots):
            counts = np.asarray(counts, np.float64)
            total += float(counts.sum())
            st, qoe, hits, diag, rec = fn(
                params, st, counts, stream.adjust_ns[t0:t1],
                stream.u_model[t0:t1], stream.perms[t0:t1],
                stream.u_shrink[t0:t1], pid)
            qoes.append(np.asarray(qoe))
            hitss.append(np.asarray(hits))
            if diagnostics:
                diags.append({k: np.asarray(v) for k, v in diag.items()})
            if record_states:
                recs.append(tuple(np.asarray(r) for r in rec))
    qoe, hits = np.concatenate(qoes), np.concatenate(hitss)
    out = {
        "avg_qoe": float(qoe.sum()) / max(total, 1.0),
        "hit_rate": float(hits.sum()) / max(total, 1.0),
        "slot_qoe": qoe,
        "slot_hits": hits,
        "final_state": OnlineState(*(np.asarray(x) for x in st)),
    }
    if diagnostics:
        out["diagnostics"] = {
            k: np.concatenate([d[k] for d in diags]) for k in diags[0]}
    if record_states:
        out["states"] = {
            key: np.concatenate([r[i] for r in recs])
            for i, key in enumerate(("lvl", "dl", "target"))}
    return out


def grid_payloads(jobs, ocfg):
    """Per-job engine arrays for a grid run: the (params, counts, stream,
    policy id, request total) each scan consumes, derived exactly as
    ``run_online`` derives them (same default seeds and streams).

    This is the online grid's ingestion stage; the ``repro.scale``
    executor buckets the payloads by shape, stacks each bucket, and
    dispatches them sharded/chunked.
    """
    from dataclasses import replace

    from repro.traces.registry import default_trace
    from repro.traces.workloads import as_workload, check_workload

    payloads = []
    for j in jobs:
        seed = j.get("seed", 0)        # same default as run_online
        cfg = replace(j["cfg"], seed=seed)
        if j.get("workload") is not None:
            wl = check_workload(as_workload(j["workload"], cfg=cfg),
                                cfg, ocfg)
            counts = wl.counts()
        else:
            trace = j.get("trace") or default_trace(cfg, ocfg)
            check_trace(trace, cfg, ocfg)
            counts = trace.counts(cfg.n_bs, cfg.n_models)
        stream = j.get("stream") or default_stream(cfg, ocfg, seed)
        payloads.append({
            "params": make_params(cfg, ocfg),
            "counts": counts,
            "stream": stream,
            "policy": _policy_id(j["algo"]),
            "total": float(counts.sum()),
        })
    return payloads


def run_online_grid(jobs, ocfg, devices: int = None, chunk_size: int = 0,
                    diagnostics: bool = False):
    """Run many (cfg, trace, algo, seed) scenarios in one vmapped scan
    dispatch per shape bucket, via the ``repro.scale`` grid executor.

    ``jobs`` is a list of dicts with keys ``cfg`` (MECConfig), ``algo``
    (policy name), and optionally ``workload`` (anything ``as_workload``
    accepts) or ``trace`` (a Trace; the default workload when neither is
    given) and ``seed``.  Heterogeneous (n_bs, n_models, n_slots) grids
    are bucketed by shape — each bucket is one dispatch.
    ``devices=k`` partitions every bucket's batch across a k-device host
    mesh (``None``: one device, no mesh); ``chunk_size`` streams it in
    bounded chunks.  Returns one summary dict per job, in order.
    """
    from repro.scale import GridSpec, run_grid

    spec = GridSpec(kind="online", jobs=list(jobs), ocfg=ocfg,
                    devices=devices, chunk_size=chunk_size,
                    diagnostics=diagnostics)
    return run_grid(spec).results
