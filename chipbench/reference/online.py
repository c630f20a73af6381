"""Plain reference of CoCaR-OL (arXiv:2511.03159 Sec. VI, Alg. 2) over a
run of slots, in NumPy at a stated float dtype.

Each slot, as the paper states it:

* the routine update (Eqs. 35-37): every BS spends its cloud budget
  ``W_n * slot_s`` on its download queue in (model, submodel) order, and
  each component that finishes switches the cache to that submodel;
* the serving state of the slot: cached level, download in flight and its
  target, as routing sees them;
* QoE (Eq. 40) of the best routing target of each (home BS, model), its
  sum over the slot's requests (Eq. 41);
* the request history of the last ``dT_past`` slots (Eq. 45);
* ``rounds`` adjustments, each at the BS the decision stream names: every
  candidate (model, submodel) of that BS within the action space, fitted
  by the greedy multi-choice knapsack over the other models in order of
  demand, valued by its discounted expected gain over ``dT_future``
  slots (Eqs. 46-47); the best candidate is taken if it gains, a shrink
  at once (Eq. 49), an enlargement by queueing its components (Eq. 48).

The deployment (catalog, topology) comes from ``reference/offline.py``'s
copy, from the configuration and its seed; the counts and the decision
stream are the run's inputs, as a served model's check takes its tokens.
It imports nothing of the system under test.  Sums that decide a
threshold are folded left to right in a stated order, so that two runs
from equal numbers decide alike.
"""
from __future__ import annotations

import numpy as np

from chipbench.reference.offline import catalog, hop_counts


class Deployment:
    """The online scenario's arrays, from the configuration, the online
    parameters of the mix, and the topology seed."""

    def __init__(self, cfg: dict, mix: dict, topo_seed: int, dtype=np.float64):
        f = lambda a: np.asarray(a, dtype)
        N, M = cfg["n_bs"], cfg["n_models"]
        sizes, prec, gflops, _ = catalog(
            cfg, topo_seed + cfg["catalog"]["seed_offset"])
        d = cfg["data_mb"]
        self.sizes, self.prec = f(sizes), f(prec)
        self.flops = f(gflops / d)                         # per data unit
        hops = hop_counts(N, cfg["er_prob"], topo_seed)
        phi = np.full(N, cfg["wireless_mbps"] / 8.0)
        lam = cfg["hop_latency_s"] * (2.0 + 2.0 * hops)
        self.C = f(np.full(N, float(cfg["compute_gflops"])))
        self.R = f(np.full(N, float(cfg["mem_capacity_mb"])))
        self.W = f(np.full(N, mix["cloud_mbps"] / 8.0))
        # Eq. 39: upload, one wired leg unless home, propagation
        self.comm = f((d / phi)[:, None] + np.where(
            np.eye(N, dtype=bool), 0.0, d / (cfg["wired_mbps"] / 8.0)) + lam)
        # Eq. 40's normaliser: the least latency any request can see
        self.theta = f(d / phi.min() + 2 * cfg["hop_latency_s"]
                       + (gflops[:, 1] / d * d / cfg["compute_gflops"]).min())
        self.dt = dtype
        self.d, self.ddl = f(d), f(cfg["ddl_s"])
        self.n_users = f(cfg["n_users"])
        self.slot_s = f(mix["slot_s"])
        self.alpha, self.gamma = f(mix["alpha"]), f(mix["gamma"])
        self.dT_future = f(mix["dT_future"])
        self.dT_past = int(mix["dT_past"])
        self.partition = bool(mix["partition"])
        self.N, self.M, self.H = N, M, sizes.shape[1] - 1


class State:
    def __init__(self, dep: Deployment):
        N, M, H = dep.N, dep.M, dep.H
        self.lvl = np.zeros((N, M), np.int64)          # cached submodel
        self.O = np.zeros((N, M, H), dep.dt)           # MB left per component
        self.target = np.zeros((N, M), np.int64)       # download target
        self.hist = np.zeros((dep.dT_past, N, M), dep.dt)


def seq_sum(rows, mask=None):
    """Rows summed left to right (``mask`` rows add an exact zero)."""
    acc = rows[0] * (mask[0] if mask is not None else 1.0)
    for i in range(1, rows.shape[0]):
        acc = acc + rows[i] * (mask[i] if mask is not None else 1.0)
    return acc


def routine_update(dep: Deployment, st: State):
    """Eqs. 35-37."""
    N, M, H = dep.N, dep.M, dep.H
    budget = dep.W * dep.slot_s
    O = st.O.reshape(N, M * H)
    before = np.cumsum(O, axis=1) - O
    take = np.clip(budget[:, None] - before, 0.0, O)
    new = O - take
    finished = (O > 0) & (new <= 1e-12)
    new = np.where(finished, 0.0, new)
    fin = finished.reshape(N, M, H)
    top = (H - 1) - np.argmax(fin[:, :, ::-1], axis=-1)
    st.lvl = np.where(fin.any(-1), top + 1, st.lvl)
    st.O = new.reshape(N, M, H).astype(dep.dt)


def best_qoe(dep: Deployment, lvl):
    """Eqs. 39-41: per (home BS, model), the best QoE over the BSs that
    could serve it at cache state ``lvl``."""
    ms = np.arange(dep.M)
    P = dep.prec[ms[None, :], lvl]                      # (N, M)
    infer = dep.flops[ms[None, :], lvl] * dep.d / dep.C[:, None]
    lat = dep.comm[:, :, None] + infer[None]           # (home, target, M)
    q = P[None] * np.clip(1.0 - (lat - dep.theta) * dep.alpha, 0.0, None)
    q = np.where((P[None] > 0) & (lat <= dep.ddl), q, 0.0)
    return q.max(axis=1)


def slot_gain(dep: Deployment, freq, lvl):
    """Expected one-slot QoE under cache state ``lvl`` (Eq. 46)."""
    return (freq * best_qoe(dep, lvl)).sum() * dep.n_users


def adjust(dep: Deployment, st: State, n: int):
    """Alg. 2 lines 15-21 at BS ``n``."""
    M, H = dep.M, dep.H
    sizes = dep.sizes
    ms = np.arange(M)
    freq = st.hist.sum(0) / max(st.hist.sum(), 1.0)    # Eq. 45
    fM = seq_sum(freq)
    cur = st.lvl[n]
    dl = st.O[n].sum(-1) > 0
    dlbudget = dep.W[n] * dep.slot_s
    cand_m = np.repeat(ms, H + 1)
    cand_h = np.tile(np.arange(H + 1), M)
    cur_k = cur[cand_m]
    shrink, enlarge = cand_h < cur_k, cand_h > cur_k
    # the action space: enlargements up to the first whose components
    # overrun one slot's download budget
    if dep.partition:
        enl_ok = sizes[cand_m, np.maximum(cand_h - 1, 0)] \
            - sizes[cand_m, cur_k] <= dlbudget
    else:
        enl_ok = cand_h == H
    valid = (~dl[cand_m]) & (cand_h >= 1) & (shrink | (enlarge & enl_ok))

    # the greedy knapsack: models downloading keep their target's room,
    # the others take, in order of demand, the largest level that fits
    need = sizes[cand_m, cand_h]
    locked = dl[None, :] & (ms[None, :] != cand_m[:, None])
    locked_sz = sizes[ms, st.target[n]]
    budget = dep.R[n] - need
    for m2 in range(M):
        budget = budget - np.where(locked[:, m2], locked_sz[m2], 0.0)
    feasible = budget >= 0
    choice = np.where(locked, cur[None, :], 0)
    for m2 in np.argsort(-fM, kind="stable"):
        free = (m2 != cand_m) & (~dl[m2])
        if dep.partition:
            fits = sizes[m2][None, :] <= budget[:, None] + 1e-9
            h2 = np.clip(np.minimum(cur[m2], fits.sum(-1) - 1), 0, None)
        else:
            h2 = np.where((cur[m2] == H) & (sizes[m2, H] <= budget + 1e-9),
                          H, 0)
        h2 = np.where(free, h2, choice[:, m2])
        budget = budget - np.where(free, sizes[m2, h2], 0.0)
        choice[:, m2] = h2

    # Eqs. 46-47: the matched-horizon discounted gain of each candidate
    K = len(cand_m)
    hyp, dur = choice.copy(), choice.copy()
    hyp[np.arange(K), cand_m] = cand_h
    dur[np.arange(K), cand_m] = cur_k
    if dep.partition:
        delta = sizes[cand_m, cand_h] - sizes[cand_m, cur_k]
    else:
        delta = sizes[cand_m, cand_h]
    delay = np.where(enlarge, np.ceil(delta / dlbudget), 0.0)
    g_cur = slot_gain(dep, freq, st.lvl)

    def g(rows):
        out = np.empty(K, dep.dt)
        for k in range(K):
            lvl = st.lvl.copy()
            lvl[n] = rows[k]
            out[k] = slot_gain(dep, freq, lvl)
        return out

    gam = dep.gamma
    geo = lambda D: gam * (1 - gam ** D) / (1 - gam)
    gain = geo(delay) * (g(dur) - g_cur) \
        + gam ** delay * geo(dep.dT_future) * (g(hyp) - g_cur)
    gains = np.where(valid & feasible, gain, -np.inf)
    k = int(np.argmax(gains))
    if not gains[k] > 1e-9:
        return
    mb, hb = cand_m[k], cand_h[k]
    curb = cur[mb]
    row = choice[k].copy()
    row[mb] = min(hb, curb)                    # a shrink now, else later
    st.lvl[n] = row
    if hb > curb:                              # Eq. 48: queue the components
        h = np.arange(1, H + 1)
        if dep.partition:
            st.O[n, mb] = np.where((h > curb) & (h <= hb),
                                   sizes[mb, 1:] - sizes[mb, :-1], 0.0)
        else:
            st.O[n, mb] = np.where(h == hb, sizes[mb, hb], 0.0)
        st.target[n, mb] = hb


def run(dep: Deployment, counts, adjust_ns, skip_routine: bool = False):
    """Slots from a fresh state: ``counts`` (T, N, M) requests, ``adjust_ns``
    (T, rounds) the BSs adjusted.  Returns per slot the serving state
    ``(lvl, dl, target)`` and the QoE (Eq. 41).  ``skip_routine`` leaves
    out the routine update, the planted fault of the check."""
    st = State(dep)
    T = len(counts)
    lvl = np.zeros((T, dep.N, dep.M), np.int64)
    dl = np.zeros((T, dep.N, dep.M), bool)
    target = np.zeros((T, dep.N, dep.M), np.int64)
    qoe = np.zeros(T, dep.dt)
    for t in range(T):
        c = np.asarray(counts[t], dep.dt)
        if not skip_routine:
            routine_update(dep, st)
        lvl[t], dl[t], target[t] = st.lvl, st.O.sum(-1) > 0, st.target
        qoe[t] = (c * best_qoe(dep, st.lvl)).sum()
        st.hist = np.concatenate([st.hist[1:], c[None]])
        for n in adjust_ns[t]:
            adjust(dep, st, int(n))
    return {"lvl": lvl, "dl": dl, "target": target}, qoe
