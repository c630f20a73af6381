"""Plain reference of one CoCaR window (arXiv:2511.03159 Alg. 1, Sec. V-D).

Written from the paper's equations and the deployment in the
configuration file, in NumPy, at a stated float dtype.  It imports
nothing of the system under test and takes nothing that the system has
made: the catalog comes from the configuration's Table II/III numbers,
the topology from its seed, the requests from the benchmark's traffic,
and the rounding uniforms from ``jax.random`` at the key the window's
seed names.  The one input taken from a run is the cache state the
window starts from, the previous decision, as a served model's check
takes the tokens that were served.

Stages, each as the paper states it:

* the instance: end-to-end latency (Eq. 15) and load latency (Eq. 16);
* the LP relaxation P1-LR, solved by diagonally preconditioned PDHG
  (Chambolle-Pock, tau_j = 1/sum_i |K_ij|, sigma_i = 1/sum_j |K_ij|) for
  the configured iteration count from x = 1/(H+1), A = 0, y = 0;
* Alg. 1 rounding against the pre-drawn uniforms, ``best_of`` trials;
* the Sec. V-D repair of each trial, and the trial with the highest
  routed precision (first one on a tie; :func:`trials` gives them all,
  so that a check can accept any trial tied for the best);
* the feasibility of a decision against constraints (1), (2), (12),
  (14), (15), (16).

Sums that decide a threshold are folded as a balanced tree of pairwise
adds, so that two decisions made from equal numbers agree bit for bit.
"""
from __future__ import annotations

import numpy as np

_EPS = 1e-9


def tree_sum(v, axis=-1):
    """Balanced pairwise sum over one axis, zero-padded to a power of two."""
    v = np.moveaxis(v, axis, -1)
    n = v.shape[-1]
    if n == 0:
        return np.zeros(v.shape[:-1], dtype=v.dtype)
    p = 1
    while p < n:
        p *= 2
    if p != n:
        v = np.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, p - n)])
    while p > 1:
        p //= 2
        v = v[..., :p] + v[..., p:2 * p]
    return v[..., 0]


# ---------------------------------------------------------------------------
# deployment: catalog and topology from the configuration
# ---------------------------------------------------------------------------

def catalog(cfg: dict, seed: int):
    """(sizes, prec, gflops, loadD) of the paper catalog: ViT's Table II
    submodels and Table III load times, model type 0 exact and types
    1..M-1 scaled by a factor drawn in [lo, hi), precisions jittered."""
    cat = cfg["catalog"]
    subs, load = cat["submodels"], np.asarray(cat["load_s"])
    M, H = cfg["n_models"], len(subs)
    rng = np.random.default_rng(seed)
    lo, hi = cat["size_factor"]
    factors = np.concatenate([[1.0], rng.uniform(lo, hi, M - 1)])
    sizes = np.zeros((M, H + 1))
    prec = np.zeros((M, H + 1))
    gflops = np.zeros((M, H + 1))
    loadD = np.zeros((M, H + 1, H + 1))
    for m, f in enumerate(factors):
        for j, sub in enumerate(subs):
            sizes[m, j + 1] = sub["memory_mb"] * f
            gflops[m, j + 1] = sub["gflops"] * f
            dp = rng.uniform(-cat["prec_jitter"], cat["prec_jitter"]) \
                if m else 0.0
            prec[m, j + 1] = min(sub["precision"] + dp, cat["prec_max"])
        loadD[m, :, 1:] = load * f
        loadD[m, 1:, 0] = 0.0
    return sizes, prec, gflops, loadD


def hop_counts(n: int, p: float, seed: int):
    """Erdos-Renyi topology, drawn again until connected; BFS hop counts."""
    rng = np.random.default_rng(seed)
    while True:
        adj = np.triu(rng.random((n, n)) < p, 1)
        adj = adj | adj.T
        hops = np.full((n, n), np.inf)
        for s in range(n):
            hops[s, s] = 0
            frontier, d = [s], 0
            while frontier:
                d += 1
                nxt = []
                for v in frontier:
                    for w in np.nonzero(adj[v])[0]:
                        if hops[s, w] == np.inf:
                            hops[s, w] = d
                            nxt.append(w)
                frontier = nxt
        if np.isfinite(hops).all():
            return hops.astype(int)


class Window:
    """One JDCR window: the deployment's arrays plus its requests."""

    def __init__(self, cfg: dict, topo_seed: int, m_u, home, s_u, x_prev):
        N, M = cfg["n_bs"], cfg["n_models"]
        self.sizes, self.prec, gflops, self.loadD = catalog(
            cfg, topo_seed + cfg["catalog"]["seed_offset"])
        self.flops = gflops / cfg["data_mb"]                # per data unit
        hops = hop_counts(N, cfg["er_prob"], topo_seed)
        phi = np.full(N, cfg["wireless_mbps"] / 8.0)
        wired = np.where(np.eye(N, dtype=bool), 1e12,
                         cfg["wired_mbps"] / 8.0)
        lam = cfg["hop_latency_s"] * (2.0 + 2.0 * hops)
        self.R = np.full(N, float(cfg["mem_capacity_mb"]))
        C = np.full(N, float(cfg["compute_gflops"]))
        self.m_u = np.asarray(m_u)
        self.home = np.asarray(home)
        self.s_u = np.asarray(s_u, np.float64)
        U = len(self.m_u)
        d_u = np.full(U, cfg["data_mb"])
        self.ddl = np.full(U, cfg["ddl_s"])
        self.x_prev = np.asarray(x_prev, np.float64)
        self.N, self.M, self.U = N, M, U
        self.H = self.sizes.shape[1] - 1
        self.onehot = np.zeros((U, M))
        self.onehot[np.arange(U), self.m_u] = 1.0
        self.prec_u = self.prec[self.m_u, 1:]                # (U, H)
        # Eq. 15: upload + wired hops + propagation + inference
        comm = (d_u / phi[self.home])[:, None] \
            + d_u[:, None] / wired[self.home, :] + lam[self.home, :]
        infer = self.flops[self.m_u, 1:][None] * d_u[None, :, None] \
            / C[:, None, None]
        self.T = comm.T[:, :, None] + infer                  # (N, U, H)
        # Eq. 16: load time of the requested submodel from the last state
        Tl = np.einsum("nmp,mph->nmh", self.x_prev, self.loadD)
        self.L = Tl[:, self.m_u, 1:]                         # (N, U, H)


# ---------------------------------------------------------------------------
# P1-LR by PDHG
# ---------------------------------------------------------------------------

def solve_lp(w: Window, iters: int, dtype=np.float64):
    """Fractional (x (N,M,H+1), A (N,U,H)) after ``iters`` PDHG steps,
    every array and every step in ``dtype``."""
    f = lambda a: np.asarray(a, dtype)
    sizes, prec_u, T, L = f(w.sizes), f(w.prec_u), f(w.T), f(w.L)
    R, ddl, s_u = f(w.R), f(w.ddl), f(w.s_u)
    N, U, H, M = w.N, w.U, w.H, w.M
    one = dtype(1.0)

    m_u = w.m_u
    members = [np.nonzero(m_u == m)[0] for m in range(M)]

    def K(x, A):
        return (x.sum(-1) - one,
                (x * sizes).sum(axis=(1, 2)) - R,
                A.sum(axis=(0, 2)) - one,
                (A * T).sum(axis=(0, 2)) - ddl,
                (A * L).sum(axis=(0, 2)) - s_u,
                A - x[:, m_u, 1:])

    def KT(y):
        y_eq, y_mem, y_route, y_lat, y_load, y_ax = y
        gx = np.zeros((N, M, H + 1), dtype)
        gx += y_eq[:, :, None]
        gx += y_mem[:, None, None] * sizes[None]
        for m, us in enumerate(members):
            gx[:, m, 1:] -= y_ax[:, us].sum(axis=1)
        gA = y_route[None, :, None] + y_ax \
            + y_lat[None, :, None] * T + y_load[None, :, None] * L
        return gx, gA

    floor = dtype(1e-9)
    rows = (np.full((N, M), H + 1, dtype), np.full(N, sizes.sum(), dtype),
            np.full(U, N * H, dtype), T.sum(axis=(0, 2)),
            L.sum(axis=(0, 2)), np.full((N, U, H), 2, dtype))
    sig = tuple(one / np.maximum(r, floor) for r in rows)
    cx = np.ones((N, M, H + 1), dtype) + sizes[None]
    cx[:, :, 1:] += np.bincount(w.m_u, minlength=M)[None, :, None]
    tau_x = one / np.maximum(cx, floor)
    tau_A = one / np.maximum(np.ones((N, U, H), dtype) + T + L + one, floor)

    x = np.full((N, M, H + 1), one / (H + 1), dtype)
    A = np.zeros((N, U, H), dtype)
    y = tuple(np.zeros_like(v) for v in K(x, A))
    for _ in range(iters):
        gx, gA = KT(y)
        x_new = np.clip(x - tau_x * gx, 0, 1).astype(dtype)
        A_new = np.clip(A - tau_A * (gA - prec_u[None]), 0, 1).astype(dtype)
        Ky = K(2 * x_new - x, 2 * A_new - A)
        y = tuple(yy + s * kk for yy, s, kk in zip(y, sig, Ky))
        y = (y[0],) + tuple(np.maximum(v, 0) for v in y[1:])
        x, A = x_new, A_new
    return x, A


def lp_objective(w: Window, A) -> float:
    return float(np.sum(np.asarray(A, np.float64) * w.prec_u[None]))


# ---------------------------------------------------------------------------
# Alg. 1 rounding and the Sec. V-D repair
# ---------------------------------------------------------------------------

def uniforms(seed: int, trials: int, N: int, M: int, U: int, H: int,
             dtype=np.float64):
    """The rounding uniforms of one window, drawn in ``dtype`` from
    ``seed``'s key: ``u_cat (T, N, M)`` and ``u_phi (T, N, U, H)``."""
    import jax

    with jax.enable_x64(True), jax.default_device(jax.devices("cpu")[0]):
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        u_cat = jax.random.uniform(k1, (1, trials, N, M), dtype=dtype)
        u_phi = jax.random.uniform(k2, (1, trials, N, U, H), dtype=dtype)
    return np.asarray(u_cat)[0], np.asarray(u_phi)[0]


def round_trials(x_f, A_f, onehot, u_cat, u_phi):
    """Alg. 1 for every trial: an inverse-CDF draw per (BS, model) and a
    Bernoulli draw A/x per route, in the uniforms' dtype; returns 0/1
    (T,N,M,H+1), (T,N,U,H)."""
    x_f = np.asarray(x_f, u_cat.dtype)
    A_f = np.asarray(A_f, u_cat.dtype)
    Hp1 = x_f.shape[-1]
    probs = np.clip(x_f, 0.0, 1.0)
    probs = probs / np.maximum(tree_sum(probs, -1), 1e-12)[..., None]
    cum = probs[..., 0]
    cat = np.zeros(u_cat.shape, np.int32)
    for k in range(Hp1 - 1):
        cat = cat + (u_cat >= cum).astype(np.int32)
        if k < Hp1 - 2:
            cum = cum + probs[..., k + 1]
    x_int = (cat[..., None] == np.arange(Hp1)).astype(np.float64)
    xa = np.einsum("nmh,um->nuh", x_f[:, :, 1:], onehot)
    phi = np.clip(np.where(xa > 1e-12, A_f / np.maximum(xa, 1e-12), 0.0),
                  0.0, 1.0)
    x_sel = np.einsum("tnmh,um->tnuh", x_int[..., 1:], onehot)
    A_int = np.where((x_sel > 0) & (u_phi < phi), 1.0, 0.0)
    return x_int, A_int


def _one_route(prec_u, A):
    """At most one route per user: the highest precision, the smallest
    (n, h) on a tie."""
    N, U, H = A.shape
    score = np.where(A > 0, np.broadcast_to(prec_u[None], A.shape), -np.inf)
    flat = np.moveaxis(score, 1, 0).reshape(U, N * H)
    k = np.argmax(flat, axis=1)
    served = (flat > -np.inf).any(axis=1)
    keep = (np.arange(N * H)[None, :] == k[:, None]) & served[:, None]
    return np.moveaxis(keep.reshape(U, N, H), 0, 1).astype(np.float64)


def repair(w: Window, x, A):
    """Sec. V-D: one route per user; while a BS overflows its memory,
    shrink (or evict) the cached submodel whose routed users gain least
    and move them down with it; drop routes that miss the deadline or
    the load time; then route each unserved user to the best cached
    submodel that meets both."""
    x = np.array(x, np.float64)
    A = _one_route(w.prec_u, np.array(A, np.float64))
    H, hh, ms = w.H, np.arange(w.H + 1), np.arange(w.M)
    for n in range(w.N):
        while True:
            used = tree_sum(tree_sum(np.where(x[n] > 0, w.sizes, 0.0)))
            cached = np.argmax(x[n], axis=-1)
            if used <= w.R[n] + _EPS or not (cached > 0).any():
                break
            cnt = np.einsum("um,uh->mh", w.onehot,
                            (A[n] > 0).astype(np.float64))
            benefit = w.prec[ms, cached] * cnt[ms, np.maximum(cached - 1, 0)]
            m_e = int(np.argmin(np.where(cached > 0, benefit, np.inf)))
            h = cached[m_e]
            slack = w.R[n] - (used - w.sizes[m_e, h])
            fits = (hh >= 1) & (hh < h) & (w.sizes[m_e] <= slack + _EPS)
            new_h = int(np.max(np.where(fits, hh, 0)))
            x[n, m_e] = 0.0
            x[n, m_e, new_h] = 1.0
            moved = (w.onehot[:, m_e] > 0) & (A[n, :, h - 1] > 0)
            A[n, moved, h - 1] = 0.0
            if new_h > 0:
                A[n, moved, new_h - 1] = 1.0
    A = np.where(np.einsum("nmh,um->nuh", x[:, :, 1:], w.onehot) > 0, A, 0.0)
    lat = tree_sum(tree_sum(np.where(A > 0, w.T, 0.0)), 0)
    load = tree_sum(tree_sum(np.where(A > 0, w.L, 0.0)), 0)
    A[:, (lat > w.ddl + _EPS) | (load > w.s_u + _EPS), :] = 0.0
    h_sel = np.argmax(x, axis=-1)[:, w.m_u]                  # (N, U)
    hm1 = np.maximum(h_sel - 1, 0)
    T_g = np.take_along_axis(w.T, hm1[:, :, None], axis=-1)[..., 0]
    L_g = np.take_along_axis(w.L, hm1[:, :, None], axis=-1)[..., 0]
    prec_g = w.prec_u[np.arange(w.U)[None, :], hm1]
    feas = (h_sel > 0) & (T_g <= w.ddl[None] + _EPS) \
        & (L_g <= w.s_u[None] + _EPS)
    n_best = np.argmax(np.where(feas, prec_g, -np.inf), axis=0)
    uu = np.nonzero(~(A > 0).any(axis=(0, 2)) & feas.any(axis=0))[0]
    A[n_best[uu], uu, h_sel[n_best[uu], uu] - 1] = 1.0
    return x, A


def routed_precision(prec_u, A):
    """Sum of the precisions of the routes taken, as a tree of adds over
    selected values."""
    v = np.where(A > 0, prec_u[None], 0.0)
    return tree_sum(tree_sum(tree_sum(v)))


def trials(w: Window, seed: int, iters: int, best_of: int,
           dtype=np.float64):
    """The whole window but the choice of trial: LP, ``best_of``
    roundings and the repair of each, the LP and the rounding draws in
    ``dtype``, the repair in float64.  Returns (every repaired trial's
    (routed precision, x, A) in draw order, lp_obj)."""
    x_f, A_f = solve_lp(w, iters, dtype)
    u_cat, u_phi = uniforms(seed, best_of, w.N, w.M, w.U, w.H, dtype)
    x_r, A_r = round_trials(x_f, A_f, w.onehot, u_cat, u_phi)
    out = []
    for t in range(best_of):
        x, A = repair(w, x_r[t], A_r[t])
        out.append((routed_precision(w.prec_u, A), x, A))
    return out, lp_objective(w, A_f)


def decide(w: Window, seed: int, iters: int, best_of: int,
           dtype=np.float64):
    """The window's decision: the trial with the highest routed
    precision, the first one on a tie.  Returns (x, A, lp_obj)."""
    out, lp_obj = trials(w, seed, iters, best_of, dtype)
    best = max(range(len(out)), key=lambda t: (out[t][0], -t))
    return out[best][1], out[best][2], lp_obj


def infeasibility(w: Window, x, A) -> float:
    """The largest excess over constraints (1), (2), (12), (14), (15),
    (16) of an integral decision, 0 when it meets them all."""
    x = np.asarray(x, np.float64)
    A = np.asarray(A, np.float64)
    excess = [
        np.max(np.abs(x.sum(-1) - 1.0)),                      # (1)
        np.max(np.sum(x * w.sizes[None], axis=(1, 2)) - w.R),  # (2)
        np.max(A.sum(axis=(0, 2)) - 1.0),                     # (12)
        np.max(A - x[:, w.m_u, 1:]),                          # (14)
        np.max((A * w.T).sum(axis=(0, 2)) - w.ddl),           # (15)
        np.max((A * w.L).sum(axis=(0, 2)) - w.s_u),           # (16)
        np.max(np.abs(A * (1 - A))),                          # integral
    ]
    return float(max(0.0, max(excess)))
