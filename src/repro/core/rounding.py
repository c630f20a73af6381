"""CoCaR randomized rounding (paper Alg. 1) + feasibility repair (Sec. V-D)
— twice: a NumPy reference and a pure-JAX device kernel, engineered to make
*identical decisions* (PR-2 style, see ``docs/algorithms.md`` Sec. 7).

Rounding (Alg. 1) is a deterministic function of pre-drawn uniforms:

  * caching: inverse-CDF multinoulli per (BS, model type) with
    probabilities x†[n,m,:] (Lines 2–6) against ``u_cat``,
  * routing: Bernoulli φ̃ with success probability A†/x† (Lines 7–13)
    against ``u_phi``; Ã = x̃ · φ̃.

``draw_rounding_uniforms`` draws *all* ``best_of × seeds`` trials as two
batched RNG ops; both engines then consume the same numbers, so every
threshold crossing — and therefore every rounded decision — coincides.

Repair (Sec. V-D "Extension to Practice") turns a rounded draw into a
feasible integral solution:

  1. route dedupe: at most one route per user, highest precision wins;
  2. memory violations: repeatedly shrink the least-beneficial cached
     submodel (or evict to h0), redirecting now-unserved users;
  3. latency / load violations: send the offending routes to the cloud;
  4. route re-repair (routing-only, constraint-safe): re-route unserved
     users to the best feasible cached replica.

``repair`` is the NumPy oracle (per-BS Python loop, closest to the paper's
pseudocode); ``repair_device`` is the same state machine as masked argmax /
select ops with the eviction loop as a bounded ``lax.while_loop`` (each
eviction strictly lowers some cached level, so M·H iterations reach the
fixpoint).  Decision-critical sums go through ``jdcr.tree_sum`` on both
paths and comparisons select (never multiply) precision values, so the two
implementations agree on the *decision* level, not merely to a tolerance —
asserted in ``tests/test_offline_batched.py`` and
``benchmarks/bench_offline.py``.
"""
from __future__ import annotations

import numpy as np

from repro.core.jdcr import JDCRInstance, tree_sum

_EPS = 1e-9


# ---------------------------------------------------------------------------
# Alg. 1 rounding — deterministic in pre-drawn uniforms
# ---------------------------------------------------------------------------

def draw_rounding_uniforms(key, n_trials, N, M, U, H, batch=None):
    """All the randomness of ``n_trials`` Alg. 1 draws, as two float64
    uniform tensors (one categorical inverse-CDF, one Bernoulli):
    ``u_cat (T, N, M)`` and ``u_phi (T, N, U, H)`` — with a leading
    ``batch`` axis when given.  Both engines consume these *same* numbers.
    """
    import jax

    shape = (n_trials, N, M) if batch is None else (batch, n_trials, N, M)
    shape_phi = shape[:-2] + (N, U, H) if batch is None \
        else (batch, n_trials, N, U, H)
    with jax.enable_x64(True):
        k = jax.random.PRNGKey(key) if isinstance(key, int) else key
        k1, k2 = jax.random.split(k)
        u_cat = jax.random.uniform(k1, shape, dtype=np.float64)
        u_phi = jax.random.uniform(k2, shape_phi, dtype=np.float64)
    return np.asarray(u_cat), np.asarray(u_phi)


def round_from_uniforms(x_frac, A_frac, onehot_mu, u_cat, u_phi):
    """Alg. 1 as a pure function of the fractional LP solution and the
    pre-drawn uniforms.  Works on NumPy *and* JAX arrays (same ops, same
    float results); ``u_cat``/``u_phi`` may carry leading trial axes that
    broadcast against the unbatched (N, M, H+1) / (N, U, H) solution.

    Returns 0/1-valued (x̃ ..., N, M, H+1) and (Ã ..., N, U, H).
    """
    xp = np if isinstance(x_frac, np.ndarray) else _jnp()
    Hp1 = x_frac.shape[-1]
    probs = xp.clip(x_frac, 0.0, 1.0)
    den = xp.maximum(tree_sum(probs, -1), 1e-12)
    probs = probs / den[..., None]
    # inverse CDF: smallest k with u < Σ_{j<=k} p_j; partial sums are
    # accumulated left-to-right (static loop) identically on both engines
    cum = probs[..., 0]
    cat = xp.zeros(u_cat.shape, dtype=xp.int32)
    for k in range(Hp1 - 1):
        cat = cat + (u_cat >= cum).astype(xp.int32)
        if k < Hp1 - 2:
            cum = cum + probs[..., k + 1]
    x_int = (cat[..., None] == xp.arange(Hp1)).astype(xp.float64)
    # Bernoulli routing: P[φ=1] = A†/x† at the user's model row
    xa = xp.einsum("nmh,um->nuh", x_frac[..., :, :, 1:], onehot_mu)
    phi_p = xp.where(xa > 1e-12, A_frac / xp.maximum(xa, 1e-12), 0.0)
    phi_p = xp.clip(phi_p, 0.0, 1.0)
    x_sel = xp.einsum("...nmh,um->...nuh", x_int[..., :, :, 1:], onehot_mu)
    A_int = xp.where((x_sel > 0) & (u_phi < phi_p), 1.0, 0.0)
    return x_int, A_int


def round_solution_batch(inst: JDCRInstance, x_frac, A_frac, key,
                         n_trials: int = 1):
    """Alg. 1, ``n_trials`` iid draws from one batched RNG dispatch.

    Returns integer (x̃ (T,N,M,H+1), Ã (T,N,U,H)) as numpy arrays.
    """
    N, M, H, U = inst.N, inst.M, inst.H, inst.U
    u_cat, u_phi = draw_rounding_uniforms(key, max(n_trials, 1), N, M, U, H)
    x_int, A_int = round_from_uniforms(
        np.asarray(x_frac, np.float64), np.asarray(A_frac, np.float64),
        inst.onehot_mu(), u_cat, u_phi)
    return x_int, A_int


def round_solution(inst: JDCRInstance, x_frac, A_frac, key):
    """Vectorized Alg. 1. Returns integer (x̃ (N,M,H+1), Ã (N,U,H))."""
    x_int, A_int = round_solution_batch(inst, x_frac, A_frac, key, n_trials=1)
    return x_int[0], A_int[0]


def _jnp():
    import jax.numpy as jnp
    return jnp


# ---------------------------------------------------------------------------
# Sec. V-D repair — NumPy reference (the oracle)
# ---------------------------------------------------------------------------

def _dedupe_routes(prec_u, A):
    """Keep at most one route per user — highest precision; exact ties go
    to the smallest (n, h) in row-major order (both engines argmax-first)."""
    N, U, H = A.shape
    score = np.where(A > 0, np.broadcast_to(prec_u[None], A.shape), -np.inf)
    flat = np.moveaxis(score, 1, 0).reshape(U, N * H)
    k = np.argmax(flat, axis=1)
    served = (flat > -np.inf).any(axis=1)
    keep = (np.arange(N * H)[None, :] == k[:, None]) & served[:, None]
    return np.moveaxis(keep.reshape(U, N, H), 0, 1).astype(np.float64)


def repair(inst: JDCRInstance, x, A):
    """Sec. V-D heuristic: convert rounded (x̃, Ã) into feasible (x, y).

    The reference implementation: per-BS Python eviction loop, mirroring
    the paper's prose.  Decision sums use ``tree_sum`` so the device kernel
    (``repair_device``) reproduces every eviction/kick-out choice exactly.
    """
    x = np.array(x, dtype=np.float64)
    A = np.array(A, dtype=np.float64)
    N, M, H = inst.N, inst.M, inst.H
    prec_u = inst.prec[inst.m_u, 1:]                        # (U,H)
    onehot_mu = inst.onehot_mu()

    A = _dedupe_routes(prec_u, A)

    # ---- 1. memory -----------------------------------------------------
    hh = np.arange(H + 1)
    ms = np.arange(M)
    for n in range(N):
        while True:
            used = tree_sum(tree_sum(
                np.where(x[n] > 0, inst.sizes, 0.0), -1), -1)
            cached = np.argmax(x[n], axis=-1)               # (M,)
            if used <= inst.R[n] + _EPS or not (cached > 0).any():
                break
            # benefit of each cached (m, h>0): Σ routed users' precision.
            # Every routed user of model m contributes the same catalog
            # p_{m,h}, so this is an exact integer count times one float —
            # bit-identical on host and device whatever the summation order
            cnt = np.einsum("um,uh->mh", onehot_mu,
                            (A[n] > 0).astype(np.float64))
            hm1 = np.maximum(cached - 1, 0)
            benefit = inst.prec[ms, cached] * cnt[ms, hm1]
            m_e = int(np.argmin(np.where(cached > 0, benefit, np.inf)))
            h = cached[m_e]
            # largest smaller submodel that fits the freed budget
            slack = inst.R[n] - (used - inst.sizes[m_e, h])
            fits = (hh >= 1) & (hh < h) & (inst.sizes[m_e] <= slack + _EPS)
            new_h = int(np.max(np.where(fits, hh, 0)))
            x[n, m_e] = 0.0
            x[n, m_e, new_h] = 1.0
            moved = (onehot_mu[:, m_e] > 0) & (A[n, :, h - 1] > 0)
            A[n, moved, h - 1] = 0.0
            if new_h > 0:                  # downgraded service survives
                A[n, moved, new_h - 1] = 1.0

    # routes must point at cached submodels
    x_sel = np.einsum("nmh,um->nuh", x[:, :, 1:], onehot_mu)
    A = np.where(x_sel > 0, A, 0.0)

    # ---- 2. latency & load ---------------------------------------------
    T = inst.e2e_latency()
    L = inst.load_latency()
    lat_u = tree_sum(tree_sum(np.where(A > 0, T, 0.0), -1), 0)
    load_u = tree_sum(tree_sum(np.where(A > 0, L, 0.0), -1), 0)
    bad = (lat_u > inst.ddl + _EPS) | (load_u > inst.s_u + _EPS)
    A[:, bad, :] = 0.0

    # ---- 3. route repair (beyond Sec. V-D, routing-only and constraint-
    # safe): unserved users whose model IS cached at some feasible BS are
    # routed there instead of the cloud (contention-free model: adding a
    # route violates nothing)
    cached_h = np.argmax(x, axis=-1)                        # (N, M)
    h_sel = cached_h[:, inst.m_u]                           # (N, U)
    hm1 = np.maximum(h_sel - 1, 0)
    T_g = np.take_along_axis(T, hm1[:, :, None], axis=-1)[..., 0]
    L_g = np.take_along_axis(L, hm1[:, :, None], axis=-1)[..., 0]
    prec_g = prec_u[np.arange(inst.U)[None, :], hm1]        # (N, U)
    feas = (h_sel > 0) & (T_g <= inst.ddl[None] + _EPS) \
        & (L_g <= inst.s_u[None] + _EPS)
    score = np.where(feas, prec_g, -np.inf)
    n_best = np.argmax(score, axis=0)                       # (U,)
    unserved = ~(A > 0).any(axis=(0, 2))
    assign = unserved & feas.any(axis=0)
    uu = np.nonzero(assign)[0]
    A[n_best[uu], uu, h_sel[n_best[uu], uu] - 1] = 1.0
    return x, A


# ---------------------------------------------------------------------------
# Sec. V-D repair — device kernel (pure jnp, one padded window)
# ---------------------------------------------------------------------------

def _dedupe_device(prec_u, A):
    jnp = _jnp()
    N, U, H = A.shape
    score = jnp.where(A > 0, jnp.broadcast_to(prec_u[None], A.shape),
                      -jnp.inf)
    flat = jnp.moveaxis(score, 1, 0).reshape(U, N * H)
    k = jnp.argmax(flat, axis=1)
    served = (flat > -jnp.inf).any(axis=1)
    keep = (jnp.arange(N * H)[None, :] == k[:, None]) & served[:, None]
    return jnp.moveaxis(keep.reshape(U, N, H), 0, 1).astype(jnp.float64)


def _mem_repair_bs(sizes, prec, onehot_mu, R_n, x_n, A_n):
    """The per-BS eviction loop at one base station, as a bounded
    ``lax.while_loop`` (each eviction strictly lowers some cached level,
    so at most M·H iterations reach the fixpoint; under ``vmap`` the
    batched loop runs only as long as the slowest station still
    overflows — finished stations' updates are masked to exact no-ops)."""
    import jax
    jnp = _jnp()

    M, Hp1 = x_n.shape
    H = Hp1 - 1
    hh = jnp.arange(Hp1)
    ms = jnp.arange(M)

    def overflowing(carry):
        x_n, _, it = carry
        used = tree_sum(tree_sum(jnp.where(x_n > 0, sizes, 0.0), -1), -1)
        cached = jnp.argmax(x_n, axis=-1)
        return (used > R_n + _EPS) & (cached > 0).any() & (it < M * H)

    def body(carry):
        x_n, A_n, it = carry
        used = tree_sum(tree_sum(jnp.where(x_n > 0, sizes, 0.0), -1), -1)
        cached = jnp.argmax(x_n, axis=-1)                   # (M,)
        act = (used > R_n + _EPS) & (cached > 0).any()
        # exact routed-user count per (m, h) times the catalog precision —
        # see the NumPy reference for why this matches Σ user precision
        cnt = jnp.einsum("um,uh->mh", onehot_mu,
                         (A_n > 0).astype(jnp.float64))
        hm1 = jnp.maximum(cached - 1, 0)
        benefit = prec[ms, cached] * cnt[ms, hm1]
        m_e = jnp.argmin(jnp.where(cached > 0, benefit, jnp.inf))
        h = cached[m_e]
        slack = R_n - (used - sizes[m_e, h])
        fits = (hh >= 1) & (hh < h) & (sizes[m_e] <= slack + _EPS)
        new_h = jnp.max(jnp.where(fits, hh, 0))
        new_row = (hh == new_h).astype(x_n.dtype)
        x_n = jnp.where(act, x_n.at[m_e].set(new_row), x_n)
        hs = jnp.maximum(h, 1)
        moved = act & (onehot_mu[:, m_e] > 0) & (A_n[:, hs - 1] > 0)
        col = jnp.arange(H)[None, :]
        A_n = jnp.where(moved[:, None] & (col == hs - 1), 0.0, A_n)
        A_n = jnp.where((moved & (new_h > 0))[:, None]
                        & (col == jnp.maximum(new_h, 1) - 1), 1.0, A_n)
        return x_n, A_n, it + 1

    x_n, A_n, _ = jax.lax.while_loop(overflowing, body, (x_n, A_n, 0))
    return x_n, A_n


def repair_device(data, x, A):
    """``repair`` as a pure jnp function of one padded window.

    ``data`` is a :class:`~repro.core.lp.PDHGData`; padded base stations
    (``bs_mask`` 0) and padded users (zero ``onehot_mu`` row) are excluded
    from the re-route step, and their zero routes / capacities make every
    other stage inert for them.  Decisions match the NumPy ``repair`` of
    the unpadded instance exactly (same tree sums, same argmin/argmax
    tie-breaking).
    """
    import jax
    jnp = _jnp()

    sizes, prec, prec_u, T, L, onehot_mu, R, ddl, s_u, bs_mask = (
        jnp.asarray(v) for v in
        (data.sizes, data.prec, data.prec_u, data.T, data.L,
         data.onehot_mu, data.R, data.ddl, data.s_u, data.bs_mask))
    x = jnp.asarray(x)
    A = jnp.asarray(A)
    N, U, H = T.shape

    A = _dedupe_device(prec_u, A)

    x, A = jax.vmap(_mem_repair_bs, in_axes=(None, None, None, 0, 0, 0))(
        sizes, prec, onehot_mu, R, x, A)

    x_sel = jnp.einsum("nmh,um->nuh", x[:, :, 1:], onehot_mu)
    A = jnp.where(x_sel > 0, A, 0.0)

    lat_u = tree_sum(tree_sum(jnp.where(A > 0, T, 0.0), -1), 0)
    load_u = tree_sum(tree_sum(jnp.where(A > 0, L, 0.0), -1), 0)
    bad = (lat_u > ddl + _EPS) | (load_u > s_u + _EPS)
    A = jnp.where(bad[None, :, None], 0.0, A)

    user_mask = tree_sum(onehot_mu, -1) > 0                 # (U,)
    m_u = jnp.argmax(onehot_mu, axis=-1)
    cached_h = jnp.argmax(x, axis=-1)                       # (N, M)
    h_sel = cached_h[:, m_u]                                # (N, U)
    hm1 = jnp.maximum(h_sel - 1, 0)
    T_g = jnp.take_along_axis(T, hm1[:, :, None], axis=-1)[..., 0]
    L_g = jnp.take_along_axis(L, hm1[:, :, None], axis=-1)[..., 0]
    prec_g = prec_u[jnp.arange(U)[None, :], hm1]            # (N, U)
    feas = (h_sel > 0) & (T_g <= ddl[None] + _EPS) \
        & (L_g <= s_u[None] + _EPS) & (bs_mask[:, None] > 0)
    score = jnp.where(feas, prec_g, -jnp.inf)
    n_best = jnp.argmax(score, axis=0)                      # (U,)
    unserved = ~(A > 0).any(axis=(0, 2))
    assign = unserved & feas.any(axis=0) & user_mask
    h_best = jnp.take_along_axis(h_sel, n_best[None, :], axis=0)[0]
    hit_n = jnp.arange(N)[:, None] == n_best[None, :]       # (N, U)
    hit_h = jnp.arange(H)[None, :] == (h_best - 1)[:, None]  # (U, H)
    A = jnp.where(assign[None, :, None] & hit_n[:, :, None]
                  & hit_h[None, :, :], 1.0, A)
    return x, A
