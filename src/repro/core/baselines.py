"""Offline baselines (paper Sec. VII-B) — twice each, PR-3 style: a NumPy
reference (the oracle, closest to the paper's prose) and a pure-jnp device
kernel riding on the same :class:`~repro.core.lp.PDHGData` pytree,
engineered to make *identical decisions* (``docs/algorithms.md`` Sec. 8).

* SPR³  [22] — random-rounding joint caching/routing, but complete models
  only (no dynamic submodels) and loading time ignored in decisions.
  Device path: the CoCaR pipeline stages (PDHG → Alg. 1 rounding → repair)
  on a *relaxed* pytree (``spr3_relax_device``), sharing the LP kernel.
* Greedy — popularity-ordered caching, highest precision first, home-BS
  routing only.  Deterministic: a per-BS ``lax.scan`` fill on device.
* Random — random submodel choices under memory + random routing.  All
  randomness is pre-drawn (``draw_baseline_uniforms``) and consumed
  verbatim by both engines, so every cache/route choice coincides.
* GatMARL [55] — compact graph-attention multi-agent RL: a 2-layer GAT over
  the BS graph encodes per-BS demand; per-BS policy heads pick a submodel
  per model type; trained with REINFORCE on average served precision.
  Training stays host-side (``gat_policy``, cached); the learned policy's
  *rollout* (forward → argmax actions → sequential fill → best-precision
  routing) is a vmappable kernel (``gat_rollout_device``) with
  ``gat_rollout_host`` as its oracle.  (Loading time ignored in decisions,
  as in the paper's comparison.)

All baselines are *evaluated* under the same feasibility enforcement as
CoCaR (``mec.metrics.enforce`` / ``enforce_device``).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.jdcr import JDCRInstance, _jnp, tree_sum


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _route_home(inst: JDCRInstance, x):
    """Route every user to its home BS if the model is cached there."""
    A = np.zeros((inst.N, inst.U, inst.H))
    for u in range(inst.U):
        n = inst.home[u]
        h = int(np.argmax(x[n, inst.m_u[u]]))
        if h > 0:
            A[n, u, h - 1] = 1.0
    return A


def _route_best(inst: JDCRInstance, x, rng=None, random_route=False):
    """Route to a BS caching m_u (random or best precision), else cloud.

    Best-precision ties resolve to the smallest BS index (``max`` keeps the
    first maximal option) — the device twin resolves its argmax the same
    way.
    """
    A = np.zeros((inst.N, inst.U, inst.H))
    cached_h = np.argmax(x, axis=-1)                     # (N, M)
    for u in range(inst.U):
        m = inst.m_u[u]
        options = [(n, cached_h[n, m]) for n in range(inst.N)
                   if cached_h[n, m] > 0]
        if not options:
            continue
        if random_route:
            n, h = options[rng.integers(len(options))]
        else:
            n, h = max(options, key=lambda nh: inst.prec[m, nh[1]])
        A[n, u, h - 1] = 1.0
    return A


def _route_home_device(data, lvl):
    """``_route_home`` on cached levels ``lvl (N, M)``: one gathered route
    per real user at its home BS, if the home BS caches its model."""
    jnp = _jnp()
    N, M = lvl.shape
    H = data.T.shape[2]
    onehot_mu = jnp.asarray(data.onehot_mu)
    user_mask = tree_sum(onehot_mu, -1) > 0                 # (U,)
    m_u = jnp.argmax(onehot_mu, axis=-1)                    # (U,)
    home = jnp.argmax(jnp.asarray(data.home_onehot), axis=-1)  # (U,)
    h_u = lvl[home, m_u]                                    # (U,)
    hit_n = jnp.arange(N)[:, None] == home[None, :]         # (N, U)
    hit_h = jnp.arange(H)[None, :] == (h_u - 1)[:, None]    # (U, H)
    on = user_mask & (h_u > 0)
    return jnp.where(on[None, :, None] & hit_n[:, :, None]
                     & hit_h[None, :, :], 1.0, 0.0)


def _route_best_device(data, lvl):
    """``_route_best`` on cached levels: per user, the real BS caching its
    model with the highest precision (argmax-first on exact ties)."""
    jnp = _jnp()
    N, U, H = data.T.shape
    onehot_mu = jnp.asarray(data.onehot_mu)
    user_mask = tree_sum(onehot_mu, -1) > 0
    m_u = jnp.argmax(onehot_mu, axis=-1)
    h_sel = lvl[:, m_u]                                     # (N, U)
    hm1 = jnp.maximum(h_sel - 1, 0)
    prec_g = jnp.asarray(data.prec_u)[jnp.arange(U)[None, :], hm1]  # (N, U)
    ok = (h_sel > 0) & (jnp.asarray(data.bs_mask)[:, None] > 0)
    score = jnp.where(ok, prec_g, -jnp.inf)
    n_best = jnp.argmax(score, axis=0)                      # (U,)
    assign = user_mask & ok.any(axis=0)
    h_best = jnp.take_along_axis(h_sel, n_best[None, :], axis=0)[0]
    hit_n = jnp.arange(N)[:, None] == n_best[None, :]
    hit_h = jnp.arange(H)[None, :] == (h_best - 1)[:, None]
    return jnp.where(assign[None, :, None] & hit_n[:, :, None]
                     & hit_h[None, :, :], 1.0, 0.0)


def _levels_to_onehot(lvl, Hp1):
    xp = np if isinstance(lvl, np.ndarray) else _jnp()
    return (lvl[..., None] == xp.arange(Hp1)).astype(xp.float64)


# ---------------------------------------------------------------------------
# Greedy — popularity order, largest fitting submodel, home routing
# ---------------------------------------------------------------------------

def greedy(inst: JDCRInstance):
    counts = np.bincount(inst.m_u, minlength=inst.M)
    order = np.argsort(-counts, kind="stable")
    x = np.zeros((inst.N, inst.M, inst.H + 1))
    x[:, :, 0] = 1.0
    for n in range(inst.N):
        free = inst.R[n]
        for m in order:
            for h in range(inst.H, 0, -1):               # high precision first
                if inst.sizes[m, h] <= free:
                    x[n, m, :] = 0
                    x[n, m, h] = 1
                    free -= inst.sizes[m, h]
                    break
    return x, _route_home(inst, x)


def greedy_device(data):
    """``greedy`` as a pure jnp function of one padded window: the per-BS
    fill is a ``lax.scan`` over the (stable) popularity order, subtracting
    sizes in exactly the host loop's sequence so every fit test sees the
    same float budget.  Padded BSs carry ``R = 0``, so nothing fits."""
    import jax
    jnp = _jnp()

    sizes = jnp.asarray(data.sizes)
    M, Hp1 = sizes.shape
    counts = tree_sum(jnp.asarray(data.onehot_mu), 0)       # (M,) exact ints
    order = jnp.argsort(-counts, stable=True)
    hh = jnp.arange(Hp1)

    def fill_bs(R_n):
        def step(free, m):
            fits = (hh >= 1) & (sizes[m] <= free)
            h = jnp.max(jnp.where(fits, hh, 0))             # largest fitting
            return free - sizes[m, h], h
        _, lvls = jax.lax.scan(step, R_n, order)
        return jnp.zeros((M,), lvls.dtype).at[order].set(lvls)

    lvl = jax.vmap(fill_bs)(jnp.asarray(data.R))            # (N, M)
    x = _levels_to_onehot(lvl, Hp1)
    return x, _route_home_device(data, lvl)


# ---------------------------------------------------------------------------
# Random — uniform-driven on both engines
# ---------------------------------------------------------------------------

def draw_baseline_uniforms(key, N, M, U, n_seeds=1, batch=None):
    """All the randomness of ``n_seeds`` Random-policy draws, as three
    float64 uniform tensors both engines consume verbatim:

      u_perm  (S, N, M)  per-BS model visiting order (argsort of the row)
      u_h     (S, N, M)  submodel pick: h = floor(u · (H+1))
      u_route (S, U)     routing pick: n = floor(u · N_real)

    With ``batch`` given, every tensor gains a leading batch axis.
    """
    import jax

    lead = (n_seeds,) if batch is None else (batch, n_seeds)
    with jax.enable_x64(True):
        k = jax.random.PRNGKey(key) if isinstance(key, int) else key
        k1, k2, k3 = jax.random.split(k, 3)
        u_perm = jax.random.uniform(k1, lead + (N, M), dtype=np.float64)
        u_h = jax.random.uniform(k2, lead + (N, M), dtype=np.float64)
        u_route = jax.random.uniform(k3, lead + (U,), dtype=np.float64)
    return np.asarray(u_perm), np.asarray(u_h), np.asarray(u_route)


def random_from_uniforms(inst: JDCRInstance, u_perm, u_h, u_route):
    """One Random-policy draw as a deterministic function of pre-drawn
    uniforms (``u_perm/u_h (N, M)``, ``u_route (U,)``) — the NumPy oracle
    of ``random_device``."""
    H = inst.H
    x = np.zeros((inst.N, inst.M, H + 1))
    x[:, :, 0] = 1.0
    for n in range(inst.N):
        free = inst.R[n]
        for m in np.argsort(u_perm[n], kind="stable"):
            h = min(int(u_h[n, m] * (H + 1)), H)
            if h > 0 and inst.sizes[m, h] <= free:
                x[n, m, :] = 0
                x[n, m, h] = 1
                free -= inst.sizes[m, h]
    # paper: "user requests are randomly routed to a BS" — any BS; it is a
    # miss if that BS does not cache the model
    A = np.zeros((inst.N, inst.U, H))
    cached_h = np.argmax(x, axis=-1)
    for u in range(inst.U):
        n = min(int(u_route[u] * inst.N), inst.N - 1)
        h = cached_h[n, inst.m_u[u]]
        if h > 0:
            A[n, u, h - 1] = 1.0
    return x, A


def random_policy(inst: JDCRInstance, seed=0):
    u_perm, u_h, u_route = draw_baseline_uniforms(seed, inst.N, inst.M,
                                                  inst.U)
    return random_from_uniforms(inst, u_perm[0], u_h[0], u_route[0])


def random_device(data, u_perm, u_h, u_route):
    """``random_from_uniforms`` as a pure jnp function of one padded
    window.  The visiting order, the floor-scaled submodel picks, and the
    routing picks all come from the same uniforms the oracle consumes;
    routing scales by the number of *real* BSs, so padded rows are never
    drawn."""
    import jax
    jnp = _jnp()

    sizes = jnp.asarray(data.sizes)
    M, Hp1 = sizes.shape
    H = Hp1 - 1
    N, U = data.T.shape[0], data.T.shape[1]
    hh = jnp.arange(Hp1)

    def fill_bs(R_n, u_perm_n, u_h_n):
        order = jnp.argsort(u_perm_n, stable=True)
        def step(free, m):
            h_pick = jnp.minimum((u_h_n[m] * (H + 1)).astype(jnp.int32), H)
            ok = (h_pick > 0) & (sizes[m, h_pick] <= free)
            h = jnp.where(ok, h_pick, 0)
            return free - sizes[m, h], h
        _, lvls = jax.lax.scan(step, R_n, order)
        return jnp.zeros((M,), lvls.dtype).at[order].set(lvls)

    lvl = jax.vmap(fill_bs)(jnp.asarray(data.R),
                            jnp.asarray(u_perm), jnp.asarray(u_h))
    x = _levels_to_onehot(lvl, Hp1)

    onehot_mu = jnp.asarray(data.onehot_mu)
    user_mask = tree_sum(onehot_mu, -1) > 0
    m_u = jnp.argmax(onehot_mu, axis=-1)
    n_real = tree_sum(jnp.asarray(data.bs_mask), -1)
    n_pick = jnp.minimum((jnp.asarray(u_route) * n_real).astype(jnp.int32),
                         (n_real - 1).astype(jnp.int32))    # (U,)
    h_u = lvl[n_pick, m_u]
    hit_n = jnp.arange(N)[:, None] == n_pick[None, :]
    hit_h = jnp.arange(H)[None, :] == (h_u - 1)[:, None]
    on = user_mask & (h_u > 0)
    A = jnp.where(on[None, :, None] & hit_n[:, :, None] & hit_h[None, :, :],
                  1.0, 0.0)
    return x, A


# ---------------------------------------------------------------------------
# SPR³ — complete models only, loading time ignored
# ---------------------------------------------------------------------------

def spr3_relaxed(inst: JDCRInstance) -> JDCRInstance:
    """The complete-model relaxation SPR³ optimizes: intermediate submodels
    as large as the full model with zero precision (the LP then never
    prefers them) and a neutralized load constraint (s_u = window end)."""
    sizes = inst.sizes.copy()
    prec = inst.prec.copy()
    for m in range(inst.M):
        for h in range(1, inst.H):
            sizes[m, h] = sizes[m, inst.H]
            prec[m, h] = 0.0
    return dataclasses.replace(inst, sizes=sizes, prec=prec,
                               s_u=np.full(inst.U, 1e9))


def spr3_relax_device(data):
    """``spr3_relaxed`` on the :class:`~repro.core.lp.PDHGData` pytree —
    the transformed pytree feeds the *same* PDHG/round/repair kernels
    CoCaR uses (the LP solve is shared, only its inputs change)."""
    jnp = _jnp()
    Hp1 = data.sizes.shape[1]
    H = Hp1 - 1
    mid = (jnp.arange(Hp1) >= 1) & (jnp.arange(Hp1) < H)
    sizes = jnp.where(mid[None, :], data.sizes[:, H:H + 1], data.sizes)
    prec = jnp.where(mid[None, :], 0.0, data.prec)
    prec_u = jnp.where(jnp.arange(H)[None, :] < H - 1, 0.0, data.prec_u)
    s_u = jnp.full_like(data.s_u, 1e9)
    return data._replace(sizes=sizes, prec=prec, prec_u=prec_u, s_u=s_u)


def spr3(inst: JDCRInstance, seed=0):
    from repro.core import lp as LP
    from repro.core.rounding import repair, round_solution

    relaxed = spr3_relaxed(inst)
    x_f, A_f, _ = LP.solve_lp_scipy(relaxed)
    x_i, A_i = round_solution(relaxed, x_f, A_f, seed)
    x, A = repair(relaxed, x_i, A_i)
    return x, A


def spr3_from_fractional(inst: JDCRInstance, x_f, A_f, u_cat, u_phi):
    """The NumPy reference of the device SPR³ stages downstream of the LP:
    Alg. 1 rounding (trial axis from the uniforms) + repair, all against
    the relaxed instance.  Returns per-trial ``(x (T,...), A (T,...))``."""
    from repro.core.rounding import repair, round_from_uniforms

    relaxed = spr3_relaxed(inst)
    x_r, A_r = round_from_uniforms(np.asarray(x_f, np.float64),
                                   np.asarray(A_f, np.float64),
                                   relaxed.onehot_mu(), u_cat, u_phi)
    outs = [repair(relaxed, x_t, A_t) for x_t, A_t in zip(x_r, A_r)]
    return (np.stack([x for x, _ in outs]), np.stack([A for _, A in outs]))


# ---------------------------------------------------------------------------
# GatMARL-lite: GAT over the BS graph + REINFORCE
# ---------------------------------------------------------------------------

def _gat_forward(params, feats, adj):
    """One graph-attention layer + policy logits.

    feats: (N, F); adj: (N, N) with self-loops. Returns (N, M·(H+1))
    logits.  Zero adj rows/columns (padded BSs) contribute exactly-zero
    attention mass, so real rows' logits equal their unpadded values."""
    import jax.numpy as jnp

    h = jnp.tanh(feats @ params["w_in"])                     # (N, d)
    att_src = h @ params["a_src"]                            # (N,)
    att_dst = h @ params["a_dst"]
    scores = att_src[:, None] + att_dst[None, :]
    scores = jnp.where(adj > 0, scores, -1e9)
    alpha = jnp.exp(scores - scores.max(1, keepdims=True))
    alpha = alpha * (adj > 0)
    alpha = alpha / jnp.maximum(alpha.sum(1, keepdims=True), 1e-9)
    h2 = jnp.tanh(alpha @ h @ params["w_msg"] + h)
    return h2 @ params["w_out"]


_GAT_CACHE = {}


def gat_features(inst: JDCRInstance, n_pad: int = None):
    """Per-BS demand features for one window, optionally zero-padded to
    ``n_pad`` rows (the stacked grid shape)."""
    N = inst.N if n_pad is None else n_pad
    f = np.zeros((N, inst.M + 1))
    for u in range(inst.U):
        f[inst.home[u], inst.m_u[u]] += 1.0
    f[:inst.N, inst.M] = inst.R / inst.R.max()
    f[:, :inst.M] /= max(inst.U / inst.N, 1)
    return f


def gat_adj(inst: JDCRInstance, n_pad: int = None):
    """BS adjacency with self-loops, zero-padded to ``n_pad``."""
    adj = np.asarray(inst.wired < 1e11, dtype=np.float64)
    np.fill_diagonal(adj, 1.0)
    if n_pad is not None and n_pad > inst.N:
        dn = n_pad - inst.N
        adj = np.pad(adj, ((0, dn), (0, dn)))
    return adj


def _train_gatmarl(inst: JDCRInstance, seed: int, episodes: int = 150):
    """REINFORCE training, pinned to float64 (``enable_x64``) so the
    learned params — and therefore the gated comparison ratio — are
    identical whether or not the process runs under JAX_ENABLE_X64."""
    import jax

    with jax.enable_x64(True):
        return _train_gatmarl_x64(inst, seed, episodes)


def _train_gatmarl_x64(inst: JDCRInstance, seed: int, episodes: int):
    import jax
    import jax.numpy as jnp

    N, M, H = inst.N, inst.M, inst.H
    d = 32
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 5)
    params = {
        "w_in": jax.random.normal(ks[0], (M + 1, d)) * 0.3,
        "a_src": jax.random.normal(ks[1], (d,)) * 0.3,
        "a_dst": jax.random.normal(ks[2], (d,)) * 0.3,
        "w_msg": jax.random.normal(ks[3], (d, d)) * 0.3,
        "w_out": jax.random.normal(ks[4], (d, M * (H + 1))) * 0.3,
    }
    adj = jnp.asarray(gat_adj(inst))

    def reward_of(actions, inst):
        x = np.zeros((N, M, H + 1))
        for n in range(N):
            free = inst.R[n]
            for m in range(M):
                h = int(actions[n, m])
                if h > 0 and inst.sizes[m, h] <= free:
                    x[n, m, h] = 1
                    free -= inst.sizes[m, h]
                else:
                    x[n, m, 0] = 1
        A = _route_best(inst, x)
        from repro.mec import metrics as MET
        return MET.window_metrics(inst, x, A)["avg_precision"], x, A

    feats = jnp.asarray(gat_features(inst))
    lr = 0.05
    baseline = 0.0

    def logp_of(p, actions):
        lg = _gat_forward(p, feats, adj).reshape(N, M, H + 1)
        logp = jax.nn.log_softmax(lg, axis=-1)
        return jnp.take_along_axis(logp, actions[..., None], -1).sum()

    grad_fn = jax.jit(jax.grad(logp_of))
    for ep in range(episodes):
        key, k1 = jax.random.split(key)
        lg = _gat_forward(params, feats, adj).reshape(N, M, H + 1)
        a = jax.random.categorical(k1, lg, axis=-1)          # (N, M)
        r, _, _ = reward_of(np.asarray(a), inst)
        adv = r - baseline
        baseline = 0.9 * baseline + 0.1 * r
        grads = grad_fn(params, a)
        params = jax.tree.map(lambda p, g: p + lr * adv * g, params, grads)
    return params


def _gat_cache_key(inst: JDCRInstance, seed: int, episodes: int):
    """Content-derived cache key: repeated calls on an *identical* window
    reuse the training run, but every distinct scenario variant (capacity,
    skew, requests, …) trains its own policy — the paper's per-scenario
    protocol."""
    import hashlib

    h = hashlib.sha1()
    for a in (inst.m_u, inst.home, inst.R, inst.C, inst.sizes, inst.prec,
              inst.wired):
        h.update(np.ascontiguousarray(a).tobytes())
    return (inst.N, inst.M, inst.H, seed, episodes, h.hexdigest())


def gat_policy(inst: JDCRInstance, seed: int = 0, episodes: int = 150):
    """Train (or fetch the cached) GatMARL policy for this window's
    scenario; returns float64 params so both rollout engines run the
    forward pass on identical numbers."""
    cache_key = _gat_cache_key(inst, seed, episodes)
    if cache_key not in _GAT_CACHE:
        params = _train_gatmarl(inst, seed, episodes)
        _GAT_CACHE[cache_key] = {k: np.asarray(v, np.float64)
                                 for k, v in params.items()}
    return _GAT_CACHE[cache_key]


def _gat_fill(inst: JDCRInstance, actions):
    """Greedy sequential fill of the argmax actions (host reference)."""
    x = np.zeros((inst.N, inst.M, inst.H + 1))
    for n in range(inst.N):
        free = inst.R[n]
        for m in range(inst.M):
            h = int(actions[n, m])
            if h > 0 and inst.sizes[m, h] <= free:
                x[n, m, h] = 1
                free -= inst.sizes[m, h]
            else:
                x[n, m, 0] = 1
    return x


def gat_rollout_host(inst: JDCRInstance, params, feats=None, adj=None):
    """The learned policy's greedy rollout, host path: f64 forward on the
    (possibly padded) features, then the NumPy fill + best-precision route.
    ``feats``/``adj`` default to the window's own unpadded arrays; pass the
    stacked grid's padded versions to oracle the device kernel."""
    import jax
    import jax.numpy as jnp

    feats = gat_features(inst) if feats is None else feats
    adj = gat_adj(inst, n_pad=len(feats)) if adj is None else adj
    with jax.enable_x64(True):
        logits = np.asarray(_gat_forward(params, jnp.asarray(feats),
                                         jnp.asarray(adj)))
    actions = np.argmax(
        logits.reshape(len(feats), inst.M, inst.H + 1), -1)[:inst.N]
    x = _gat_fill(inst, actions)
    return x, _route_best(inst, x)


def gat_rollout_device(data, params, feats, adj):
    """``gat_rollout_host`` as a pure jnp function of one padded window:
    forward → argmax actions → per-BS ``lax.scan`` fill → masked-argmax
    best-precision routing.  vmappable over stacked windows (stack the
    params pytree alongside ``feats``/``adj``)."""
    import jax
    jnp = _jnp()

    sizes = jnp.asarray(data.sizes)
    M, Hp1 = sizes.shape
    N = data.T.shape[0]
    params = {k: jnp.asarray(v) for k, v in params.items()}
    logits = _gat_forward(params, jnp.asarray(feats),
                          jnp.asarray(adj)).reshape(N, M, Hp1)
    actions = jnp.argmax(logits, -1)                        # (N, M)

    def fill_bs(R_n, act_n):
        def step(free, ma):
            m, h_a = ma
            ok = (h_a > 0) & (sizes[m, h_a] <= free)
            h = jnp.where(ok, h_a, 0)
            return free - sizes[m, h], h
        _, lvls = jax.lax.scan(step, R_n, (jnp.arange(M), act_n))
        return lvls

    lvl = jax.vmap(fill_bs)(jnp.asarray(data.R), actions)   # (N, M)
    x = _levels_to_onehot(lvl, Hp1)
    return x, _route_best_device(data, lvl)


def gatmarl(inst: JDCRInstance, seed=0, episodes: int = 150):
    params = gat_policy(inst, seed, episodes)
    return gat_rollout_host(inst, params)
