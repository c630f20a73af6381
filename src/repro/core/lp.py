"""LP solvers for problem P1-LR (paper Sec. V-A).

Two interchangeable backends:

  * ``solve_lp_scipy`` — sparse HiGHS (exact; correctness oracle and default
    at paper scale: ~10k vars solve in well under a second);
  * ``solve_lp_pdhg`` — matrix-free PDHG (Chambolle–Pock with diagonal
    preconditioning, PDLP-style) written in JAX and fully jit-compiled.
    This is the accelerator-native production path: the operator K is never
    materialized — every constraint family is applied functionally — so the
    solver scales to large (N·U·H) instances and can run on the serving mesh
    next to the data plane.

The PDHG iteration is a pure function of a :class:`PDHGData` pytree, so it
jits once per shape and vmaps across whole *batches* of windows:
``solve_lp_pdhg_batched`` solves a stack of instances (windows, seeds,
scenario-grid variants — see ``repro.mec.scenario.stack_instances``) in one
dispatch.  Heterogeneous (N, U) stacks are padded with inert base stations
(masked out of the routing update entirely via ``bs_mask``) and inert
users (zero precision and a zero one-hot row, so no mass ever moves
toward them); real rows see exactly the per-iteration updates of a solo
solve.

Both backends return fractional (x†, A†) with x (N,M,H+1) and A (N,U,H).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import NamedTuple

import numpy as np

from repro.core.jdcr import JDCRInstance
from repro.obs.diagnostics import DEFAULT_TOL as PDHG_TOL
from repro.obs.tracing import register_jit

#: Default sampling stride (iterations) for the diagnostics tap.
DIAG_STRIDE = 50


# ---------------------------------------------------------------------------
# scipy / HiGHS oracle
# ---------------------------------------------------------------------------

def solve_lp_scipy(inst: JDCRInstance):
    import scipy.sparse as sp
    from scipy.optimize import linprog

    N, M, H, U = inst.N, inst.M, inst.H, inst.U
    nx = N * M * (H + 1)
    nA = N * U * H
    nz = nx + nA

    def xid(n, m, h):
        return (n * M + m) * (H + 1) + h

    def aid(n, u, h):
        return nx + (n * U + u) * H + h

    c = np.zeros(nz)
    prec_u = inst.prec[inst.m_u, 1:]                       # (U, H)
    for n in range(N):
        base = nx + n * U * H
        c[base:base + U * H] = -prec_u.ravel()             # maximize

    rows, cols, vals, b_ub = [], [], [], []

    def add_row(cidx, cval, rhs):
        r = len(b_ub)
        rows.extend([r] * len(cidx))
        cols.extend(cidx)
        vals.extend(cval)
        b_ub.append(rhs)

    # (2) memory
    for n in range(N):
        idx = [xid(n, m, h) for m in range(M) for h in range(H + 1)]
        val = [inst.sizes[m, h] for m in range(M) for h in range(H + 1)]
        add_row(idx, val, inst.R[n])
    # (12) route, (15) latency, (16) load
    T = inst.e2e_latency()                                 # (N,U,H)
    L = inst.load_latency()                                # (N,U,H)
    for u in range(U):
        idx = [aid(n, u, h) for n in range(N) for h in range(H)]
        add_row(idx, [1.0] * len(idx), 1.0)
        add_row(idx, [T[n, u, h] for n in range(N) for h in range(H)],
                inst.ddl[u])
        add_row(idx, [L[n, u, h] for n in range(N) for h in range(H)],
                inst.s_u[u])
    # (14) A <= x
    for n in range(N):
        for u in range(U):
            m = inst.m_u[u]
            for h in range(H):
                add_row([aid(n, u, h), xid(n, m, h + 1)], [1.0, -1.0], 0.0)

    A_ub = sp.csr_matrix((vals, (rows, cols)), shape=(len(b_ub), nz))

    # (1) equality: one submodel slot per (n, m)
    er, ec, ev, b_eq = [], [], [], []
    for n in range(N):
        for m in range(M):
            r = len(b_eq)
            for h in range(H + 1):
                er.append(r)
                ec.append(xid(n, m, h))
                ev.append(1.0)
            b_eq.append(1.0)
    A_eq = sp.csr_matrix((ev, (er, ec)), shape=(len(b_eq), nz))

    res = linprog(c, A_ub=A_ub, b_ub=np.asarray(b_ub), A_eq=A_eq,
                  b_eq=np.asarray(b_eq), bounds=(0, 1), method="highs")
    if not res.success:
        raise RuntimeError(f"LP failed: {res.message}")
    z = res.x
    x = z[:nx].reshape(N, M, H + 1)
    A = z[nx:].reshape(N, U, H)
    return x, A, -res.fun


# ---------------------------------------------------------------------------
# JAX PDHG (matrix-free, diagonally preconditioned, batchable)
# ---------------------------------------------------------------------------

class PDHGData(NamedTuple):
    """Everything the PDHG iteration needs about one window, as arrays.

    A pure pytree: jit-traceable, and vmappable over a leading batch axis
    (see ``solve_lp_pdhg_batched``).  Shapes (unbatched):

      sizes      (M, H+1)   submodel memory footprints r_h
      prec       (M, H+1)   catalog precision p_h (slot 0 = 0) — unused by
                            the LP iteration itself, but the repair kernel
                            (``repro.core.rounding.repair_device``) rides
                            on the same pytree and keys eviction benefits
                            off the per-model precision
      prec_u     (U, H)     objective coefficients p_h per user
      T          (N, U, H)  end-to-end latency T̂ (paper Eq. 15)
      L          (N, U, H)  model-load latency (paper Eq. 16)
      onehot_mu  (U, M)     one-hot of each user's requested model type
      R          (N,)       memory capacity
      ddl        (U,)       latency budgets
      s_u        (U,)       initiation times (load-latency budgets)
      bs_mask    (N,)       1 for real base stations, 0 for padding; the
                            kernel freezes routing mass at masked rows and
                            sizes the route-dual step from the mask, so
                            padded rows never perturb real ones
      home_onehot (U, N)    one-hot of each user's home BS — unused by the
                            LP iteration, but the baseline kernels riding
                            on the same pytree (``repro.core.baselines``)
                            key home-BS routing off it; zero row for
                            padded users
    """
    sizes: object
    prec: object
    prec_u: object
    T: object
    L: object
    onehot_mu: object
    R: object
    ddl: object
    s_u: object
    bs_mask: object
    home_onehot: object


def pdhg_data(inst: JDCRInstance) -> PDHGData:
    """Extract the solver-facing arrays from one instance."""
    home_onehot = np.zeros((inst.U, inst.N))
    home_onehot[np.arange(inst.U), inst.home] = 1.0
    return PDHGData(
        sizes=np.asarray(inst.sizes, dtype=np.float64),
        prec=np.asarray(inst.prec, dtype=np.float64),
        prec_u=np.asarray(inst.prec[inst.m_u, 1:], dtype=np.float64),
        T=np.asarray(inst.e2e_latency(), dtype=np.float64),
        L=np.asarray(inst.load_latency(), dtype=np.float64),
        onehot_mu=inst.onehot_mu(),
        R=np.asarray(inst.R, dtype=np.float64),
        ddl=np.asarray(inst.ddl, dtype=np.float64),
        s_u=np.asarray(inst.s_u, dtype=np.float64),
        bs_mask=np.ones(inst.N),
        home_onehot=home_onehot)


def _pdhg_kernel(data: PDHGData, iters: int, diagnostics: bool = False,
                 diag_stride: int = DIAG_STRIDE):
    """One window's PDHG solve as a pure jnp function of ``data``.

    Chambolle–Pock with Pock–Chambolle diagonal step sizes (alpha = 1):
    tau_j = 1/sum_i |K_ij|, sigma_i = 1/sum_j |K_ij|.  Duals: the one-hot
    equality (N,M) is free, every inequality dual is projected to >= 0.

    Working layout: every per-(BS, user, exit) tensor — A, its
    extrapolation, the coupling dual, ``tau_A``, T, L and the objective
    ``prec_u`` — is held as (N, H, U), users minor, transposed once
    outside the loop; A goes back to (N, U, H) on return.  On the TPU the
    minor axis maps to the 128 vreg lanes, so (N, U, H) would fill 3 of
    them.  The loop body has no ``dot_general``: the chip has no float64
    matrix unit, and XLA emulates an f64 dot as nested loops over f32
    and bf16 pieces.  The coupling ``x_a[n,h,u] = x[n, m_u, 1+h]`` selects
    ``x[n, m, 1+h]`` where user u wants model m and sums over the M
    models, which is exact: every term but one is 0.0 (all of them for
    padded users, whose ``onehot_mu`` row is 0).  Its adjoint is a masked
    lane sum over users per model, and the latency, load and memory rows
    are multiply-then-reduce.

    With ``diagnostics=True`` the same iteration runs as nested scans over
    ``diag_stride``-sized segments (bit-identical composition — the scan
    body is unchanged and segment boundaries only read the carry) and the
    return grows a third element: a jit-safe pytree of curves sampled at
    each stride boundary plus the final iterate —

      iters       (S,) int32   sampled iteration counts
      primal_res  (S,)         scaled primal residual (the same masked
                               max the host ``pdhg_primal_residual``
                               computes: memory / max(R), route, A <= x,
                               one-submodel equality)
      dual_res    (S,)         fixed-point displacement of one extra
                               PDHG step at the sampled iterate (0 at a
                               saddle point)
      obj         (S,)         LP objective trajectory
    """
    import jax
    import jax.numpy as jnp

    sizes, onehot_mu, R, ddl, s_u, bs_mask = (
        data.sizes, data.onehot_mu, data.R, data.ddl, data.s_u,
        data.bs_mask)
    N, U, H = data.T.shape
    M = sizes.shape[0]
    # (N, H, U) working layout, users on the lanes (see the docstring)
    T = jnp.swapaxes(data.T, 1, 2)
    L = jnp.swapaxes(data.L, 1, 2)
    prec_u = data.prec_u.T                                          # (H,U)
    wants = onehot_mu.T > 0                                         # (M,U)

    def K(x, A):
        y_eq = x.sum(-1) - 1.0                                      # (N,M)
        y_mem = (x * sizes).sum((1, 2)) - R                         # (N,)
        y_route = A.sum((0, 1)) - 1.0                               # (U,)
        y_lat = (A * T).sum((0, 1)) - ddl                           # (U,)
        y_load = (A * L).sum((0, 1)) - s_u                          # (U,)
        xa = jnp.where(wants[:, None], x[:, :, 1:, None],
                       0.0).sum(1)                                  # (N,H,U)
        y_ax = A - xa                                               # (N,H,U)
        return y_eq, y_mem, y_route, y_lat, y_load, y_ax

    def KT(y):
        y_eq, y_mem, y_route, y_lat, y_load, y_ax = y
        gx_sub = -jnp.where(wants[:, None], y_ax[:, None],
                            0.0).sum(-1)                            # (N,M,H)
        gx = y_eq[:, :, None] + y_mem[:, None, None] * sizes[None] \
            + jnp.pad(gx_sub, ((0, 0), (0, 0), (1, 0)))
        gA = y_route + y_ax + y_lat * T + y_load * L
        return gx, gA

    # row sums (per dual)
    r_eq = jnp.full((N, M), float(H + 1))
    r_mem = jnp.ones((N,)) * sizes.sum()
    r_route = jnp.ones((U,)) * bs_mask.sum() * H     # only real BSs route
    r_lat = T.sum((0, 1))
    r_load = L.sum((0, 1))
    r_ax = jnp.full((N, H, U), 2.0)
    sig = tuple(1.0 / jnp.maximum(r, 1e-9)
                for r in (r_eq, r_mem, r_route, r_lat, r_load, r_ax))
    # column sums (per primal)
    cx = jnp.ones((N, M, H + 1))                                    # eq
    cx += sizes[None]                                               # mem
    users_of_m = onehot_mu.sum(0)                                   # (M,)
    cx = cx.at[:, :, 1:].add(users_of_m[None, :, None])             # A<=x
    cA = jnp.ones((N, H, U)) + T + L + 1.0                          # route+lat+load+ax
    tau_x = 1.0 / jnp.maximum(cx, 1e-9)
    # masked rows get a zero step: A starts at 0 there and stays exactly 0,
    # so padded base stations never couple into the real rows' duals
    tau_A = bs_mask[:, None, None] / jnp.maximum(cA, 1e-9)

    def proj_dual(y):
        y_eq, *ineq = y
        return (y_eq,) + tuple(jnp.maximum(v, 0.0) for v in ineq)

    x = jnp.full((N, M, H + 1), 1.0 / (H + 1))
    A = jnp.zeros((N, H, U))
    y = tuple(jnp.zeros_like(v) for v in K(x, A))

    def body(carry, _):
        x, A, y = carry
        gx, gA = KT(y)
        # gradient of -objective wrt A is -prec
        x_new = jnp.clip(x - tau_x * gx, 0.0, 1.0)
        A_new = jnp.clip(A - tau_A * (gA - prec_u), 0.0, 1.0)
        xb = 2 * x_new - x
        Ab = 2 * A_new - A
        Ky = K(xb, Ab)
        y_new = proj_dual(tuple(yy + s * kk
                                for yy, s, kk in zip(y, sig, Ky)))
        return (x_new, A_new, y_new), None

    if not diagnostics:
        (x, A, y), _ = jax.lax.scan(body, (x, A, y), None, length=iters)
        return x, jnp.swapaxes(A, 1, 2)

    bs = bs_mask > 0                                            # (N,)
    um = onehot_mu.sum(-1) > 0                                  # (U,)
    r_scale = 1.0 / jnp.maximum(R.max(), 1e-9)

    def sample(carry):
        x, A, _ = carry
        y_eq, y_mem, y_route, _, _, y_ax = K(x, A)
        r_eq = jnp.max(jnp.where(bs[:, None], jnp.abs(y_eq), 0.0))
        r_mem = jnp.max(jnp.where(bs, y_mem, -jnp.inf)) * r_scale
        r_route = jnp.max(jnp.where(um, y_route, -jnp.inf))
        primal = jnp.maximum(
            jnp.maximum(jnp.maximum(r_eq, r_mem),
                        jnp.maximum(r_route, jnp.max(y_ax))), 0.0)
        (x2, A2, _), _ = body(carry, None)
        dual = jnp.maximum(jnp.abs(x2 - x).max(), jnp.abs(A2 - A).max())
        obj = (A * prec_u).sum()
        return primal, dual, obj

    n_seg, rem = divmod(int(iters), int(diag_stride))

    def seg(carry, _):
        carry, _ = jax.lax.scan(body, carry, None, length=diag_stride)
        return carry, sample(carry)

    carry = (x, A, y)
    curves = []
    if n_seg:
        carry, curves = jax.lax.scan(seg, carry, None, length=n_seg)
    if rem:
        carry, _ = jax.lax.scan(body, carry, None, length=rem)
    sampled = [diag_stride * (s + 1) for s in range(n_seg)]
    if rem or not n_seg:  # final iterate not already on a stride boundary
        final = sample(carry)
        sampled.append(int(iters))
        pr, dr, ob = (jnp.concatenate([curves[i], final[i][None]])
                      if n_seg else final[i][None] for i in range(3))
    else:
        pr, dr, ob = curves
    diag = {"iters": jnp.asarray(sampled, dtype=jnp.int32),
            "primal_res": pr, "dual_res": dr, "obj": ob}
    x, A, _ = carry
    return x, jnp.swapaxes(A, 1, 2), diag


_JIT_CACHE = {}


def _jitted_kernel(batched: bool, diagnostics: bool = False,
                   diag_stride: int = DIAG_STRIDE):
    """Module-level jit cache: one compile per (batched, diag, shape,
    iters) — repeat calls at the same shapes (e.g. window loops) skip
    tracing.  Every cached entry point is registered with ``repro.obs``
    so span retrace counters see it."""
    mode = "batched" if batched else "single"
    # the stride is only a trace constant when the tap is on; normalize
    # it out of the key otherwise so diag-off callers share one compile
    key = (mode, bool(diagnostics),
           int(diag_stride) if diagnostics else None)
    if key not in _JIT_CACHE:
        import jax
        fn = functools.partial(_pdhg_kernel, diagnostics=diagnostics,
                               diag_stride=diag_stride)
        if batched:
            fn = jax.vmap(fn, in_axes=(0, None))
        jitted = jax.jit(fn, static_argnums=(1,))
        name = f"lp:{mode}:diag={int(bool(diagnostics))}"
        _JIT_CACHE[key] = register_jit(name, jitted)
    return _JIT_CACHE[key]


@dataclass
class PDHGResult:
    x: np.ndarray
    A: np.ndarray
    obj: float
    iters: int
    primal_res: float
    dual_res: float
    converged: bool = False
    tol: float = 0.0
    diag: object = None


@dataclass
class BatchedPDHGResult:
    """Padded batch solution: x (B,N,M,H+1), A (B,N,U,H), objs (B,).

    With heterogeneous stacks, slice each element back to its true (N_i,
    U_i) before use — ``StackedWindows.unstack`` does this.  ``diag``
    carries the batched diagnostics curves (leading axis B) when the run
    asked for them, else None.
    """
    x: np.ndarray
    A: np.ndarray
    objs: np.ndarray
    iters: int
    diag: object = None


def pdhg_primal_residual(inst: JDCRInstance, x, A) -> float:
    """Scaled primal feasibility residual of a fractional (x, A) — the
    max over memory / max(R), route, A <= x and the one-submodel
    equality (the same contract the device-side diagnostics sample and
    ``obs.DEFAULT_TOL`` are calibrated against)."""
    from repro.core.jdcr import check_feasible
    res = check_feasible(inst, x, A, atol=np.inf)
    primal = max(res["memory"] / max(inst.R.max(), 1e-9), res["route"],
                 res["A_le_x"], res["one_submodel"])
    return float(max(primal, 0.0))


def solve_lp_pdhg(inst: JDCRInstance, iters: int = 4000, check_every: int = 200,
                  tol: float = PDHG_TOL, diagnostics: bool = False):
    """One-window PDHG solve.  The result always carries a ``converged``
    flag (final residual vs ``tol``) instead of silently returning after
    the fixed iteration budget; ``diagnostics=True`` additionally attaches
    the device-sampled residual/objective curves (stride =
    ``check_every``) without changing x/A bits."""
    out = _jitted_kernel(batched=False, diagnostics=diagnostics,
                         diag_stride=check_every)(pdhg_data(inst), iters)
    x, A = out[0], out[1]
    diag = ({k: np.asarray(v) for k, v in out[2].items()}
            if diagnostics else None)
    x = np.asarray(x)
    A = np.asarray(A)
    obj = inst.objective(A)
    primal = pdhg_primal_residual(inst, x, A)
    return PDHGResult(x=x, A=A, obj=obj, iters=iters,
                      primal_res=primal, dual_res=0.0,
                      converged=bool(primal <= tol), tol=float(tol),
                      diag=diag)


def solve_lp_pdhg_batched(data: PDHGData, iters: int = 4000,
                          diagnostics: bool = False,
                          diag_stride: int = DIAG_STRIDE) -> BatchedPDHGResult:
    """Solve a whole stack of windows in ONE vmapped, jitted dispatch.

    ``data`` is a :class:`PDHGData` whose every field carries a leading
    batch axis (build it with ``repro.mec.scenario.stack_instances``).
    Objectives are exact: padded users carry zero ``prec_u`` and padded
    base stations hold A == 0 throughout (``bs_mask``), so padding
    contributes nothing to the einsum.
    """
    out = _jitted_kernel(batched=True, diagnostics=diagnostics,
                         diag_stride=diag_stride)(data, iters)
    x, A = out[0], out[1]
    diag = ({k: np.asarray(v) for k, v in out[2].items()}
            if diagnostics else None)
    x = np.asarray(x)
    A = np.asarray(A)
    objs = np.einsum("bnuh,buh->b", A, np.asarray(data.prec_u))
    return BatchedPDHGResult(x=x, A=A, objs=objs, iters=iters, diag=diag)
