"""Host-side summaries of the jit-safe diagnostics pytrees.

The kernels (``repro.core.lp``, ``repro.traces.engine``) emit raw
device curves — residuals, objective trajectories, per-slot cache
stats — sampled every ``diag_stride`` iterations.  This module turns those curves into the JSON-safe
convergence records that sweeps, benches, ``scripts/report.py`` and
``check_bench.py`` consume.  Pure numpy/stdlib; imports no jax.

``DEFAULT_TOL`` is calibrated against the production sweep grid: at the
default 4000 PDHG iterations the worst window's final scaled primal
residual is ~4.2e-3, so 1e-2 converges everywhere with ~2.4x headroom
while still catching a solver that stalls.  Truncated bench budgets
(200–500 iterations) intentionally do *not* reach it; those are gated
by residual-drift checks in ``check_bench.py`` instead of a flag.
"""
from __future__ import annotations

import numpy as np

#: Convergence tolerance on the scaled primal residual (see module doc).
DEFAULT_TOL = 1e-2


def _to_np(x):
    return np.asarray(x)


def lp_diag_summary(diag, tol: float = DEFAULT_TOL) -> dict:
    """Summarize one window's PDHG diagnostics pytree (1-D curves).

    Returns ``final_residual``, ``converged`` (final residual <= tol),
    ``iters_to_tol`` (first *sampled* iteration whose primal residual
    is <= tol, -1 if never — the curve is sampled at ``diag_stride``,
    so this is an upper bound on the true crossing), ``tol`` and
    ``n_samples``, plus the final dual residual and objective where the
    pytree has those curves.
    """
    pr = _to_np(diag["primal_res"]).ravel()
    iters = _to_np(diag["iters"]).ravel()
    final = float(pr[-1]) if pr.size else float("nan")
    hit = np.nonzero(pr <= tol)[0]
    out = {
        "final_residual": final,
        "converged": bool(final <= tol),
        "iters_to_tol": int(iters[hit[0]]) if hit.size else -1,
        "tol": float(tol),
        "n_samples": int(pr.size),
    }
    if "dual_res" in diag:
        dr = _to_np(diag["dual_res"]).ravel()
        if dr.size:
            out["final_dual_residual"] = float(dr[-1])
    if "obj" in diag:
        ob = _to_np(diag["obj"]).ravel()
        if ob.size:
            out["final_obj"] = float(ob[-1])
    return out


def convergence_table(residuals, tol: float = DEFAULT_TOL) -> dict:
    """Aggregate per-window final residuals into the convergence record
    sweeps publish (and ``report.py --check-converged`` gates on)."""
    res = [float(r) for r in residuals]
    not_conv = [i for i, r in enumerate(res) if not (r <= tol)]
    return {
        "n_windows": len(res),
        "n_not_converged": len(not_conv),
        "all_converged": not not_conv,
        "max_final_residual": max(res) if res else float("nan"),
        "tol": float(tol),
        "per_window": res,
    }
