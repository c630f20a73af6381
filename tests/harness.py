"""Shared conformance harness for the dual-engine test suite.

Every "two engines, one algorithm" test in this repo asserts the same
contract: *decisions* (0/1 cache and routing arrays, winning trial
indices) must be bit-identical across engines, while *objectives and
metrics* — plain float reductions whose summation order may differ —
agree to 1e-9.  This module is the single home of that contract:
instance builders (``make_instance``, ``tiny_instance``, heterogeneous
grids), the identity assertions (``assert_decisions_identical``,
``assert_same_offline``, ``assert_obj_close``), the rounding
certificates (``decision_margin``, ``threshold_shift_certificate``)
that make decision identity under a perturbed fractional solution
checkable rather than merely observed, and the offline grid's NumPy
host composition (``offline_grid_host``) with the bridge to the
benchmark's independent f64 LP (``reference_window``).

Used by tests/test_offline_batched.py, tests/test_baselines_device.py,
tests/test_scale.py, tests/test_batched_lp.py and chip_smoke.py.
"""
from types import SimpleNamespace

import numpy as np

from repro.core import cocar as CC
from repro.core import lp as LP
from repro.core.jdcr import JDCRInstance, tree_sum
from repro.mec.scenario import MECConfig, Scenario, stack_instances


# ---------------------------------------------------------------------------
# instance builders
# ---------------------------------------------------------------------------

def make_instance(seed=0, n_users=40, n_bs=3, n_models=4):
    """One scenario window from a seeded config — the stock random
    instance every dual-engine test starts from."""
    cfg = MECConfig(n_bs=n_bs, n_users=n_users, n_models=n_models, seed=seed)
    sc = Scenario(cfg)
    return sc.instance(0, sc.empty_cache())


def tiny_instance(n_bs=1, m_u=(0, 1), prec2=(0.9, 0.8), R=25.0,
                  ddl=10.0, sizes12=(10.0, 20.0)):
    """Hand-built 2-model, 2-submodel instance for repair edge cases:
    negligible latencies (unless ``ddl`` is shrunk), zero load times."""
    M, H = 2, 2
    U = len(m_u)
    sizes = np.zeros((M, H + 1))
    sizes[:, 1], sizes[:, 2] = sizes12
    prec = np.zeros((M, H + 1))
    prec[:, 1] = np.asarray(prec2) / 2.0
    prec[:, 2] = np.asarray(prec2)
    flops = np.zeros((M, H + 1))
    flops[:, 1:] = 1e-3
    x_prev = np.zeros((n_bs, M, H + 1))
    x_prev[:, :, 0] = 1.0
    return JDCRInstance(
        sizes=sizes, prec=prec, flops=flops,
        loadD=np.zeros((M, H + 1, H + 1)),
        R=np.full(n_bs, R), C=np.full(n_bs, 100.0),
        phi=np.full(n_bs, 100.0), wired=np.full((n_bs, n_bs), 1e12),
        lam=np.zeros((n_bs, n_bs)), m_u=np.asarray(m_u),
        d_u=np.full(U, 0.1), ddl=np.full(U, ddl),
        s_u=np.full(U, 10.0), home=np.zeros(U, dtype=int),
        x_prev=x_prev)


def hetero_insts(spec):
    """A heterogeneous grid from ``[(seed, n_users, n_bs), ...]`` — the
    padded-stack fixture shape every identity test sweeps over."""
    return [make_instance(seed=s, n_users=u, n_bs=n) for s, u, n in spec]


def padded_stack(spec):
    """``(insts, stacked)`` for a heterogeneous grid spec — instances at
    their true shapes plus the max-padded :class:`StackedWindows`."""
    insts = hetero_insts(spec)
    return insts, stack_instances(insts)


def reference_window(inst):
    """The arrays ``chipbench/reference/offline.py::solve_lp`` reads,
    taken from ``inst``'s own arrays: its NumPy f64 PDHG, which imports
    nothing of the program, then solves the same window."""
    return SimpleNamespace(
        N=inst.N, M=inst.M, U=inst.U, H=inst.H, m_u=np.asarray(inst.m_u),
        sizes=inst.sizes, prec_u=inst.prec[inst.m_u, 1:],
        T=inst.e2e_latency(), L=inst.load_latency(), R=inst.R,
        ddl=inst.ddl, s_u=inst.s_u)


# ---------------------------------------------------------------------------
# the offline grid composed on the host
# ---------------------------------------------------------------------------

def offline_grid_host(insts, seed=0, pdhg_iters=4000, best_of=8,
                      n_seeds=1):
    """``cocar_grid``'s computation with NumPy round + repair: stack the
    windows, draw the shared uniforms, solve the batched LP on the
    device, then per-(window, seed, trial) NumPy loops.  Returns
    ``results[b][s] = (x, A, info)`` at true shapes."""
    stacked = stack_instances(list(insts))
    u_cat, u_phi = CC.offline_uniforms(stacked, seed, n_seeds, best_of)
    res = LP.solve_lp_pdhg_batched(stacked.data, iters=pdhg_iters)
    return CC.offline_pipeline_host(stacked, res.x, res.A, u_cat, u_phi,
                                    n_seeds=n_seeds)


# ---------------------------------------------------------------------------
# decision-identity assertions
# ---------------------------------------------------------------------------

def assert_decisions_identical(x_a, A_a, x_b, A_b, msg=""):
    """The core contract: 0/1 cache and routing arrays bit-equal."""
    np.testing.assert_array_equal(np.asarray(x_a), np.asarray(x_b),
                                  err_msg=f"cache decisions differ {msg}")
    np.testing.assert_array_equal(np.asarray(A_a), np.asarray(A_b),
                                  err_msg=f"routing decisions differ {msg}")


def assert_obj_close(a, b, atol=1e-9, msg=""):
    """Objectives/metrics: float reductions, 1e-9 not bit equality."""
    assert abs(float(a) - float(b)) < atol, (msg, float(a), float(b))


def assert_same_offline(a, b):
    """Two ``results[window][seed] = (x, A, info)`` offline grids make
    identical decisions: arrays bit-equal, same winning trial per seed
    (``info`` may be a metrics dict on policy grids — then only the
    arrays are compared)."""
    for per_a, per_b in zip(a, b):
        for (xa, Aa, ia), (xb, Ab, ib) in zip(per_a, per_b):
            assert_decisions_identical(xa, Aa, xb, Ab)
            if isinstance(ia, dict) and "best_t" in ia:
                assert ia["best_t"] == ib["best_t"]


# ---------------------------------------------------------------------------
# the rounding-margin certificate
# ---------------------------------------------------------------------------

def _thresholds(x_frac, A_frac, onehot_mu):
    """The two threshold families Alg. 1's rounding compares uniforms
    against: categorical partial sums ``cums (..., H)`` and Bernoulli
    routing probabilities ``phi (n, u, h)``."""
    x_frac = np.asarray(x_frac, np.float64)
    A_frac = np.asarray(A_frac, np.float64)
    probs = np.clip(x_frac, 0.0, 1.0)
    den = np.maximum(tree_sum(probs, -1), 1e-12)
    probs = probs / den[..., None]
    # the same left-to-right partial sums round_from_uniforms compares
    cums = np.cumsum(probs[..., :-1], axis=-1)
    xa = np.einsum("nmh,um->nuh", x_frac[..., :, :, 1:], onehot_mu)
    phi = np.where(xa > 1e-12, A_frac / np.maximum(xa, 1e-12), 0.0)
    return cums, np.clip(phi, 0.0, 1.0)


def decision_margin(x_frac, A_frac, onehot_mu, u_cat, u_phi):
    """Distance of every rounding uniform to its nearest decision
    threshold, for the given fractional solution.

    Alg. 1 decisions are threshold crossings: the categorical draw
    compares ``u_cat`` against partial sums of the normalized x†[n,m,:],
    the Bernoulli routing draw compares ``u_phi`` against
    φ = clip(A†/x_a, 0, 1).  A perturbed fractional solution (e.g. the
    device pipeline's against an independent f64 solve, within ``gap``)
    moves each threshold by O(gap / min-normalizer); decisions therefore
    cannot flip while the reported margins stay far above that.
    ``tests/test_offline_batched.py`` asserts on it — turning "decisions
    happened to match" into "decisions had slack to spare".

    Returns ``{"cat": float, "phi": float, "min": float}`` (each the
    minimum over all trials and entries; padded users, whose ``phi``
    threshold is pinned at 0, are excluded from the phi margin).
    """
    onehot_mu = np.asarray(onehot_mu, np.float64)
    u_cat = np.asarray(u_cat, np.float64)
    u_phi = np.asarray(u_phi, np.float64)
    cums, phi_p = _thresholds(x_frac, A_frac, onehot_mu)
    margin_cat = float(np.min(np.abs(u_cat[..., None] - cums)))
    user_mask = onehot_mu.sum(-1) > 0                       # (U,)
    d_phi = np.abs(u_phi - phi_p)
    margin_phi = float(np.min(np.where(user_mask[None, :, None],
                                       d_phi, np.inf)))
    return {"cat": margin_cat, "phi": margin_phi,
            "min": min(margin_cat, margin_phi)}


def threshold_shift_certificate(x_ref, A_ref, x_alt, A_alt, onehot_mu,
                                u_cat, u_phi):
    """Per-comparison certificate that two fractional solutions round to
    identical decisions under the given uniforms.

    For every rounding comparison, the uniform's distance to the
    *reference* threshold must exceed the shift of that same threshold
    under the perturbed solution — the uniform then lands on the same
    side of both, so every threshold crossing (and hence the whole
    round → repair → argmax chain, which consumes only the crossings)
    resolves identically.  This is sharper than ``decision_margin``'s
    global minimum: a large fractional gap on a slack threshold and a
    razor-thin margin on an *unmoved* threshold both certify, which is
    what makes the certificate hold at bench scale where the global
    min-margin (a minimum over ~1e5 draws) collapses below the global
    max-gap.

    Returns ``{"certified": bool, "headroom": float}`` — headroom is the
    minimum margin/shift ratio over all moved thresholds (inf when no
    threshold moved); certified requires margin > shift (or shift == 0)
    everywhere.
    """
    onehot_mu = np.asarray(onehot_mu, np.float64)
    u_cat = np.asarray(u_cat, np.float64)
    u_phi = np.asarray(u_phi, np.float64)
    cums_r, phi_r = _thresholds(x_ref, A_ref, onehot_mu)
    cums_p, phi_p = _thresholds(x_alt, A_alt, onehot_mu)
    user_mask = onehot_mu.sum(-1) > 0

    m_cat = np.abs(u_cat[..., None] - cums_r)
    s_cat = np.broadcast_to(np.abs(cums_r - cums_p), m_cat.shape)
    m_phi = np.where(user_mask[None, :, None], np.abs(u_phi - phi_r),
                     np.inf)
    s_phi = np.broadcast_to(np.abs(phi_r - phi_p), m_phi.shape)

    def _family(m, s):
        ok = bool(((s < m) | (s == 0.0)).all())
        moved = s > 0.0
        if not moved.any():
            return ok, float("inf")
        with np.errstate(divide="ignore"):
            ratio = np.where(moved, m / np.maximum(s, 1e-300), np.inf)
        return ok, float(ratio.min())

    ok_c, head_c = _family(m_cat, s_cat)
    ok_p, head_p = _family(m_phi, s_phi)
    return {"certified": ok_c and ok_p,
            "headroom": min(head_c, head_p)}
