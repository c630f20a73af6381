"""Fused PDHG inner loop for the P1-LR window solver.

``repro.core.lp._pdhg_kernel`` — the bit-compared reference — materializes
the full primal/dual state through HBM every iteration: selects and masked
sums for the one-hot coupling, separate reductions per dual family, and a
dozen elementwise passes.  This module is the fused production path behind
``solve_lp_pdhg(..., backend="pallas")``:

  * **one step, restructured** (``_fused_step``) on a 2-D *row layout*:
    every per-(BS, exit) tensor is a matrix with one row per (n, h) pair,
    ``R = N·(H+1)`` rows, exit 0 included.  The cache state is
    ``x (R, M)``, the routing state and its coupling dual are
    ``A, y_ax (R, U)`` with the exit-0 rows pinned at exactly 0 (zero
    step sizes), and the per-user duals are ``(1, U)`` rows.  The
    cache↔route coupling ``x_a`` and its transpose are each one real
    GEMM against the one-hot model→user matrix at ``precision=HIGHEST``
    (bit-identical to the reference's gather: one-hot rows contract
    exactly one term per output, and HIGHEST splits an f32 operand into
    bf16 pieces that sum back to it exactly); the per-BS sums and
    broadcasts are GEMMs against the 0/1 block matrix ``E (N, R)``; the
    routing prox folds ``tau_A`` into precomputed ``tau_A·T`` /
    ``tau_A·L`` tensors.  The same Chambolle–Pock math
    (docs/algorithms.md Sec. 3) with no scatter, no 1-D state and no
    reshape inside the step — the layout Mosaic compiles for the TPU;
  * **two engines over the same step**: ``engine="scan"`` wraps the step
    in ``lax.scan``, ``engine="pallas"`` keeps the whole state resident
    in VMEM scratch across a *block* of iterations per grid step
    (sequential grid), so the primal/dual tensors never round-trip HBM
    between iterations.  Both engines execute the identical jnp
    expressions on the identical state layout; what separates them is
    only per-compilation FMA contraction, so interpret-mode Pallas agrees
    with the scan engine to f32-ulp noise (~1e-7) through the sweep — and
    the *decisions* derived from either are bit-identical, the
    conformance contract ``tests/test_pdhg_fused.py`` enforces;
  * **mixed precision** (``polish``): the inner sweep runs in float32,
    then the last ``polish`` iterations re-run the same fused step in
    float64 on the carried state — always on the scan engine, since
    Mosaic has no float64.  Decisions downstream (rounding, repair,
    winning trials) are gated on ~1e-15-scale comparisons of *uniforms
    vs thresholds*; the fused path preserves them because (a) the
    float64 tail pins every saturated coordinate back to the exact 0/1
    the reference reaches, and (b) the residual fractional gap is orders
    of magnitude below the rounding-threshold margins, which
    ``tests/harness.py::decision_margin`` certifies per run.

Padding is *stronger* than the reference's inertness: ``tau_A`` carries
both the ``bs_mask`` row mask and a per-user column mask (users with an
all-zero ``onehot_mu`` row), so padded base-station rows AND padded user
columns of ``A`` stay exactly 0.0 through both precision phases.
"""
from __future__ import annotations

import functools

#: float64 polish-tail length (iterations) of the mixed-precision schedule.
POLISH_TAIL = 64

#: iterations per Pallas grid step (state stays in VMEM within a block).
PALLAS_BLOCK = 8

#: the constants ``_fused_step`` reads — the Pallas kernel's inputs.
STEP_KEYS = ("sizes_r", "oh", "E", "Et", "R", "ddl", "s_u", "T_r", "L_r",
             "sig_eq", "sig_mem", "sig_route", "sig_lat", "sig_load",
             "sig_ax", "tau_x", "tau_A", "tau_prec", "tAT", "tAL")


def _f(v, dtype):
    import jax.numpy as jnp

    return jnp.asarray(v, dtype)


def _rows(t, N, H):
    """``(N, U, H)`` per-(BS, user, exit) tensor → ``(N·(H+1), U)`` row
    layout, with a zero exit-0 row per base station."""
    import jax.numpy as jnp

    t = jnp.pad(jnp.swapaxes(t, 1, 2), ((0, 0), (1, 0), (0, 0)))
    return t.reshape(N * (H + 1), t.shape[-1])


def _constants(data, dtype):
    """Precomputed step-size / operator tensors in the fused row layout,
    all cast to ``dtype``.  Pure function of the PDHGData pytree; shared
    verbatim by the scan and Pallas engines."""
    import jax.numpy as jnp

    sizes = _f(data.sizes, dtype)                      # (M, H+1)
    onehot_mu = _f(data.onehot_mu, dtype)              # (U, M)
    T = _f(data.T, dtype)                              # (N, U, H)
    L = _f(data.L, dtype)
    bs_mask = _f(data.bs_mask, dtype)
    N, U, H = T.shape
    M = sizes.shape[0]
    P = H + 1

    u_mask = onehot_mu.sum(-1)                         # 0.0 on padded users
    E = jnp.repeat(jnp.eye(N, dtype=dtype), P, axis=1)   # (N, R) BS blocks
    hmask = (jnp.arange(N * P) % P != 0).astype(dtype)[:, None]  # exit rows
    sizes_r = jnp.tile(sizes.T, (N, 1))                # (R, M)
    T_r, L_r = _rows(T, N, H), _rows(L, N, H)          # (R, U)
    prec_r = _rows(jnp.broadcast_to(_f(data.prec_u, dtype), (N, U, H)),
                   N, H)

    # Pock–Chambolle diagonal step sizes (alpha = 1), exactly the
    # reference's row/column sums
    sig_eq = jnp.full((N, M), 1.0 / (H + 1), dtype)
    sig_mem = 1.0 / jnp.maximum(jnp.full((N, 1), 1.0, dtype) * sizes.sum(),
                                1e-9)
    sig_route = 1.0 / jnp.maximum(
        jnp.ones((1, U), dtype) * bs_mask.sum() * H, 1e-9)
    sig_lat = 1.0 / jnp.maximum(T.sum(axis=(0, 2))[None], 1e-9)
    sig_load = 1.0 / jnp.maximum(L.sum(axis=(0, 2))[None], 1e-9)
    sig_ax = 0.5 * hmask                               # exit-0 duals frozen

    cx = 1.0 + sizes_r + hmask * onehot_mu.sum(0)[None]
    tau_x = 1.0 / jnp.maximum(cx, 1e-9)
    # exit-0 rows, padded BSs and padded users get a zero step, so A
    # stays exactly 0.0 there for the whole solve
    row_mask = jnp.repeat(bs_mask, P)[:, None] * hmask
    tau_A = (row_mask * u_mask[None]) / jnp.maximum(2.0 + T_r + L_r, 1e-9)

    # bs_mask / u_mask / prec_r are read only by the diagnostics sampler
    return dict(sizes_r=sizes_r, oh=onehot_mu.T, E=E, Et=E.T,
                R=_f(data.R, dtype)[:, None],
                ddl=_f(data.ddl, dtype)[None], s_u=_f(data.s_u, dtype)[None],
                T_r=T_r, L_r=L_r,
                sig_eq=sig_eq, sig_mem=sig_mem, sig_route=sig_route,
                sig_lat=sig_lat, sig_load=sig_load, sig_ax=sig_ax,
                tau_x=tau_x, tau_A=tau_A,
                tau_prec=tau_A * prec_r,               # objective gradient
                tAT=tau_A * T_r, tAL=tau_A * L_r,      # folded prox tensors
                bs_mask=bs_mask, u_mask=u_mask, prec_r=prec_r,
                dims=(N, M, H, U))


def _dot(a, b, contract=(1, 0)):
    """2-D GEMM at full precision: on the TPU the default f32 dot takes
    reduced-precision passes, which would break the one-hot identities."""
    import jax

    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=a.dtype)


def _apply_K(c, x, A):
    """The forward operator K in the row layout: per-family residuals of
    (x (R, M), A (R, U))."""
    y_eq = _dot(c["E"], x) - 1.0                                  # (N, M)
    y_mem = _dot(c["E"], x * c["sizes_r"]).sum(-1, keepdims=True) \
        - c["R"]                                                  # (N, 1)
    y_route = A.sum(0, keepdims=True) - 1.0                       # (1, U)
    y_lat = (A * c["T_r"]).sum(0, keepdims=True) - c["ddl"]
    y_load = (A * c["L_r"]).sum(0, keepdims=True) - c["s_u"]
    # one-hot GEMM over M: exactly one term per output, so bit-identical
    # to the gather x[n, m_u, h] it replaces
    return y_eq, y_mem, y_route, y_lat, y_load, A - _dot(x, c["oh"])


def _init_state(data, dtype):
    """The reference's cold start: x = 1/(H+1), A = 0, every dual 0."""
    import jax.numpy as jnp

    c = _constants(data, dtype)
    N, M, H, U = c["dims"]
    x = jnp.full((N * (H + 1), M), 1.0 / (H + 1), dtype)
    A = jnp.zeros((N * (H + 1), U), dtype)
    y = tuple(jnp.zeros_like(v) for v in _apply_K(c, x, A))
    return c, (x, A) + y


def _fused_step(c, state):
    """One PDHG iteration (prox-primal → over-relax → dual ascent) on the
    row layout.  This is the single source of truth both engines
    execute — identical expressions, identical float results."""
    import jax.numpy as jnp

    x, A, y_eq, y_mem, y_route, y_lat, y_load, y_ax = state

    # KT(y) for x: the per-BS duals broadcast to their rows, minus the
    # coupling dual contracted over users (zero on the exit-0 rows)
    gx = _dot(c["Et"], y_eq) + _dot(c["Et"], y_mem) * c["sizes_r"] \
        - _dot(y_ax, c["oh"], contract=(1, 1))                    # (R, M)
    x_new = jnp.clip(x - c["tau_x"] * gx, 0.0, 1.0)
    # routing prox with tau_A folded into the operator tensors; tau_prec
    # carries the (negated) objective gradient
    A_new = jnp.clip(
        A - c["tau_A"] * (y_route + y_ax)
        - c["tAT"] * y_lat - c["tAL"] * y_load + c["tau_prec"], 0.0, 1.0)
    xb = 2 * x_new - x                                           # over-relax
    Ab = 2 * A_new - A
    k_eq, k_mem, k_route, k_lat, k_load, k_ax = _apply_K(c, xb, Ab)
    return (x_new, A_new,
            y_eq + c["sig_eq"] * k_eq,
            jnp.maximum(y_mem + c["sig_mem"] * k_mem, 0.0),
            jnp.maximum(y_route + c["sig_route"] * k_route, 0.0),
            jnp.maximum(y_lat + c["sig_lat"] * k_lat, 0.0),
            jnp.maximum(y_load + c["sig_load"] * k_load, 0.0),
            jnp.maximum(y_ax + c["sig_ax"] * k_ax, 0.0))


def _cast_state(state, dtype):
    import jax.numpy as jnp

    return tuple(jnp.asarray(v, dtype) for v in state)


def _diag_sample(c, state):
    """(primal residual, dual displacement, objective) of the current
    fused state, cast to float64 — the same masked residual contract as
    the reference tap in ``repro.core.lp._pdhg_kernel``, evaluated in
    the row layout.  Pure: never perturbs the carried state.  The
    coupling residual's exit-0 rows are ``-x[n, m_u, 0] <= 0``, so they
    never raise its max above the true one."""
    import jax.numpy as jnp

    f64 = _f64()
    x, A = state[0], state[1]
    y_eq, y_mem, y_route, _, _, y_ax = _apply_K(c, x, A)
    bs = (c["bs_mask"] > 0)[:, None]
    um = (c["u_mask"] > 0)[None]
    r_eq = jnp.max(jnp.where(bs, jnp.abs(y_eq), 0.0))
    r_mem = jnp.max(jnp.where(bs, y_mem, -jnp.inf)) \
        / jnp.maximum(c["R"].max(), 1e-9)
    r_route = jnp.max(jnp.where(um, y_route, -jnp.inf))
    primal = jnp.maximum(
        jnp.maximum(jnp.maximum(r_eq, r_mem),
                    jnp.maximum(r_route, jnp.max(y_ax))), 0.0)
    x2, A2 = _fused_step(c, state)[:2]
    dual = jnp.maximum(jnp.abs(x2 - x).max(), jnp.abs(A2 - A).max())
    obj = (jnp.asarray(A, f64) * jnp.asarray(c["prec_r"], f64)).sum()
    return jnp.asarray(primal, f64), jnp.asarray(dual, f64), obj


def _f64():
    """float64, degraded to float32 when x64 is disabled (matching what
    the reference kernel would silently compute under the same config)."""
    import jax
    import jax.numpy as jnp

    return jax.dtypes.canonicalize_dtype(jnp.float64)


def _finalize(state, dims):
    """Row-layout state → the reference's (x (N,M,H+1), A (N,U,H))
    float64."""
    import jax.numpy as jnp

    N, M, H, U = dims
    x = state[0].reshape(N, H + 1, M)
    A = state[1].reshape(N, H + 1, U)[:, 1:]
    return (jnp.swapaxes(jnp.asarray(x, _f64()), 1, 2),
            jnp.swapaxes(jnp.asarray(A, _f64()), 1, 2))


# ---------------------------------------------------------------------------
# engine: lax.scan (the XLA realization; production path off-TPU, and the
# float64 polish everywhere)
# ---------------------------------------------------------------------------

def _scan_phase(data, state, iters, dtype):
    import jax

    c = _constants(data, dtype)

    def body(carry, _):
        return _fused_step(c, carry), None

    state, _ = jax.lax.scan(body, _cast_state(state, dtype), None,
                            length=int(iters))
    return state


# ---------------------------------------------------------------------------
# engine: Pallas (state resident in VMEM across an iteration block)
# ---------------------------------------------------------------------------

def _pallas_phase(data, state, iters, dtype, block=PALLAS_BLOCK,
                  interpret=None):
    """``iters`` fused iterations as Pallas grid steps of ``block``
    iterations each.  The eight state tensors live in VMEM scratch for the
    whole call: loaded from the inputs at grid step 0, advanced in-place
    ``block`` steps per grid step, and emitted on the last step — one
    kernel invocation per iteration block, zero HBM round-trips inside.

    The kernel body executes ``_fused_step`` verbatim; output matches
    ``_scan_phase`` at the same dtype up to FMA contraction (dtype ulp
    per step, asserted in interpret mode by tests/test_pdhg_fused.py).
    Every operand is 2-D and whole-array, so under ``vmap`` each window
    is one more (outer) grid step."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"

    iters = int(iters)
    if iters <= 0:
        return _cast_state(state, dtype)
    block = max(1, min(int(block), iters))
    n_blocks, rem = divmod(iters, block)

    c = _constants(data, dtype)
    state = _cast_state(state, dtype)
    shapes = [jax.ShapeDtypeStruct(v.shape, v.dtype) for v in state]
    n_state = len(state)
    consts = [c[k] for k in STEP_KEYS]

    def run(state, n_steps, n_blk):
        def kernel(*refs):
            in_refs = refs[:n_state + len(consts)]
            out_refs = refs[n_state + len(consts):
                            n_state + len(consts) + n_state]
            scratch = refs[n_state + len(consts) + n_state:]
            cc = {k: v[...] for k, v in zip(STEP_KEYS, in_refs[n_state:])}

            j = pl.program_id(0)

            @pl.when(j == 0)
            def _load():
                for s, r in zip(scratch, in_refs[:n_state]):
                    s[...] = r[...]

            cur = tuple(s[...] for s in scratch)
            for _ in range(n_steps):
                cur = _fused_step(cc, cur)
            for s, v in zip(scratch, cur):
                s[...] = v

            @pl.when(j == n_blk - 1)
            def _emit():
                for o, s in zip(out_refs, scratch):
                    o[...] = s[...]

        def whole(j):
            # int32 block indices: the offline pipeline traces under x64,
            # and Mosaic takes no int64 index
            return jnp.int32(0), jnp.int32(0)

        return pl.pallas_call(
            kernel,
            grid=(n_blk,),
            in_specs=[pl.BlockSpec(v.shape, whole)
                      for v in list(state) + consts],
            out_specs=[pl.BlockSpec(s.shape, whole) for s in shapes],
            out_shape=shapes,
            scratch_shapes=[_vmem(v.shape, v.dtype) for v in state],
            interpret=interpret,
        )(*state, *consts)

    if n_blocks:
        state = tuple(run(state, block, n_blocks))
    if rem:
        state = tuple(run(state, rem, 1))
    return state


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)


# ---------------------------------------------------------------------------
# public entry point
# ---------------------------------------------------------------------------

def pdhg_fused(data, iters: int, polish: int = POLISH_TAIL,
               engine: str = "auto", block: int = PALLAS_BLOCK,
               interpret=None, diagnostics: bool = False,
               diag_stride: int = 50):
    """The fused mixed-precision PDHG solve of one (padded) window.

    Runs ``iters - polish`` float32 sweep iterations then ``polish``
    float64 iterations of the same fused step, and returns float64
    ``(x (N,M,H+1), A (N,U,H))`` in the reference layout.  ``engine``
    picks the realization of the float32 sweep (the float64 polish always
    runs on the scan engine):

      * ``"auto"``  — Pallas on TPU, ``lax.scan`` elsewhere (the fast
        realization per platform; both run the identical step);
      * ``"scan"``  — force the XLA scan realization;
      * ``"pallas"`` — force the Pallas kernel (interpret mode is
        auto-selected off-TPU, or pass ``interpret=`` explicitly).

    ``diagnostics=True`` re-expresses each precision phase as the same
    phase calls segmented at ``diag_stride`` boundaries (pure function
    composition — the scan engine composes bit-exactly, which
    tests/test_obs.py asserts; the Pallas engine is exact whenever
    ``diag_stride`` is a multiple of ``block``, else remainder blocks
    compile separately and may regroup FMAs at dtype-ulp scale) and
    returns ``(x, A, diag)`` where ``diag`` carries float64 residual /
    objective curves plus ``polish_delta``, the max coordinate movement
    of the f32→f64 polish tail.

    Traceable (jit/vmap-safe) for fixed static ``iters``/``polish``.
    """
    import jax

    if engine == "auto":
        engine = "pallas" if jax.devices()[0].platform == "tpu" else "scan"
    if engine not in ("scan", "pallas"):
        raise ValueError(f"unknown engine {engine!r}; "
                         "one of ('auto', 'scan', 'pallas')")
    import jax.numpy as jnp

    iters = int(iters)
    polish = max(0, min(int(polish), iters))
    sweep = iters - polish

    phase = _scan_phase if engine == "scan" else functools.partial(
        _pallas_phase, block=block, interpret=interpret)

    f64 = _f64()
    c64 = _constants(data, f64)
    if not diagnostics:
        if sweep:
            _, state = _init_state(data, jnp.float32)
            state = phase(data, state, sweep, jnp.float32)
        else:
            _, state = _init_state(data, f64)
        state = _scan_phase(data, state, polish, f64)
        return _finalize(state, c64["dims"])

    stride = max(1, int(diag_stride))
    samples = []  # (sampled iteration, primal, dual, obj)
    if sweep:
        c32 = _constants(data, jnp.float32)
        _, state = _init_state(data, jnp.float32)
        n1, r1 = divmod(sweep, stride)
        for s in range(n1):
            state = phase(data, state, stride, jnp.float32)
            samples.append(((s + 1) * stride,) + _diag_sample(c32, state))
        if r1:
            state = phase(data, state, r1, jnp.float32)
            samples.append((sweep,) + _diag_sample(c32, state))
    else:
        _, state = _init_state(data, f64)
    x_sw, A_sw = _finalize(state, c64["dims"])
    n2, r2 = divmod(polish, stride)
    for s in range(n2):
        state = _scan_phase(data, state, stride, f64)
        samples.append((sweep + (s + 1) * stride,) + _diag_sample(c64, state))
    # unconditional, mirroring the diag-off path: a zero-length phase
    # call still applies the f64 cast
    state = _scan_phase(data, state, r2, f64)
    if r2 or not samples:
        samples.append((iters,) + _diag_sample(c64, state))
    x, A = _finalize(state, c64["dims"])
    polish_delta = jnp.maximum(jnp.abs(x - x_sw).max(),
                               jnp.abs(A - A_sw).max())
    diag = {"iters": jnp.asarray([s[0] for s in samples], jnp.int32),
            "primal_res": jnp.stack([s[1] for s in samples]),
            "dual_res": jnp.stack([s[2] for s in samples]),
            "obj": jnp.stack([s[3] for s in samples]),
            "polish_delta": polish_delta}
    return x, A, diag


def fused_vs_reference_gap(data, iters: int, polish: int = POLISH_TAIL):
    """Max abs fractional gap between the fused scan solve and the f64
    reference — the number the bench reports next to the decision gap."""
    import jax.numpy as jnp

    from repro.core import lp as LP

    x_r, A_r = LP._pdhg_kernel(data, iters)
    x_f, A_f = pdhg_fused(data, iters, polish=polish, engine="scan")
    return float(jnp.maximum(jnp.abs(x_f - x_r).max(),
                             jnp.abs(A_f - A_r).max()))
