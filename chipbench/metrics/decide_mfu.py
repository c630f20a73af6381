"""The decisions' share of the chip's peak, in %: the operations a
CoCaR window asks for, counted from its sizes by :func:`window_flops`,
times the decisions completed, over the window's wall time, the chips
and the bf16 peak of ``peaks.json`` (the only published FLOP peak; the
decisions run in float64, which has none).  The count is of the work the
configuration asks for, whatever implements it."""
from chipbench import harness as H


def pdhg_iteration_flops(N: int, U: int, M: int, H_: int) -> int:
    """Floating-point operations of one PDHG step of P1-LR on x
    (N, M, H+1) and A (N, U, H), counting each add, multiply, compare
    and clip bound as one:

    * K(x, A): one-submodel sums NM(H+1); memory 2NM(H+1); route NUH;
      latency and load 2NUH each; A - x 1NUH;
    * K^T(y): 2NM(H+1) for the equality and memory rows, NUH to gather
      the A <= x duals back to (n, m, h), 7NUH for the A gradient;
    * the primal steps: x 4NM(H+1) (multiply, subtract, two clip
      bounds), A 5NUH; the extrapolations 2NM(H+1) + 2NUH;
    * the dual steps: two per dual entry plus one projection, over
      NM + N + 3U + NUH entries.
    """
    X, A = N * M * (H_ + 1), N * U * H_
    k = X + 2 * X + A + 2 * A + 2 * A + A
    kt = 2 * X + A + 7 * A
    primal = 4 * X + 5 * A + 2 * X + 2 * A
    dual = 3 * (N * M + N + 3 * U + A)
    return k + kt + primal + dual


def window_flops(N: int, U: int, M: int, H_: int, iters: int,
                 best_of: int) -> int:
    """One window: ``iters`` PDHG steps plus, per rounding trial, the
    draw (4 operations per routing entry and 2H+2 per caching row) and
    the repair's latency and load sums and route choice (6 per routing
    entry)."""
    A = N * U * H_
    trial = 4 * A + (2 * H_ + 2) * N * M + 6 * A
    return iters * pdhg_iteration_flops(N, U, M, H_) + best_of * trial


def read(ctx, state):
    n = ctx.counters.get("decisions", 0)
    if not n:
        return None
    c = ctx.config
    flops = n * window_flops(c["n_bs"], c["n_users"], c["n_models"],
                             len(c["catalog"]["submodels"]),
                             c["pdhg_iters"], c["best_of"])
    chips = int(ctx.cell["chips"])
    return 100.0 * flops / (ctx.window_seconds * chips * H.peak(
        ctx.device_kind, "bf16_flops_per_s"))
