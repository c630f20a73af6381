"""The exact percentile and the operation counts behind
``decide_mfu``, against hand computations."""
import numpy as np
import pytest

from chipbench import harness as H


@pytest.mark.parametrize("q", [0.0, 0.25, 0.5, 0.95, 1.0])
def test_quantile_matches_numpy_linear(q):
    v = np.random.default_rng(3).exponential(size=37)
    assert H.quantile(v, q) == pytest.approx(np.quantile(v, q), rel=1e-12)


def test_quantile_by_hand():
    # ranks 0..4; p95 sits at 3.8: 40 + 0.8 * (50 - 40)
    assert H.quantile([10, 50, 20, 40, 30], 0.95) == pytest.approx(48.0)
    assert H.quantile([7], 0.95) == 7.0


def test_pdhg_iteration_flops_by_hand():
    mod = H.load_module("metrics", "decide_mfu")
    N, U, M, Hh = 2, 3, 2, 1
    X, A = N * M * (Hh + 1), N * U * Hh          # 8, 6
    k = 3 * X + 6 * A                            # 24 + 36
    kt = 2 * X + 8 * A                           # 16 + 48
    primal = 6 * X + 7 * A                       # 48 + 42
    dual = 3 * (N * M + N + 3 * U + A)           # 3 * (4 + 2 + 9 + 6)
    assert mod.pdhg_iteration_flops(N, U, M, Hh) == k + kt + primal + dual
    assert mod.pdhg_iteration_flops(N, U, M, Hh) == 277


def test_window_flops_by_hand():
    mod = H.load_module("metrics", "decide_mfu")
    it = mod.pdhg_iteration_flops(5, 600, 8, 3)
    trial = 10 * 5 * 600 * 3 + 8 * 5 * 8
    assert mod.window_flops(5, 600, 8, 3, 4000, 8) == 4000 * it + 8 * trial


def test_peaks_known_and_unknown_device():
    assert H.peak("TPU v5 lite", "bf16_flops_per_s") == 197e12
    with pytest.raises(KeyError):
        H.peak("TPU v9 imaginary", "bf16_flops_per_s")


def test_sub_seed_stable_and_31_bit():
    a = H.sub_seed(2**40 + 3, "traffic")
    assert a == H.sub_seed(2**40 + 3, "traffic")
    assert a != H.sub_seed(2**40 + 3, "weights")
    assert 0 <= a < 2**31
