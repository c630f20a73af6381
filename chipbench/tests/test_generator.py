"""The traffic generator: deterministic by seed, at its stated sizes and
popularity, with the same amount of work for every seed."""
import numpy as np

from chipbench import generator as G

CFG = {"n_bs": 5, "n_models": 8, "n_users": 600, "window_s": 3.0,
       "zipf": 0.8}


def test_zipf_windows_deterministic_sizes_and_popularity():
    w1 = G.ZipfWindows({}, CFG, 5)
    w2 = G.ZipfWindows({}, CFG, 5)
    for _ in range(3):
        a, b = w1.next(), w2.next()
        for x, y in zip(a, b):
            assert np.array_equal(x, y)
        m_u, home, s_u = a
        assert len(m_u) == len(home) == len(s_u) == 600
        assert home.min() >= 0 and home.max() < 5
        assert s_u.min() >= 0 and s_u.max() < 3.0
    # popularity: Zipf(0.8) over 8 models, ranks permuted by the seed
    w = G.ZipfWindows({}, CFG, 9)
    counts = np.zeros(8)
    for _ in range(200):
        counts += np.bincount(w.next()[0], minlength=8)
    freq = np.sort(counts / counts.sum())[::-1]
    want = 1.0 / np.arange(1, 9) ** 0.8
    assert np.allclose(freq, want / want.sum(), atol=0.005)


def test_zipf_popularity_changes_every_k_windows():
    w = G.ZipfWindows({"popularity_change_every": 2}, CFG, 3)
    pops = []
    for _ in range(5):
        w.next()
        pops.append(w.pop.copy())
    assert np.array_equal(pops[0], pops[1])
    assert not np.array_equal(pops[1], pops[2])
    assert np.array_equal(np.sort(pops[0]), np.sort(pops[2]))
