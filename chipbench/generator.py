"""The one traffic generator.  A mix is a data file under ``traffic/``;
it names the driver that feeds it to the program, and the driver draws
its requests with the class below, which reads the mix's parameters.  Everything is drawn from the seed it is given, and
every seed gives the same sizes, so that a seed changes what is asked
and not how much.

The arithmetic is the benchmark's own copy of the program's seeded
traffic (``Scenario.draw_requests``, ``zipf_popularity``), so that a
change to the program cannot change the traffic it is judged on.
"""
from __future__ import annotations

import numpy as np


def zipf_popularity(n: int, a: float, rng) -> np.ndarray:
    """Zipf(a) over ``n`` items, ranks assigned by a permutation."""
    p = np.ones(n) if a <= 0 else 1.0 / np.arange(1, n + 1) ** a
    p = p / p.sum()
    return p[rng.permutation(n)]


class ZipfWindows:
    """Observation windows of the MEC deployment: each holds
    ``n_users`` requests, the model of each drawn from the Zipf
    popularity, its home BS uniform, its start time uniform in the
    window.  Popularity is permuted anew every ``popularity_change_every``
    windows (0: never)."""

    def __init__(self, mix: dict, cfg: dict, seed: int):
        self.rng = np.random.default_rng(seed)
        self.N, self.M = cfg["n_bs"], cfg["n_models"]
        self.U, self.window_s = cfg["n_users"], cfg["window_s"]
        self.change = int(mix.get("popularity_change_every", 0))
        self.pop = zipf_popularity(self.M, cfg["zipf"], self.rng)
        self.k = 0

    def next(self):
        """(m_u, home, s_u) of the next window."""
        if self.change and self.k and self.k % self.change == 0:
            self.pop = self.pop[self.rng.permutation(self.M)]
        self.k += 1
        m_u = self.rng.choice(self.M, size=self.U, p=self.pop)
        home = self.rng.integers(0, self.N, size=self.U)
        s_u = self.rng.uniform(0.0, self.window_s, size=self.U)
        return m_u, home, s_u

