"""The one traffic generator.  A mix is a data file under ``traffic/``;
it names the driver that feeds it to the program, and the driver draws
its requests with a class below, which reads the mix's parameters:
``ZipfWindows`` for observation windows of the control plane,
``PoissonArrivals`` for requests served as they arrive.  Everything is
drawn from the seed it is given, and every seed gives the same sizes, so
that a seed changes what is asked and not how much.

``ZipfWindows`` is the benchmark's own copy of the program's seeded
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


class PoissonArrivals:
    """Open-loop requests over a window of ``seconds``: arrivals at the
    mix's ``rate_per_s``, each a prompt of ``prompt_tokens`` ids drawn
    uniformly from ``[1, vocab)`` and a home pod drawn uniformly.

    The gaps between arrivals are the ``n = round(rate * seconds)``
    midpoint quantiles of the exponential distribution of that rate,
    scaled so that the last arrival falls half a mean gap before the
    window's end, in an order drawn from the mix's ``schedule_seed``.  So
    every run offers the same Poisson-like schedule, and its seed draws
    the prompts and homes: a seed changes what is asked, not when.  An
    order drawn from the run's seed would change how much the window
    queues: the 90th percentile of the serving cell's reply time then
    spreads by a third over six seeds on the chip."""

    def __init__(self, mix: dict, vocab: int, n_homes: int, seconds: float,
                 seed: int):
        rate = float(mix["rate_per_s"])
        n = max(1, int(round(rate * seconds)))
        q = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-q)
        gaps *= seconds * (n - 0.5) / n / gaps.sum()
        order = np.random.default_rng(int(mix["schedule_seed"])).permutation(n)
        self.at = np.cumsum(gaps[order])
        rng = np.random.default_rng(seed)
        self.prompts = rng.integers(1, vocab, size=(n, int(
            mix["prompt_tokens"])), dtype=np.int32)
        self.homes = rng.integers(0, n_homes, size=n)

    def __len__(self):
        return len(self.at)
