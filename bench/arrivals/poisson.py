"""A Poisson process at the mix's rate: the gaps are the quantiles of an
exponential, in a seeded order, so every seed offers the same gaps."""

import numpy as np

from bench.generator import seed_words


def offsets(n: int, rate: float, seed: int, traffic: dict) -> np.ndarray:
    """Due times (seconds from the window's start) of ``n`` arrivals at
    ``rate`` per second."""
    del traffic
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    rng = np.random.default_rng(seed_words(seed, "arrivals"))
    return np.cumsum(gaps[rng.permutation(n)])
