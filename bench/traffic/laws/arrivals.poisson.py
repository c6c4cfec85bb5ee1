"""Poisson arrivals at the mix's ``rate_per_s``: exponential gaps, taken
by their quantiles so that every seed gets the same multiset of gaps, in
its own order. The first request is due at the window's start."""
import numpy as np


def draw(mix: dict, seconds: float, rng: np.random.Generator) -> np.ndarray:
    rate = float(mix["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q) / rate)
    return np.cumsum(gaps) - gaps[0]
