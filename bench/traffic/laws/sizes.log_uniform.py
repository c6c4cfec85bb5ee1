"""Request sizes in [``size_min``, ``size_max``], log-uniform:
P(size <= s) = log(s + 1 / lo) / log(hi + 1 / lo), so each octave of
sizes is equally likely; taken by their quantiles, so every seed gets the
same multiset of sizes, in its own order."""
import numpy as np


def draw(mix: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    lo, hi = int(mix["size_min"]), int(mix["size_max"])
    q = (np.arange(n) + 0.5) / n
    sizes = np.floor(np.exp(np.log(lo) + q * (np.log(hi + 1) - np.log(lo))))
    return rng.permutation(np.clip(sizes, lo, hi).astype(np.int64))
