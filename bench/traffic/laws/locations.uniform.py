"""Points uniform over the domain's box ((x0, y0), (x1, y1))."""
import numpy as np


def draw(mix: dict, bounds, n: int, rng: np.random.Generator) -> np.ndarray:
    lo, hi = (np.asarray(b, np.float64) for b in bounds)
    return rng.uniform(lo, hi, (n, 2)).astype(np.float32)
