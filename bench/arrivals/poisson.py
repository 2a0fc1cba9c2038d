"""Open-loop Poisson arrivals at ``rate_per_s``.

The gaps are the quantiles of the exponential distribution, so every seed
offers the same set of gaps (the same count and total) in an order drawn
from the seed."""
import numpy as np


def gaps(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    rate = float(traffic["rate_per_s"])
    m = max(1, int(round(rate * seconds)))
    g = -np.log1p(-(np.arange(m) + 0.5) / m) / rate
    return np.random.default_rng([seed % 2 ** 63, 0xa77]).permutation(g)
