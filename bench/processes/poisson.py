"""Poisson arrivals at a fixed rate, conditioned on their count.

    {"process": "poisson", "rate_per_s": 144.0}

`round(rate_per_s * seconds)` arrivals at sorted uniform times over the
window: a Poisson process given its count, so every seed offers the same
amount of work in another order.
"""
import numpy as np


def times(params: dict, seconds: float, rng: np.random.Generator):
    rate = float(params["rate_per_s"])
    if not rate > 0:
        raise ValueError("rate_per_s must be > 0")
    extra = set(params) - {"rate_per_s"}
    if extra:
        raise ValueError(f"unknown poisson parameters {sorted(extra)}")
    return np.sort(rng.uniform(0.0, seconds, int(round(rate * seconds))))
