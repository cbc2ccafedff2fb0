"""Poisson arrivals at ``rate_per_s``.

``gaps(mix, span, rng)`` gives the inter-arrival gaps of a segment of
``span`` seconds: ``round(rate_per_s * span)`` gaps at the exponential's
quantiles (i + 1/2) / n, scaled to fill the segment exactly, in an order
drawn from ``rng``.  Every seed gets the same gaps in another order.
"""
import numpy as np


def quantile_gaps(n: int, span: float) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    return g * (span / g.sum())


def gaps(mix: dict, span: float, rng: np.random.Generator) -> np.ndarray:
    n = int(round(float(mix["rate_per_s"]) * span))
    return rng.permutation(quantile_gaps(n, span)) if n > 0 else np.zeros(0)
