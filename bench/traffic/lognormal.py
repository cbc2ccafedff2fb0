"""Log-normal lengths with ``median`` and ``sigma``, clipped to
``min``..``max`` and rounded up to a ``multiple``.

``lengths(spec, n)`` gives ``n`` lengths at the distribution's quantiles
(i + 1/2) / n: the same multiset for every seed, which the generator then
orders by the seed.
"""
from statistics import NormalDist

import numpy as np


def lengths(spec: dict, n: int) -> np.ndarray:
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    m = int(spec.get("multiple", 1))
    lo, hi = int(spec["min"]), int(spec["max"])
    out = np.ceil(np.clip(raw, lo, hi) / m).astype(np.int64) * m
    return np.clip(out, -(-lo // m) * m, hi // m * m)
