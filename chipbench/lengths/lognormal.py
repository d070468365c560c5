"""Lognormal lengths, clipped: ``median``, ``sigma``, ``min``, ``max``."""
from statistics import NormalDist

import numpy as np


def quantiles(p: dict, n: int) -> np.ndarray:
    """The n stratified quantiles (i + 0.5) / n of the distribution, so that
    every seed draws the same set of lengths in another order."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(np.log(p["median"]) + p["sigma"] * z)
    return np.clip(np.rint(x), p["min"], p["max"]).astype(np.int64)
