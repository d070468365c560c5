"""Lengths uniform on the integers ``min`` .. ``max``."""
import numpy as np


def quantiles(p: dict, n: int) -> np.ndarray:
    """The n stratified quantiles (i + 0.5) / n, rounded to whole tokens."""
    u = (np.arange(n) + 0.5) / n
    return np.rint(p["min"] + u * (p["max"] - p["min"])).astype(np.int64)
