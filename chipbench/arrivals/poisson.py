"""Poisson arrivals at ``rate_per_s`` (arrival arithmetic as in the program's
``core/workload.py`` ``poisson``: exponential gaps of mean 1 / rate).

The gaps are the stratified quantiles of the exponential distribution in
the order ``rng`` draws, so that a span carries the same amount of work
whatever the order."""
import numpy as np


def count(p: dict, span_s: float) -> int:
    """Requests due in a span of ``span_s`` seconds."""
    return int(round(p["rate_per_s"] * span_s))


def times(p: dict, n: int, span_s: float, rng: np.random.Generator) -> np.ndarray:
    """n due times in [0, span_s): the cumulative sums of n + 1 permuted gaps,
    scaled so the n + 1 gaps fill the span exactly."""
    if n == 0:
        return np.zeros(0)
    u = (np.arange(n + 1) + 0.5) / (n + 1)
    gaps = rng.permutation(-np.log1p(-u) / p["rate_per_s"])
    t = np.cumsum(gaps)
    return t[:n] * (span_s / t[-1])
