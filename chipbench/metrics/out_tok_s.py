"""Output tokens stamped inside the window, of any request, over the window's
length (tokens/s)."""


def read(obs):
    t0, close = obs["t0"], obs["close"]
    n = sum(1 for ts in obs["stamps"] for t in ts if t0 <= t < close)
    return n / (close - t0) if n else None
