"""Gap between tokens, 95th percentile over every gap between consecutive
tokens of any request whose later token was stamped between the window's
opening and the end of the run's measurement (ms)."""
import numpy as np


def read(obs):
    t0, t1 = obs["t0"], obs["t_end"]
    v = [b - a for ts in obs["stamps"] for a, b in zip(ts, ts[1:]) if t0 <= b <= t1]
    return 1e3 * float(np.percentile(v, 95)) if v else None
