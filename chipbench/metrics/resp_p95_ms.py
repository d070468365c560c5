"""Response time, 95th percentile over the window's finished requests: the
router's finish stamp minus the time the request was due (ms)."""
import numpy as np


def read(obs):
    v = [r["finish_t"] - r["due"] for r in obs["requests"] if r["done"]]
    return 1e3 * float(np.percentile(v, 95)) if v else None
