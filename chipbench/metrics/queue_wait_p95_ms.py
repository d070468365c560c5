"""Router queue wait, 95th percentile over the window's finished requests:
``Request.start_t - arrival_t``, the program's own stamps (ms)."""
import numpy as np


def read(obs):
    v = [r["start_t"] - r["arrival_t"] for r in obs["requests"]
         if r["done"] and r["start_t"] is not None]
    return 1e3 * float(np.percentile(v, 95)) if v else None
