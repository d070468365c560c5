"""Time to first token, 95th percentile over the window's finished requests:
the first token's stamp minus the time the request was due (ms). Read in
the traced run: below the knee it swings with host stalls of a tenth of a
second to seconds, too widely for an end-to-end bound."""
import numpy as np


def read(obs):
    v = [r["token_times"][0] - r["due"] for r in obs["requests"] if r["done"] and r["token_times"]]
    return 1e3 * float(np.percentile(v, 95)) if v else None
