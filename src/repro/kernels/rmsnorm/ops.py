"""Model-facing wrapper: arbitrary leading dims + row padding."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret
from repro.kernels.rmsnorm.kernel import rmsnorm_2d

SUBLANES = 8        # a TPU row block must be a multiple of this
BLOCK_ROWS = 256


def rmsnorm(x, w, eps: float = 1e-6):
    """RMSNorm over the last axis. Rows are padded up to a multiple of the
    row block (itself a multiple of 8, as Mosaic requires) and the padding
    is sliced off again, so any row count compiles — the decode batch
    normalises ``max_slots`` rows and speculative verify ``slots*(k+1)``."""
    shape = x.shape
    d = shape[-1]
    rows = 1
    for s in shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    block = min(BLOCK_ROWS, -(-rows // SUBLANES) * SUBLANES)
    pad = (-rows) % block
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    out = rmsnorm_2d(x2, w, eps=eps, block_rows=block, interpret=interpret())
    return (out[:rows] if pad else out).reshape(shape)
