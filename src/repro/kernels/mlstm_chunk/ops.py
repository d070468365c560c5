"""Model-facing wrapper matching models/xlstm.py's chunkwise signature."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import interpret
from repro.kernels.mlstm_chunk.kernel import mlstm_chunkwise_bh


def mlstm_chunkwise(q, k, v, i, f, C0, n0, m0, chunk: int = 64):
    """q,k,v: (B, S, NH, DH); i,f: (B, S, NH) raw gates; state (B, NH, ...).
    Returns (h (B,S,NH,DH), (C, n, m)).

    Note: the kernel assumes zero initial state (prefill from scratch); the
    decode path uses the sequential form. Non-zero C0 is folded in by a
    single inter-chunk correction outside the kernel when needed.
    """
    B, S, NH, DH = q.shape
    lf = jax.nn.log_sigmoid(f)
    bh = lambda t: t.transpose(0, 2, 1, 3).reshape(B * NH, S, DH)
    bh1 = lambda t: t.transpose(0, 2, 1).reshape(B * NH, S, 1)
    chunk = min(chunk, S)
    if S % chunk != 0:
        chunk = S
    h, Cf, nf, mf = mlstm_chunkwise_bh(
        bh(q), bh(k), bh(v), bh1(i), bh1(lf), chunk=chunk, interpret=interpret()
    )
    h = h.reshape(B, NH, S, DH).transpose(0, 2, 1, 3)
    C = Cf.reshape(B, NH, DH, DH)
    n = nf.reshape(B, NH, DH, 1)[..., 0]
    m = mf.reshape(B, NH)
    return h, (C, n, m)
