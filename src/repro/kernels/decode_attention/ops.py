"""Model-facing wrapper: (B, 1, H, hd) q + (B, T, KV, hd) cache layout."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret
from repro.kernels.decode_attention.kernel import decode_attention_grouped


def decode_attention(q, k, v, cache_len, bt: int = 128):
    """q: (B, S=1, H, hd); k/v: (B, T, KV, hd); cache_len: scalar or (B,)."""
    B, S, H, hd = q.shape
    T = k.shape[1]
    KV = k.shape[2]
    G = H // KV
    lens = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (B,))
    bt = min(bt, T)
    pad = (-T) % bt
    kk = k.transpose(0, 2, 1, 3)
    vv = v.transpose(0, 2, 1, 3)
    if pad:
        kk = jnp.pad(kk, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vv = jnp.pad(vv, ((0, 0), (0, 0), (0, pad), (0, 0)))
    qg = q[:, 0].reshape(B, KV, G, hd)
    o = decode_attention_grouped(qg, kk, vv, lens, bt=bt, interpret=interpret())
    return o.reshape(B, 1, H, hd)
