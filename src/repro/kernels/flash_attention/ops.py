"""Model-facing wrapper: (B, S, H, hd) layout + padding + layout swap."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret
from repro.kernels.flash_attention.kernel import flash_attention_bhsd


def flash_attention(q, k, v, pos_q=None, pos_k=None, bq: int = 128, bkv: int = 128):
    """q: (B, S, H, hd); k/v: (B, S, KV, hd) -> (B, S, H, hd). Standard
    causal positions (the model's train/prefill path)."""
    B, S, H, hd = q.shape
    bq = min(bq, S)
    bkv = min(bkv, S)
    pad = (-S) % bq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    o = flash_attention_bhsd(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        bq=bq,
        bkv=bkv,
        interpret=interpret(),
    )
    o = o.transpose(0, 2, 1, 3)
    return o[:, :S] if pad else o
