"""Causal flash attention (GQA) — Pallas TPU kernel.

Blockwise online-softmax with *causal block skipping*: fully-masked
(q-block, kv-block) pairs are predicated off, so the quadratic masked waste
of the jnp reference path disappears (~2x FLOPs), and probabilities never
leave VMEM — removing the dominant HBM term of the baseline roofline.

Grid: (B, H, n_q_blocks, n_kv_blocks), kv innermost; the (acc, m, l)
scratch carries across the sequential kv dimension. Block shapes are
MXU-aligned (bq x hd, bkv x hd).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *, bq, bkv, scale, n_kv):
    iq = pl.program_id(2)
    ikv = pl.program_id(3)

    @pl.when(ikv == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * bq
    kv_start = ikv * bkv

    @pl.when(kv_start <= q_start + bq - 1)  # causal block skipping
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)            # (bkv, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                       # (bq, bkv)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        kpos = kv_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)

        m_prev = m_ref[...]                             # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)                  # (bq, 1)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ikv == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bkv", "interpret"))
def flash_attention_bhsd(
    q: jax.Array,  # (B, H, S, hd)
    k: jax.Array,  # (B, KV, S, hd)
    v: jax.Array,
    bq: int = 128,
    bkv: int = 128,
    *,
    interpret: bool,
) -> jax.Array:
    B, H, S, hd = q.shape
    KV = k.shape[1]
    G = H // KV
    assert S % bq == 0 and S % bkv == 0, (S, bq, bkv)
    n_q = S // bq
    n_kv = S // bkv
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(_kernel, bq=bq, bkv=bkv, scale=scale, n_kv=n_kv)
    return pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ikv: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bkv, hd), lambda b, h, iq, ikv: (b, h // G, ikv, 0)),
            pl.BlockSpec((1, 1, bkv, hd), lambda b, h, iq, ikv: (b, h // G, ikv, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ikv: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),   # output accumulator
            pltpu.VMEM((bq, 1), jnp.float32),    # running max
            pltpu.VMEM((bq, 1), jnp.float32),    # normalizer
        ],
        interpret=interpret,
    )(q, k, v)
