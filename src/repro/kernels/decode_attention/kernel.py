"""Fused GQA decode attention — Pallas TPU kernel (flash-decoding style).

One new token per sequence attends over a long KV cache. Grid
(B, KV, n_t_blocks): each step streams one (bt, hd) KV block through VMEM,
updating an online-softmax accumulator for the G query heads that share the
KV head. Per-sequence valid length arrives via scalar prefetch so padded /
short slots mask correctly (continuous batching).

VMEM working set per step: G x hd (q) + 2 x bt x hd (k, v) + G x hd f32
accumulator — independent of cache length.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *, bt, n_t, scale):
    b = pl.program_id(0)
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    valid = len_ref[b]                                  # scalar int32
    t_start = it * bt

    @pl.when(t_start < valid)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)             # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)             # (bt, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                        # (G, bt)
        tpos = t_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(tpos < valid, s, NEG_INF)
        m_prev = m_ref[...]                              # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(it == n_t - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def decode_attention_grouped(
    q: jax.Array,        # (B, KV, G, hd) — one token per sequence
    k: jax.Array,        # (B, KV, T, hd)
    v: jax.Array,
    lengths: jax.Array,  # (B,) int32 valid prefix per sequence
    bt: int = 128,
    *,
    interpret: bool,
) -> jax.Array:
    B, KV, G, hd = q.shape
    T = k.shape[2]
    assert T % bt == 0, (T, bt)
    n_t = T // bt
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(_kernel, bt=bt, n_t=n_t, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KV, n_t),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, it, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bt, hd), lambda b, h, it, lens: (b, h, it, 0)),
            pl.BlockSpec((1, 1, bt, hd), lambda b, h, it, lens: (b, h, it, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, it, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, hd), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(lengths, q, k, v)
