"""Chunkwise mLSTM — Pallas TPU kernel.

The xLSTM matrix-memory cell in its chunkwise-parallel form: grid
(B*NH, n_chunks), chunks sequential; the (C, n, m) recurrent state lives in
VMEM scratch and carries across the chunk dimension, so the (DH x DH) matrix
memory never round-trips HBM between chunks (the CUDA kernels of the xLSTM
paper keep it in SMEM; VMEM is the TPU analogue — DESIGN.md §2).

Per chunk: two (L x L) MXU matmuls (intra-chunk attention-like term) + two
(L x DH) x (DH x DH) matmuls (inter-chunk via C), all stabilized in f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, i_ref, lf_ref, h_ref, Cf_ref, nf_ref, mf_ref,
            C_ref, n_ref, m_ref, *, L, DH, n_chunks):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        C_ref[...] = jnp.zeros_like(C_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.zeros_like(m_ref)

    q = q_ref[0].astype(jnp.float32)                    # (L, DH) — caller pre-scales
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    it = i_ref[0].astype(jnp.float32)                    # (L, 1)
    lf = lf_ref[0].astype(jnp.float32)                   # (L, 1)

    cum = jnp.cumsum(lf, axis=0)                         # (L, 1) inclusive
    total = cum[L - 1:L, :]                              # (1, 1)
    m0 = m_ref[0, 0]

    # intra-chunk decay D_ij = cum_i - cum_j + i_j (j <= i)
    Dm = cum - cum.reshape(1, L) + it.reshape(1, L)      # (L, L)
    tri = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) >= jax.lax.broadcasted_iota(
        jnp.int32, (L, L), 1
    )
    Dm = jnp.where(tri, Dm, NEG)

    g = cum + m0                                         # (L, 1)
    m_row = jnp.maximum(jnp.max(Dm, axis=-1, keepdims=True), g)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    s = s * jnp.exp(Dm - m_row)                          # (L, L)
    inter = jnp.exp(g - m_row)                           # (L, 1)
    num = jax.lax.dot_general(s, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    num = num + inter * jax.lax.dot_general(
        q, C_ref[...], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    den = jnp.sum(s, axis=-1, keepdims=True) + inter * jax.lax.dot_general(
        q, n_ref[...], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    h = num / jnp.maximum(jnp.abs(den), 1.0)
    h_ref[0] = h.astype(h_ref.dtype)

    # carry update
    a = total - cum + it                                 # (L, 1)
    m_new = jnp.maximum(total[0, 0] + m0, jnp.max(a))
    w = jnp.exp(a - m_new)                               # (L, 1)
    scale_old = jnp.exp(total[0, 0] + m0 - m_new)
    C_ref[...] = scale_old * C_ref[...] + jax.lax.dot_general(
        k * w, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    n_ref[...] = scale_old * n_ref[...] + jnp.sum(k * w, axis=0, keepdims=True).T
    m_ref[...] = jnp.full_like(m_ref, m_new)

    @pl.when(ic == n_chunks - 1)
    def _emit_state():
        Cf_ref[0] = C_ref[...]
        nf_ref[0] = n_ref[...]
        mf_ref[0] = m_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_chunkwise_bh(
    q: jax.Array,   # (BH, S, DH)
    k: jax.Array,
    v: jax.Array,
    i: jax.Array,   # (BH, S, 1) input-gate preactivation
    lf: jax.Array,  # (BH, S, 1) log-sigmoid forget gate
    chunk: int = 64,
    *,
    interpret: bool,
):
    BH, S, DH = q.shape
    assert S % chunk == 0, (S, chunk)
    n_chunks = S // chunk
    kernel = functools.partial(_kernel, L=chunk, DH=DH, n_chunks=n_chunks)
    spec_sd = pl.BlockSpec((1, chunk, DH), lambda bh, ic: (bh, ic, 0))
    spec_s1 = pl.BlockSpec((1, chunk, 1), lambda bh, ic: (bh, ic, 0))
    h, Cf, nf, mf = pl.pallas_call(
        kernel,
        grid=(BH, n_chunks),
        in_specs=[spec_sd, spec_sd, spec_sd, spec_s1, spec_s1],
        out_specs=[
            spec_sd,
            pl.BlockSpec((1, DH, DH), lambda bh, ic: (bh, 0, 0)),
            pl.BlockSpec((1, DH, 1), lambda bh, ic: (bh, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda bh, ic: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, DH), q.dtype),
            jax.ShapeDtypeStruct((BH, DH, DH), jnp.float32),
            jax.ShapeDtypeStruct((BH, DH, 1), jnp.float32),
            jax.ShapeDtypeStruct((BH, 1, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((DH, DH), jnp.float32),   # C
            pltpu.VMEM((DH, 1), jnp.float32),    # n
            pltpu.VMEM((1, 1), jnp.float32),     # m
        ],
        interpret=interpret,
    )(q, k, v, i, lf)
    return h, Cf, nf, mf
