"""Paged GQA decode attention — Pallas TPU kernel (vLLM-style block tables).

One new token per sequence attends over a KV cache stored as fixed-size
pages in a shared pool; a per-sequence block table maps logical block i to a
physical page. Grid (B, KV, n_pages): each step DMAs ONE physical page of
K/V into VMEM — the page id comes from the scalar-prefetched block table, so
the index map itself performs the gather and the kernel body is identical in
shape to the dense flash-decoding kernel (online softmax over page blocks).

Unused block-table entries point at the reserved null page 0, so every index
the DMA engine sees is in-bounds; the length mask kills their scores.

Two orthogonal extensions ride the same grid:

* **int8 pools** (``pool_ks``/``pool_vs``): K/V pages are stored int8 with a
  bf16 scale per (page slot, head group); the kernel DMAs the int8 page plus
  its (ps, 1) scale column and dequantizes IN VMEM right after the gather —
  the decode hot loop reads ~hd/(hd+2) of the fp page bytes from HBM and the
  MXU sees f32 operands as before.
* **chained block tables** (``l2_tab``): ``block_tab`` becomes a first-level
  row of *table-page* ids into a shared (n_rows, tpp) second-level pool, so
  the per-sequence table width no longer caps context at
  ``max_seq_len`` — the scalar-prefetched index map simply chases one more
  pointer: page(ip) = l2[l1[b, ip // tpp], ip % tpp]. Row 0 of l2 is the
  reserved all-null table page (the null-page contract, one level up).

VMEM working set per step: G x hd (q) + 2 x ps x hd (one K and one V page)
+ G x hd f32 accumulator — independent of sequence length and pool size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(*refs, ps, n_p, scale, softcap, quant, chained):
    ns = 3 if chained else 2
    len_ref = refs[0]
    if quant:
        q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs[ns:]
    else:
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs[ns:]
    b = pl.program_id(0)
    ip = pl.program_id(2)

    @pl.when(ip == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    valid = len_ref[b]                                  # scalar int32
    t_start = ip * ps

    @pl.when(t_start < valid)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)             # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)             # (ps, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        if quant:
            # dequant-on-gather: the int8 page and its (ps, 1) scale column
            # were DMA'd together; one broadcast multiply in VMEM restores
            # f32 operands before the MXU pass
            k = k * ks_ref[0, 0].astype(jnp.float32)
            v = v * vs_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                        # (G, ps)
        if softcap:
            # gemma-style logit softcap, applied pre-mask so capped scores
            # match the dense decode path bit-for-bit
            s = jnp.tanh(s / softcap) * softcap
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

    @pl.when(ip == n_p - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "softcap"))
def paged_attention_grouped(
    q: jax.Array,          # (B, KV, G, hd) — one token per sequence
    pool_k: jax.Array,     # (num_pages, KV, ps, hd) shared page pool
    pool_v: jax.Array,
    block_tab: jax.Array,  # (B, P) physical pages — or (B, W1) l1 rows (chained)
    lengths: jax.Array,    # (B,) int32 valid tokens per sequence
    *,
    interpret: bool,
    softcap: float = 0.0,
    pool_ks: jax.Array | None = None,   # (num_pages, KV, ps, 1) bf16 scales
    pool_vs: jax.Array | None = None,
    l2_tab: jax.Array | None = None,    # (n_rows, tpp) second-level table pool
) -> jax.Array:
    B, KV, G, hd = q.shape
    ps = pool_k.shape[2]
    quant = pool_ks is not None
    chained = l2_tab is not None
    scale = 1.0 / (hd ** 0.5)

    if chained:
        tpp = l2_tab.shape[1]
        n_p = block_tab.shape[1] * tpp

        def page(ip, l1, l2, b):
            # two-level gather: logical block ip -> table page -> data page
            return l2[l1[b, ip // tpp], ip % tpp]

        def qmap(b, h, ip, lens, l1, l2):
            return (b, h, 0, 0)

        def kvmap(b, h, ip, lens, l1, l2):
            return (page(ip, l1, l2, b), h, 0, 0)
    else:
        n_p = block_tab.shape[1]

        def qmap(b, h, ip, lens, tab):
            return (b, h, 0, 0)

        def kvmap(b, h, ip, lens, tab):
            # the gather: block ip of sequence b lives in page tab[b, ip]
            return (tab[b, ip], h, 0, 0)

    kernel = functools.partial(
        _kernel, ps=ps, n_p=n_p, scale=scale, softcap=softcap,
        quant=quant, chained=chained,
    )
    in_specs = [
        pl.BlockSpec((1, 1, G, hd), qmap),
        pl.BlockSpec((1, 1, ps, hd), kvmap),
        pl.BlockSpec((1, 1, ps, hd), kvmap),
    ]
    operands = [q, pool_k, pool_v]
    if quant:
        in_specs += [
            pl.BlockSpec((1, 1, ps, 1), kvmap),
            pl.BlockSpec((1, 1, ps, 1), kvmap),
        ]
        operands += [pool_ks, pool_vs]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 if chained else 2,
        grid=(B, KV, n_p),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, hd), qmap),
        scratch_shapes=[
            pltpu.VMEM((G, hd), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    scalars = [lengths, block_tab] + ([l2_tab] if chained else [])
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        name="paged_attention_grouped",
        interpret=interpret,
    )(*scalars, *operands)


# ---------------------------------------------------------------------------
# Prefill write — the decode gather's twin: scatter one prompt's K/V through
# its block-table row into the pool. Grid (n_blocks,): step ib transposes one
# ps-token chunk of the incoming K/V into page layout and lands it in
# physical page tab_row[ib] — the scalar-prefetched row drives the OUTPUT
# index map, so the scatter happens in the write-back DMA and the kernel body
# is a pure VMEM transpose. The pools are input/output-aliased: only visited
# pages change, everything else is untouched in place. Bucket padding past
# the sequence's allocated pages carries tab_row entries of the reserved null
# page 0 — those trailing steps all land on (and fully overwrite) the null
# page, which is garbage by contract and never read back.
#
# The quantized variant fuses the int8 conversion into the same VMEM pass:
# per (token, head) absmax scales (models/quant.py's KV idiom, bit-identical
# to the jnp ref) are computed on the transposed page and written to the
# aliased scale pools alongside the int8 values — quantization happens at
# write time, so readers never see an fp page.
# ---------------------------------------------------------------------------


def _write_kernel(tab_ref, k_ref, v_ref, pool_k_ref, pool_v_ref, ok_ref, ov_ref):
    # k/v block: (1, ps, KV, hd) token-major -> page layout (KV, ps, hd)
    ok_ref[0] = jnp.transpose(k_ref[0], (1, 0, 2)).astype(ok_ref.dtype)
    ov_ref[0] = jnp.transpose(v_ref[0], (1, 0, 2)).astype(ov_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_prefill_write_grouped(
    pool_k: jax.Array,     # (num_pages, KV, ps, hd) shared page pool (donated)
    pool_v: jax.Array,
    k: jax.Array,          # (1, Lp, KV, hd) — Lp a multiple of ps
    v: jax.Array,
    tab_row: jax.Array,    # (P,) int32, P >= Lp // ps
    *,
    interpret: bool,
):
    """Returns (new_pool_k, new_pool_v); Lp % ps must be 0 (bucketed prefill
    guarantees it — ops.py falls back to the jnp ref for ragged lengths)."""
    num_pages, KV, ps, hd = pool_k.shape
    Lp = k.shape[1]
    assert Lp % ps == 0, f"Lp={Lp} not a page multiple (ps={ps})"
    nb = Lp // ps

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, ps, KV, hd), lambda ib, tab: (0, ib, 0, 0)),
            pl.BlockSpec((1, ps, KV, hd), lambda ib, tab: (0, ib, 0, 0)),
            # the pools stay in place (aliased outputs); no copy-in
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            # the scatter: chunk ib of the prompt lands in page tab[ib]
            pl.BlockSpec((1, KV, ps, hd), lambda ib, tab: (tab[ib], 0, 0, 0)),
            pl.BlockSpec((1, KV, ps, hd), lambda ib, tab: (tab[ib], 0, 0, 0)),
        ],
    )
    return pl.pallas_call(
        _write_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(pool_k.shape, pool_k.dtype),
            jax.ShapeDtypeStruct(pool_v.shape, pool_v.dtype),
        ],
        # operand indices count the scalar-prefetch arg: tab=0, k=1, v=2
        input_output_aliases={3: 0, 4: 1},
        name="paged_prefill_write_grouped",
        interpret=interpret,
    )(tab_row, k, v, pool_k, pool_v)


def _write_kernel_quant(
    tab_ref, k_ref, v_ref,
    pool_k_ref, pool_v_ref, pool_ks_ref, pool_vs_ref,
    ok_ref, ov_ref, oks_ref, ovs_ref,
):
    # quantize-at-write: transpose to page layout, absmax per (token, head),
    # land int8 values + bf16 scales in one pass (same op order as the jnp
    # ref / models.quant.quantize_kv, so parity is exact on the int8 bits)
    k = jnp.transpose(k_ref[0], (1, 0, 2)).astype(jnp.float32)   # (KV, ps, hd)
    v = jnp.transpose(v_ref[0], (1, 0, 2)).astype(jnp.float32)
    ks = jnp.maximum(jnp.max(jnp.abs(k), axis=-1, keepdims=True) / 127.0, 1e-8)
    vs = jnp.maximum(jnp.max(jnp.abs(v), axis=-1, keepdims=True) / 127.0, 1e-8)
    ok_ref[0] = jnp.clip(jnp.round(k / ks), -127, 127).astype(jnp.int8)
    ov_ref[0] = jnp.clip(jnp.round(v / vs), -127, 127).astype(jnp.int8)
    oks_ref[0] = ks.astype(oks_ref.dtype)
    ovs_ref[0] = vs.astype(ovs_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_prefill_write_grouped_quant(
    pool_k: jax.Array,     # (num_pages, KV, ps, hd) int8 page pool (donated)
    pool_v: jax.Array,
    pool_ks: jax.Array,    # (num_pages, KV, ps, 1) bf16 scale pool (donated)
    pool_vs: jax.Array,
    k: jax.Array,          # (1, Lp, KV, hd) fp activations — Lp % ps == 0
    v: jax.Array,
    tab_row: jax.Array,    # (P,) int32, P >= Lp // ps
    *,
    interpret: bool,
):
    """Returns (new_pool_k, new_pool_v, new_pool_ks, new_pool_vs)."""
    num_pages, KV, ps, hd = pool_k.shape
    Lp = k.shape[1]
    assert Lp % ps == 0, f"Lp={Lp} not a page multiple (ps={ps})"
    nb = Lp // ps

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, ps, KV, hd), lambda ib, tab: (0, ib, 0, 0)),
            pl.BlockSpec((1, ps, KV, hd), lambda ib, tab: (0, ib, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, KV, ps, hd), lambda ib, tab: (tab[ib], 0, 0, 0)),
            pl.BlockSpec((1, KV, ps, hd), lambda ib, tab: (tab[ib], 0, 0, 0)),
            pl.BlockSpec((1, KV, ps, 1), lambda ib, tab: (tab[ib], 0, 0, 0)),
            pl.BlockSpec((1, KV, ps, 1), lambda ib, tab: (tab[ib], 0, 0, 0)),
        ],
    )
    return pl.pallas_call(
        _write_kernel_quant,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(pool_k.shape, pool_k.dtype),
            jax.ShapeDtypeStruct(pool_v.shape, pool_v.dtype),
            jax.ShapeDtypeStruct(pool_ks.shape, pool_ks.dtype),
            jax.ShapeDtypeStruct(pool_vs.shape, pool_vs.dtype),
        ],
        # operand indices count the scalar-prefetch arg: tab=0, k=1, v=2
        input_output_aliases={3: 0, 4: 1, 5: 2, 6: 3},
        name="paged_prefill_write_grouped_quant",
        interpret=interpret,
    )(tab_row, k, v, pool_k, pool_v, pool_ks, pool_vs)
