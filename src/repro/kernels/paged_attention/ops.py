"""Model-facing wrappers for the paged KV pool: decode-time gather-attention
over block tables, and its write-side twin — the prefill scatter that lands a
whole prompt's K/V in the pool without ever materializing a dense per-length
staging cache. Every entry point carries an optional int8 leg (scale pools
alongside the value pools — quantize at write, dequantize on gather) and the
decode path additionally accepts chained two-level block tables."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret
from repro.kernels.paged_attention.kernel import (
    paged_attention_grouped,
    paged_prefill_write_grouped,
    paged_prefill_write_grouped_quant,
)
from repro.kernels.paged_attention.ref import (
    gather_kv,
    paged_attention_ref,
    paged_prefill_write_quant_ref,
    paged_prefill_write_ref,
    paged_verify_write_quant_ref,
    paged_verify_write_ref,
)
from repro.models.quant import dequantize_kv


def _shift_row(tab, offset, ps):
    """Shift a block-table row left by ``offset // ps`` pages (chunked
    prefill: chunk token t lands at absolute position offset + t). Entries
    shifted past the row's end map to the reserved null page 0."""
    P = tab.shape[0]
    idx = jnp.asarray(offset, jnp.int32) // ps + jnp.arange(P, dtype=jnp.int32)
    return jnp.where(idx < P, tab[jnp.clip(idx, 0, P - 1)], 0)  # 0 == null page


def paged_prefill_write(pool_k, pool_v, k, v, tab_row, use_pallas: bool = True,
                        offset=None):
    """Scatter one prefilled prompt's (or prompt chunk's) K/V through its
    block-table row.

    pool_k/pool_v: (num_pages, KV, ps, hd); k/v: (1, Lp, KV, hd) — Lp may be
    bucket-padded past the sequence's allocated pages, in which case
    ``tab_row[t // ps]`` is the reserved null page 0 and the pad writes are
    absorbed there (never read: the length mask kills those positions).
    Returns (new_pool_k, new_pool_v).

    ``offset`` (scalar int32, page-multiple) makes this the CHUNKED prefill
    write: chunk token t lands at absolute position offset + t, realized by
    shifting the block-table row left by offset // ps pages before the
    scatter — the kernels keep their token-t -> row[t // ps] contract
    untouched. Row entries shifted past the table's end map to the reserved
    null page 0, so a tail chunk whose bucket padding overruns the allocated
    pages is absorbed exactly like whole-prompt bucket padding.

    The Pallas kernel requires Lp to be a page multiple (bucketed prefill
    always is); ragged lengths (bucketing off) fall back to the jnp ref."""
    ps = pool_k.shape[2]
    Lp = k.shape[1]
    tab = jnp.asarray(tab_row, jnp.int32)
    if offset is not None:
        tab = _shift_row(tab, offset, ps)
    if use_pallas and Lp % ps == 0:
        return paged_prefill_write_grouped(pool_k, pool_v, k, v, tab, interpret=interpret())
    return paged_prefill_write_ref(pool_k, pool_v, k, v, tab)


def paged_prefill_write_quant(pool_k, pool_v, pool_ks, pool_vs, k, v, tab_row,
                              use_pallas: bool = True, offset=None):
    """Int8 leg of ``paged_prefill_write``: quantization happens AT WRITE
    TIME — fused into the Pallas write kernel's VMEM pass on the kernel
    path, via ``models/quant.py``'s KV idiom on the jnp path (bit-identical
    by construction). Returns the four updated pools (values + scales)."""
    ps = pool_k.shape[2]
    Lp = k.shape[1]
    tab = jnp.asarray(tab_row, jnp.int32)
    if offset is not None:
        tab = _shift_row(tab, offset, ps)
    if use_pallas and Lp % ps == 0:
        return paged_prefill_write_grouped_quant(
            pool_k, pool_v, pool_ks, pool_vs, k, v, tab, interpret=interpret()
        )
    return paged_prefill_write_quant_ref(pool_k, pool_v, pool_ks, pool_vs, k, v, tab)


def paged_verify_write(pool_k, pool_v, k, v, tab_row, offset):
    """Scatter a speculative verify stripe's K/V (1, S, KV, hd) through a
    block-table row at an arbitrary (non-page-multiple) token offset — the
    write-side of the speculative-decode verify pass. S is k+1 proposal
    tokens (single digits), far below any Pallas grid's useful occupancy, so
    the jnp per-token scatter IS the kernel on every path; the read side
    reuses ``paged_gather_context`` + absolute-position masking exactly like
    a chunked-prefill chunk."""
    tab = jnp.asarray(tab_row, jnp.int32)
    return paged_verify_write_ref(pool_k, pool_v, k, v, tab, offset)


def paged_verify_write_quant(pool_k, pool_v, pool_ks, pool_vs, k, v, tab_row, offset):
    """Int8 leg of ``paged_verify_write``: quantize the stripe per (token,
    head) and land values + scales through the same per-token page indexing,
    so speculative decoding rides the one quantized storage format."""
    tab = jnp.asarray(tab_row, jnp.int32)
    return paged_verify_write_quant_ref(pool_k, pool_v, pool_ks, pool_vs, k, v, tab, offset)


def paged_gather_context(pool_k, pool_v, tab_row, pool_ks=None, pool_vs=None):
    """Materialize one sequence's dense K/V context view from the page pool:
    (num_pages, KV, ps, hd) x (P,) -> two (1, P*ps, KV, hd) arrays where
    index t holds the token at logical position t (null-row entries carry
    page-0 garbage — callers mask them out positionally).

    This is the read-side of the chunked prefill: each chunk's queries
    attend over every previously written position plus the chunk itself, so
    the bounded-compilation contract holds (the gathered shape is fixed at
    the row width * page_size regardless of how much context is live).

    With ``pool_ks``/``pool_vs`` the pools are int8 and the gathered view is
    dequantized (f32) — chunked prefill and speculative verify read the same
    quantized storage the decode kernel does."""
    tab = jnp.asarray(tab_row, jnp.int32)[None, :]            # (1, P)
    k = gather_kv(pool_k, tab)                                # (1, KV, P*ps, hd)
    v = gather_kv(pool_v, tab)
    if pool_ks is not None:
        k = dequantize_kv(k, gather_kv(pool_ks, tab), jnp.float32)
        v = dequantize_kv(v, gather_kv(pool_vs, tab), jnp.float32)
    return k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)


def paged_attention(q, pool_k, pool_v, block_tab, lengths, use_pallas: bool = True,
                    softcap: float = 0.0, pool_ks=None, pool_vs=None, l2_tab=None):
    """q: (B, S=1, H, hd); pools: (num_pages, KV, ps, hd); block_tab: (B, P)
    physical pages — or, with ``l2_tab`` (n_rows, tpp), the (B, W1)
    first-level rows of a chained table; lengths: (B,) valid tokens per
    sequence. ``pool_ks``/``pool_vs`` select the int8 dequant-on-gather
    path. Returns (B, 1, H, hd)."""
    B, S, H, hd = q.shape
    KV = pool_k.shape[1]
    G = H // KV
    qg = q[:, 0].reshape(B, KV, G, hd)
    lens = jnp.asarray(lengths, jnp.int32)
    tab = jnp.asarray(block_tab, jnp.int32)
    l2 = None if l2_tab is None else jnp.asarray(l2_tab, jnp.int32)
    if use_pallas:
        o = paged_attention_grouped(
            qg, pool_k, pool_v, tab, lens, interpret=interpret(), softcap=softcap,
            pool_ks=pool_ks, pool_vs=pool_vs, l2_tab=l2,
        )
    else:
        o = paged_attention_ref(
            qg, pool_k, pool_v, tab, lens, softcap=softcap,
            pool_ks=pool_ks, pool_vs=pool_vs, l2_tab=l2,
        )
    return o.reshape(B, 1, H, hd)
