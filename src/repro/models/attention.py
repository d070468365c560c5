"""GQA attention: train / prefill / decode, KV cache (bf16 or int8).

Memory discipline: training/prefill attention is *query-chunked* (lax.scan
over query blocks) so the live score tensor is (B, KV, G, Cq, T) instead of
(B, H, S, S) — this is what makes 32k prefill lower/compile within per-device
HBM. The Pallas flash-attention kernel (kernels/flash_attention) is the TPU
execution path; the jnp path here is the oracle and the CPU dry-run path.
"""
from __future__ import annotations

from typing import Mapping, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.common import EMBED, NULL, TP, ModelConfig, ParamDef
from repro.models.quant import dequantize_kv, qeinsum, quantize_kv
from repro.models.rotary import apply_mrope, apply_rope

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def attention_defs(cfg: ModelConfig, n_heads: int = 0, cross: bool = False) -> dict:
    H = n_heads or cfg.n_heads
    KV = H if cross else min(cfg.n_kv_heads, H)
    hd = cfg.hd
    d = cfg.d_model
    defs = {
        "wq": ParamDef((d, H * hd), (NULL, TP)),
        "wk": ParamDef((d, KV * hd), (NULL, TP)),
        "wv": ParamDef((d, KV * hd), (NULL, TP)),
        "wo": ParamDef((H * hd, d), (TP, NULL)),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((H * hd,), (TP,), "zeros")
        defs["bk"] = ParamDef((KV * hd,), (TP,), "zeros")
        defs["bv"] = ParamDef((KV * hd,), (TP,), "zeros")
    return defs


def kv_cache_defs(
    cfg: ModelConfig, batch: int, cap: int, n_heads: int = 0
) -> dict:
    """ShapeDtypeStructs for one attention layer's KV cache."""
    H = n_heads or cfg.n_heads
    KV = min(cfg.n_kv_heads, H)
    hd = cfg.hd
    if cfg.kv_quant:
        return {
            "k": jax.ShapeDtypeStruct((batch, cap, KV, hd), jnp.int8),
            "v": jax.ShapeDtypeStruct((batch, cap, KV, hd), jnp.int8),
            "k_scale": jax.ShapeDtypeStruct((batch, cap, KV, 1), jnp.bfloat16),
            "v_scale": jax.ShapeDtypeStruct((batch, cap, KV, 1), jnp.bfloat16),
        }
    return {
        "k": jax.ShapeDtypeStruct((batch, cap, KV, hd), cfg.kv_dtype),
        "v": jax.ShapeDtypeStruct((batch, cap, KV, hd), cfg.kv_dtype),
    }


def init_kv_cache(cfg: ModelConfig, batch: int, cap: int, n_heads: int = 0) -> dict:
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), kv_cache_defs(cfg, batch, cap, n_heads))


# ---------------------------------------------------------------------------
# Paged KV cache (vLLM-style page pool + block tables; serving/paging.py
# owns the host-side allocator, this is the device layout + access path)
# ---------------------------------------------------------------------------


class PagedIndex(NamedTuple):
    """Decode-time cache address for the paged path.

    lengths: (B,) int32 — tokens already in cache per slot (write position).
    block_tab: (B, P) int32 — physical page per logical block; unused
    entries point at the reserved null page 0. With ``l2`` set (chained
    two-level tables), block_tab is instead the (B, W1) first-level row of
    *table-page* ids and l2 is the (n_rows, tpp) pool of second-level rows:
    logical block i resolves to ``l2[block_tab[b, i // tpp], i % tpp]``.
    """

    lengths: jax.Array
    block_tab: jax.Array
    l2: Optional[jax.Array] = None


class PagedPrefillIndex(NamedTuple):
    """Prefill-time cache address for the paged path (one sequence).

    tab_row: (P,) int32 — the sequence's block-table row; token t scatters to
    (tab_row[t // ps], t % ps). Bucket padding beyond the allocated pages
    maps to the reserved null page 0 (harmless by construction).
    slot: scalar int32 — decode-batch slot owning the recurrent (SSM) state.
    """

    tab_row: jax.Array
    slot: jax.Array


class ChunkPrefillIndex(NamedTuple):
    """Chunked (resumable) dense prefill of one slot's cache stripe.

    offset: scalar int32 — tokens already in cache when this chunk starts;
    chunk token t lives at absolute position offset + t. The chunk's K/V is
    written at ``offset`` and its queries attend causally over the WHOLE
    stripe by absolute position, so positions written by earlier chunks stay
    visible while unwritten/stale positions (> offset + t) are masked out.
    Recurrent-mixer state does NOT live in the cache mid-prefill — it rides
    the explicit ``chunk_state`` carry (see transformer.forward) so decode
    steps batched between chunks cannot corrupt it.
    """

    offset: jax.Array


class PagedChunkPrefillIndex(NamedTuple):
    """Chunked (resumable) paged prefill of one sequence.

    tab_row: (P,) int32 — the sequence's full block-table row.
    slot: scalar int32 — decode-batch slot (recurrent-state install target).
    offset: scalar int32 — page-multiple chunk start; the chunk's K/V
    scatters through the row shifted by offset // ps pages (tail overruns
    land on the null page), and its queries attend over the dense gathered
    context view masked by absolute position.
    """

    tab_row: jax.Array
    slot: jax.Array
    offset: jax.Array


class PagedVerifyIndex(NamedTuple):
    """Speculative-decode verify pass over one paged sequence.

    tab_row: (P,) int32 — the sequence's full block-table row.
    offset: scalar int32 — tokens already in cache; verify token t (the
    pending last token plus k proposal tokens) scatters to absolute position
    offset + t through the row at an ARBITRARY offset (per-token page
    indexing — unlike chunk offsets, verify starts mid-page), and the
    queries attend over the dense gathered context view masked by absolute
    position, exactly like a chunked-prefill chunk. Rejected speculative
    positions (> the accepted run) stay in the pool as stale garbage — the
    engine rolls its write-head back and absolute-position masks plus
    overwrite-on-next-write keep them invisible.
    """

    tab_row: jax.Array
    offset: jax.Array


def paged_kv_pool_defs(cfg: ModelConfig, num_pages: int, page_size: int, n_heads: int = 0) -> dict:
    """ShapeDtypeStructs for one attention layer's shared page pool.

    With ``cfg.kv_quant`` the pool stores int8 values plus per-(page-slot,
    head) bf16 scales — ``models/quant.py``'s KV idiom with the token axis
    living inside the page. Every access path dispatches on the presence of
    the ``k_scale`` leaf."""
    H = n_heads or cfg.n_heads
    KV = min(cfg.n_kv_heads, H)
    shape = (num_pages, KV, page_size, cfg.hd)
    if cfg.kv_quant:
        sshape = (num_pages, KV, page_size, 1)
        return {
            "k": jax.ShapeDtypeStruct(shape, jnp.int8),
            "v": jax.ShapeDtypeStruct(shape, jnp.int8),
            "k_scale": jax.ShapeDtypeStruct(sshape, jnp.bfloat16),
            "v_scale": jax.ShapeDtypeStruct(sshape, jnp.bfloat16),
        }
    return {
        "k": jax.ShapeDtypeStruct(shape, cfg.kv_dtype),
        "v": jax.ShapeDtypeStruct(shape, cfg.kv_dtype),
    }


def paged_cache_kv(cfg: ModelConfig, cache: Mapping, k: jax.Array, v: jax.Array, idx: PagedIndex) -> dict:
    """Scatter one new token's K/V (B, 1, KV, hd) into the page pool at each
    slot's (page, offset). Dead slots (length 0, null block table) scatter
    into the reserved null page — harmless by construction. With chained
    tables (``idx.l2``) the logical page index resolves through the
    second-level pool; with a quantized pool the token is quantized here and
    its scales land in the scale pools through the same indices."""
    ps = cache["k"].shape[2]
    KV = cache["k"].shape[1]
    lp = idx.lengths // ps                                   # logical page index
    if idx.l2 is not None:
        tpp = idx.l2.shape[1]
        l1e = jnp.take_along_axis(idx.block_tab, (lp // tpp)[:, None], axis=1)[:, 0]
        pages = idx.l2[l1e, lp % tpp]
    else:
        pages = jnp.take_along_axis(idx.block_tab, lp[:, None], axis=1)[:, 0]
    offs = idx.lengths % ps
    kvh = jnp.arange(KV)
    at = (pages[:, None], kvh[None, :], offs[:, None])
    out = dict(cache)
    if "k_scale" in cache:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        out["k"] = cache["k"].at[at].set(kq[:, 0])
        out["v"] = cache["v"].at[at].set(vq[:, 0])
        out["k_scale"] = cache["k_scale"].at[at].set(ks[:, 0].astype(cache["k_scale"].dtype))
        out["v_scale"] = cache["v_scale"].at[at].set(vs[:, 0].astype(cache["v_scale"].dtype))
    else:
        out["k"] = cache["k"].at[at].set(k[:, 0].astype(cache["k"].dtype))
        out["v"] = cache["v"].at[at].set(v[:, 0].astype(cache["v"].dtype))
    return out


def paged_write_prompt(
    cfg: ModelConfig, cache: Mapping, k: jax.Array, v: jax.Array, tab_row: jax.Array,
    offset=None,
) -> dict:
    """Write a whole prefilled prompt — or, with ``offset``, one prompt
    chunk — (1, Lp, KV, hd) through one sequence's block-table row (P,) into
    the pool; chunk token t -> absolute position offset + t (offset is a
    page multiple; tail-chunk padding past the table lands on the null
    page). The scatter itself lives with the paged kernels (the decode
    gather's write-side twin): a Pallas kernel on the TPU path, the jnp ref
    oracle otherwise."""
    from repro.kernels.paged_attention import ops as pa_ops

    out = dict(cache)
    if "k_scale" in cache:
        out["k"], out["v"], out["k_scale"], out["v_scale"] = pa_ops.paged_prefill_write_quant(
            cache["k"], cache["v"], cache["k_scale"], cache["v_scale"],
            k, v, tab_row, use_pallas=cfg.use_pallas, offset=offset,
        )
    else:
        out["k"], out["v"] = pa_ops.paged_prefill_write(
            cache["k"], cache["v"], k, v, tab_row, use_pallas=cfg.use_pallas, offset=offset
        )
    return out


# ---------------------------------------------------------------------------
# int8 KV quantization — the shared idiom lives in models/quant.py (the paged
# pool's write kernels and jnp oracles import it from there too, which is
# what keeps every storage path bit-identical on the int8 tensors).
# ---------------------------------------------------------------------------


def _dus(buf: jax.Array, upd: jax.Array, index) -> jax.Array:
    """Write upd (B,S,...) into buf (B,T,...) at seq position index. index may
    be a scalar or per-batch (B,) — the latter vmaps (continuous batching:
    every slot has its own length)."""
    idx = jnp.asarray(index)
    if idx.ndim == 1:
        return jax.vmap(
            lambda b, u, i: jax.lax.dynamic_update_slice_in_dim(b, u, i, axis=0)
        )(buf, upd, idx)
    return jax.lax.dynamic_update_slice_in_dim(buf, upd, index, axis=1)


def cache_kv(cfg: ModelConfig, cache: Mapping, k: jax.Array, v: jax.Array, index) -> dict:
    """Write k/v (B, S_new, KV, hd) into cache at position ``index``."""
    out = dict(cache)
    if cfg.kv_quant:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        out["k"] = _dus(cache["k"], kq, index)
        out["v"] = _dus(cache["v"], vq, index)
        out["k_scale"] = _dus(cache["k_scale"], ks, index)
        out["v_scale"] = _dus(cache["v_scale"], vs, index)
    else:
        out["k"] = _dus(cache["k"], k.astype(cache["k"].dtype), index)
        out["v"] = _dus(cache["v"], v.astype(cache["v"].dtype), index)
    return out


def read_kv(cfg: ModelConfig, cache: Mapping, dtype):
    if cfg.kv_quant:
        return (
            dequantize_kv(cache["k"], cache["k_scale"], dtype),
            dequantize_kv(cache["v"], cache["v_scale"], dtype),
        )
    return cache["k"].astype(dtype), cache["v"].astype(dtype)


# ---------------------------------------------------------------------------
# Core attention math (grouped-query, fp32 softmax)
# ---------------------------------------------------------------------------


def _group(q: jax.Array, kv_heads: int) -> jax.Array:
    """(B, S, H, hd) -> (B, S, KV, G, hd)."""
    B, S, H, hd = q.shape
    return q.reshape(B, S, kv_heads, H // kv_heads, hd)


def _attend_block(q, k, v, mask, softcap: float = 0.0, scores_bf16: bool = False):
    """q: (B,Cq,KV,G,hd); k/v: (B,T,KV,hd); mask: (B,1,1,Cq,T) bool.
    scores_bf16 halves the materialized score traffic (row stats stay f32)."""
    hd = q.shape[-1]
    sdt = jnp.bfloat16 if scores_bf16 else jnp.float32
    scale = jnp.asarray(1.0 / (hd ** 0.5), sdt)
    s = jnp.einsum("bqkgh,btkh->bkgqt", q, k, preferred_element_type=sdt)
    s = s * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    s = jnp.where(mask, s, jnp.asarray(NEG_INF, sdt))
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp((s - m).astype(jnp.float32))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bkgqt,btkh->bqkgh", p.astype(q.dtype), v)
    return o


def chunked_attention(
    cfg: ModelConfig,
    q: jax.Array,           # (B, S, H, hd)
    k: jax.Array,           # (B, T, KV, hd)
    v: jax.Array,
    pos_q: jax.Array,       # (B, S) int32
    pos_k: jax.Array,       # (B, T) int32
    causal: bool = True,
    allow_kernel: bool = True,
) -> jax.Array:
    """Query-chunked attention; returns (B, S, H, hd). ``allow_kernel=False``
    forces the jnp path — the flash kernel assumes square causal q/k of equal
    length, which the chunked-prefill context attention (short q over a long
    cached prefix at an absolute-position offset) violates."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    if cfg.use_pallas and causal and S > 1 and allow_kernel and S == k.shape[1]:
        from repro.kernels.flash_attention import ops as fa_ops

        return fa_ops.flash_attention(q, k, v, pos_q, pos_k)
    qg = _group(q, KV)
    chunk = min(cfg.attn_chunk, S)
    if S % chunk != 0:
        chunk = S  # irregular small shapes: single block
    nc = S // chunk
    if nc == 1:
        mask = (pos_q[:, None, None, :, None] >= pos_k[:, None, None, None, :]) if causal else jnp.ones((B, 1, 1, S, k.shape[1]), bool)
        o = _attend_block(qg, k, v, mask, cfg.logit_softcap, cfg.attn_scores_bf16)
        return o.reshape(B, S, H, hd)

    qc = qg.reshape(B, nc, chunk, KV, H // KV, hd).transpose(1, 0, 2, 3, 4, 5)
    pc = pos_q.reshape(B, nc, chunk).transpose(1, 0, 2)

    @jax.checkpoint  # flash-attention-style: recompute scores/probs in bwd —
    # without this the scan stacks per-chunk probs (O(S^2) live residuals)
    def body(_, qp):
        qb, pb = qp
        if causal:
            mask = pb[:, None, None, :, None] >= pos_k[:, None, None, None, :]
        else:
            mask = jnp.ones((B, 1, 1, chunk, k.shape[1]), bool)
        return None, _attend_block(qb, k, v, mask, cfg.logit_softcap, cfg.attn_scores_bf16)

    _, o = jax.lax.scan(body, None, (qc, pc))
    o = o.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H, hd)
    return o


def context_attention(
    cfg: ModelConfig,
    q: jax.Array,           # (B, Cq, H, hd) — one prefill chunk's queries
    k: jax.Array,           # (B, T, KV, hd) — the full cached context view
    v: jax.Array,
    pos_q: jax.Array,       # (B, Cq) absolute positions (offset + arange)
) -> jax.Array:
    """Chunked-prefill attention: a chunk of queries over the whole cached
    context (earlier chunks + this chunk, freshly written), masked causally
    by ABSOLUTE position — key t is visible to query at position p iff
    t <= p, which simultaneously exposes the valid prefix, enforces
    causality inside the chunk, and hides unwritten/stale cache positions
    and tail-chunk bucket padding (all strictly in the future)."""
    B, T = k.shape[0], k.shape[1]
    pos_k = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
    return chunked_attention(cfg, q, k, v, pos_q, pos_k, causal=True, allow_kernel=False)


def decode_attention_quant(cfg: ModelConfig, q: jax.Array, cache: Mapping, cache_len) -> jax.Array:
    """int8-KV decode without materializing a dequantized cache: the per
    (token, head) scales fold into the score matrix (k) and the probability
    matrix (v), so the int8 tensors feed the dots directly (mixed-dtype dot;
    converts fuse into the MXU pass on TPU)."""
    B, S, H, hd = q.shape
    KV = cache["k"].shape[2]
    qg = _group(q, KV)                                       # (B,S,KV,G,hd)
    T = cache["k"].shape[1]
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    s = jnp.einsum("bqkgh,btkh->bkgqt", qg, cache["k"], preferred_element_type=jnp.float32)
    k_sc = cache["k_scale"].astype(jnp.float32)[..., 0]      # (B,T,KV)
    s = s * scale * k_sc.transpose(0, 2, 1)[:, :, None, None, :]
    cl = jnp.asarray(cache_len)
    cl = cl.reshape(-1, 1, 1, 1, 1) if cl.ndim == 1 else cl
    mask = (jnp.arange(T)[None, None, None, None, :] < cl) & jnp.ones((B, 1, 1, S, 1), bool)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    v_sc = cache["v_scale"].astype(jnp.float32)[..., 0]
    p = p * v_sc.transpose(0, 2, 1)[:, :, None, None, :]
    o = jnp.einsum("bkgqt,btkh->bqkgh", p.astype(jnp.bfloat16), cache["v"], preferred_element_type=jnp.float32)
    return o.astype(q.dtype).reshape(B, S, H, hd)


def decode_attention(
    cfg: ModelConfig,
    q: jax.Array,           # (B, 1, H, hd)
    k: jax.Array,           # (B, T, KV, hd)  (cache contents, incl. new token)
    v: jax.Array,
    cache_len,              # scalar: valid prefix length
) -> jax.Array:
    B, S, H, hd = q.shape
    KV = k.shape[2]
    if cfg.use_pallas:
        from repro.kernels.decode_attention import ops as da_ops

        return da_ops.decode_attention(q, k, v, cache_len)
    qg = _group(q, KV)
    T = k.shape[1]
    cl = jnp.asarray(cache_len)
    cl = cl.reshape(-1, 1, 1, 1, 1) if cl.ndim == 1 else cl  # per-slot lengths
    mask = (jnp.arange(T)[None, None, None, None, :] < cl) & jnp.ones((B, 1, 1, S, 1), bool)
    o = _attend_block(qg, k, v, mask, cfg.logit_softcap, cfg.attn_scores_bf16)
    return o.reshape(B, S, H, hd)


# ---------------------------------------------------------------------------
# Full attention sub-layer (projection + rope + attend + out-projection)
# ---------------------------------------------------------------------------


def _project_qkv(cfg: ModelConfig, p: Mapping, x: jax.Array, n_heads: int):
    B, S, _ = x.shape
    H = n_heads
    KV = min(cfg.n_kv_heads, H)
    hd = cfg.hd
    q = qeinsum("bsd,dh->bsh", x, p["wq"])
    k = qeinsum("bsd,dh->bsh", x, p["wk"])
    v = qeinsum("bsd,dh->bsh", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    return (
        q.reshape(B, S, H, hd),
        k.reshape(B, S, KV, hd),
        v.reshape(B, S, KV, hd),
    )


def _apply_pos(cfg: ModelConfig, x: jax.Array, positions) -> jax.Array:
    if cfg.pos == "rope":
        return apply_rope(x, positions, cfg.rope_theta)
    if cfg.pos == "mrope":
        return apply_mrope(x, positions, cfg.rope_theta, cfg.mrope_sections)
    return x


def self_attention(
    cfg: ModelConfig,
    p: Mapping,
    x: jax.Array,
    positions: jax.Array,
    mode: str,                      # train | prefill | decode
    cache: Optional[Mapping] = None,
    cache_index=None,               # scalar write offset for decode/prefill
    causal: bool = True,
    n_heads: int = 0,
):
    """Returns (out, new_cache)."""
    H = n_heads or cfg.n_heads
    B, S, _ = x.shape
    q, k, v = _project_qkv(cfg, p, x, H)
    pos_t = positions[0] if cfg.pos == "mrope" else positions  # temporal stream for masks
    q = _apply_pos(cfg, q, positions)
    k = _apply_pos(cfg, k, positions)

    new_cache = cache
    if mode == "train":
        o = chunked_attention(cfg, q, k, v, pos_t, pos_t, causal=causal)
    elif mode == "prefill" and isinstance(cache_index, PagedPrefillIndex):
        # truly paged prefill: K/V scatter straight through the block table
        # into the page pool — no dense per-length staging cache exists.
        assert cache is not None
        with jax.named_scope("kv_write"):
            new_cache = paged_write_prompt(cfg, cache, k, v, cache_index.tab_row)
        o = chunked_attention(cfg, q, k, v, pos_t, pos_t, causal=causal)
    elif mode == "prefill" and isinstance(cache_index, PagedChunkPrefillIndex):
        # chunked paged prefill: scatter this chunk at its page-aligned
        # offset, then attend over the dense gathered context view (fixed
        # table_width * ps shape — compilation stays offset-independent).
        from repro.kernels.paged_attention import ops as pa_ops

        assert cache is not None
        with jax.named_scope("kv_write"):
            new_cache = paged_write_prompt(
                cfg, cache, k, v, cache_index.tab_row, offset=cache_index.offset
            )
        with jax.named_scope("paged_attn"):
            ck, cv = pa_ops.paged_gather_context(
                new_cache["k"], new_cache["v"], cache_index.tab_row,
                pool_ks=new_cache.get("k_scale"), pool_vs=new_cache.get("v_scale"),
            )
            o = context_attention(cfg, q, ck.astype(x.dtype), cv.astype(x.dtype), pos_t)
    elif mode == "prefill" and isinstance(cache_index, PagedVerifyIndex):
        # speculative verify: scatter the k+1 verify tokens' K/V at an
        # arbitrary (mid-page) offset, then attend over the gathered context
        # view — same absolute-position masking as a prefill chunk, so every
        # verify position sees exactly the prefix + its own causal slice.
        from repro.kernels.paged_attention import ops as pa_ops

        assert cache is not None
        new_cache = dict(cache)
        with jax.named_scope("kv_write"):
            if "k_scale" in cache:
                (
                    new_cache["k"], new_cache["v"],
                    new_cache["k_scale"], new_cache["v_scale"],
                ) = pa_ops.paged_verify_write_quant(
                    cache["k"], cache["v"], cache["k_scale"], cache["v_scale"],
                    k, v, cache_index.tab_row, cache_index.offset,
                )
            else:
                new_cache["k"], new_cache["v"] = pa_ops.paged_verify_write(
                    cache["k"], cache["v"], k, v, cache_index.tab_row, cache_index.offset
                )
        with jax.named_scope("paged_attn"):
            ck, cv = pa_ops.paged_gather_context(
                new_cache["k"], new_cache["v"], cache_index.tab_row,
                pool_ks=new_cache.get("k_scale"), pool_vs=new_cache.get("v_scale"),
            )
            o = context_attention(cfg, q, ck.astype(x.dtype), cv.astype(x.dtype), pos_t)
    elif mode == "prefill" and isinstance(cache_index, ChunkPrefillIndex):
        # chunked dense prefill: write this chunk into the slot's stripe at
        # ``offset`` and attend over the whole stripe by absolute position.
        assert cache is not None
        with jax.named_scope("kv_write"):
            new_cache = cache_kv(cfg, cache, k, v, cache_index.offset)
        ck, cv = read_kv(cfg, new_cache, x.dtype)
        o = context_attention(cfg, q, ck, cv, pos_t)
    elif mode == "prefill":
        assert cache is not None
        with jax.named_scope("kv_write"):
            new_cache = cache_kv(cfg, cache, k, v, 0 if cache_index is None else cache_index)
        o = chunked_attention(cfg, q, k, v, pos_t, pos_t, causal=causal)
    elif mode == "decode" and isinstance(cache_index, PagedIndex):
        assert cache is not None and S == 1
        with jax.named_scope("kv_write"):
            new_cache = paged_cache_kv(cfg, cache, k, v, cache_index)
        from repro.kernels.paged_attention import ops as pa_ops

        with jax.named_scope("paged_attn"):
            o = pa_ops.paged_attention(
                q, new_cache["k"], new_cache["v"],
                cache_index.block_tab, cache_index.lengths + 1,
                use_pallas=cfg.use_pallas,
                softcap=cfg.logit_softcap,
                pool_ks=new_cache.get("k_scale"),
                pool_vs=new_cache.get("v_scale"),
                l2_tab=cache_index.l2,
            )
    elif mode == "decode":
        assert cache is not None and cache_index is not None
        with jax.named_scope("kv_write"):
            new_cache = cache_kv(cfg, cache, k, v, cache_index)
        if cfg.kv_quant:
            o = decode_attention_quant(cfg, q, new_cache, cache_len=cache_index + S)
        else:
            ck, cv = read_kv(cfg, new_cache, x.dtype)
            o = decode_attention(cfg, q, ck, cv, cache_len=cache_index + S)
    else:
        raise ValueError(mode)

    out = qeinsum("bsh,he->bse", o.reshape(B, S, H * cfg.hd), p["wo"])
    return out, new_cache


def cross_attention(
    cfg: ModelConfig,
    p: Mapping,
    x: jax.Array,
    kv_cache: Mapping,       # precomputed {"k": (B,T,H,hd), "v": ...} from encoder
):
    """Enc-dec cross attention; KV computed once from encoder output."""
    B, S, _ = x.shape
    H = cfg.n_heads
    hd = cfg.hd
    q = qeinsum("bsd,dh->bsh", x, p["wq"]).reshape(B, S, H, hd)
    k = kv_cache["k"].astype(x.dtype)
    v = kv_cache["v"].astype(x.dtype)
    T = k.shape[1]
    pos_q = jnp.zeros((B, S), jnp.int32)
    pos_k = jnp.zeros((B, T), jnp.int32)
    o = chunked_attention(cfg, q, k, v, pos_q, pos_k, causal=False)
    return qeinsum("bsh,he->bse", o.reshape(B, S, H * hd), p["wo"])


def cross_kv(cfg: ModelConfig, p: Mapping, enc_out: jax.Array):
    """Project encoder output to cross-attention K/V once (prefill)."""
    B, T, _ = enc_out.shape
    H = cfg.n_heads
    hd = cfg.hd
    k = qeinsum("btd,dh->bth", enc_out, p["wk"]).reshape(B, T, H, hd)
    v = qeinsum("btd,dh->bth", enc_out, p["wv"]).reshape(B, T, H, hd)
    return {"k": k, "v": v}
