"""Paged GQA decode attention — Pallas TPU kernel (vLLM-style block tables).

One new token per sequence attends over a KV cache stored as fixed-size
pages in a shared pool; a per-sequence block table maps logical block i to a
physical page. A pool page is ``(KV, ps, hd)``, contiguous in the
``(num_pages, KV, ps, hd)`` layout, so one copy brings every KV head of it.

The walk follows each row's live pages, not the table width. A row of
``len`` tokens holds ``live_pages(len) = ceil(len / ps)`` pages, walked in
blocks of ``ppb`` (``pages_per_block``, from the page's shape and dtype).
The wrapper lays the blocks of all rows end to end as a work list — row,
block and the ``ppb`` physical pages of each grid step, resolved through
flat or chained tables alike — and the grid has exactly as many steps as
there are live blocks (at least one a row), so a block past a row's length
costs nothing at all. Grid step s copies its block's pages into VMEM through
``ppb`` page-sized block specs whose index maps read the work list; Mosaic
double-buffers them, so step s+1's pages — across row boundaries too — are
in flight while step s computes. A page slot that the block does not fill
(the last block of a row) keeps the page it held, and an unchanged block
index starts no copy: only live pages are ever copied. The block's scores
are one batched ``(KV, G, hd) x (KV, ppb*ps, hd)`` contraction, masked by
position and folded into an f32 online softmax that lives across a row's
steps; the row's output is written at its last block.

Two orthogonal extensions ride the same walk:

* **int8 pools** (``pool_ks``/``pool_vs``): K/V pages are stored int8 with a
  bf16 scale per (page slot, head group); each page brings its (ps, 1)
  scale column along and is dequantized IN VMEM — the decode reads
  ~hd/(hd+2) of the fp page bytes from HBM and the MXU sees f32 operands as
  before.
* **chained block tables** (``l2_tab``): ``block_tab`` becomes a first-level
  row of *table-page* ids into a shared (n_rows, tpp) second-level pool, so
  the per-sequence table width no longer caps context at ``max_seq_len`` —
  the work list's addresses chase one more pointer: page(ip) =
  l2[l1[b, ip // tpp], ip % tpp]. Row 0 of l2 is the reserved all-null
  table page (the null-page contract, one level up).

VMEM working set: two buffers of each of the block's ``2 x ppb`` pages
(plus their scale columns for int8), q, the output row and an f32
``(KV, G, hd)`` accumulator — independent of sequence length, table width
and pool size. SMEM holds the work list, ``ppb + 2`` ints a step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128              # a block's scores fill at least one lane row
_BLOCK_BYTES = 64 * 1024  # K bytes a block copies, at least


def live_pages(lengths, page_size: int, n_pages: int):
    """Pages the decode kernel copies for rows of ``lengths`` tokens: those
    below ``ceil(len / ps)``, at most the table width. Takes host ints or
    numpy arrays (the engine's ``pages_walked`` counter) and traced arrays
    (the kernel's work list) alike."""
    n = (lengths + page_size - 1) // page_size
    if isinstance(n, jax.Array):
        return jnp.minimum(n, n_pages)
    return np.minimum(n, n_pages)


def pages_per_block(page_size: int, kv_heads: int, head_dim: int, itemsize: int,
                    n_pages: int) -> int:
    """Pages one grid step copies: the least power of two whose scores
    fill a lane row and whose K copy reaches ``_BLOCK_BYTES``, at most the
    table width. SmolLM-360M's bf16 page (5 x 16 x 64) is 10 KiB and takes
    8 a step; Granite-8B's (8 x 16 x 128) is 32 KiB and takes 8 too."""
    page = kv_heads * page_size * head_dim * itemsize
    need = max(-(-_LANES // page_size), -(-_BLOCK_BYTES // page))
    return min(1 << (need - 1).bit_length(), n_pages)


def _work_list(lengths, tab, ps: int, ppb: int):
    """The grid's steps, the live blocks of every row end to end: ``(steps,
    rows, blks, pages)``. ``steps`` is the count of live blocks (a row of no
    tokens still takes one, so that its output is written); ``rows`` and
    ``blks`` name each step's row and block; ``pages[j * n + s]`` is the
    physical page slot j holds at step s (``n`` steps in all). A slot past
    its row's length keeps the page it held at its last live step (or,
    before its first, the page it will hold then), so its block index never
    changes and Mosaic copies nothing for it. The arrays are ``(ppb, n)``,
    steps along the lanes: ``(n, ppb)`` would pad each step to a lane
    tile."""
    B, n_p = tab.shape
    nb = -(-n_p // ppb)
    # the most steps there can be, and a spare for a look past the last
    n = B * nb + 1
    n_live = live_pages(lengths, ps, n_p)            # (B,)
    per_row = jnp.maximum((n_live + ppb - 1) // ppb, 1)
    ends = jnp.cumsum(per_row)
    starts = ends - per_row
    s = jnp.arange(n, dtype=jnp.int32)
    # each step's row as a (B, n) one-hot, which fuses into the sums over
    # it; a step past the last belongs to no row and has no live slot
    hot = (starts[:, None] <= s) & (s < ends[:, None])
    rows = jnp.sum(jnp.where(hot, jnp.arange(B, dtype=jnp.int32)[:, None], 0), axis=0)
    blks = s - jnp.sum(jnp.where(hot, starts[:, None], 0), axis=0)
    row_live = jnp.sum(jnp.where(hot, n_live[:, None], 0), axis=0)
    # slot j of block i of row b reads column (b * nb + i) * ppb + j of the
    # flat padded table, which grows with the step: a dead slot takes the
    # column of its last live step by a running max (before its first, that
    # of its next by a running min from the end)
    tab = jnp.pad(tab, ((0, 0), (0, nb * ppb - n_p))).reshape(-1)
    ip = blks[None, :] * ppb + jnp.arange(ppb, dtype=jnp.int32)[:, None]   # (ppb, n)
    live = ip < row_live[None, :]
    col = (rows * nb * ppb)[None, :] + ip
    held = jax.lax.cummax(jnp.where(live, col, -1), axis=1)
    ahead = jax.lax.cummin(jnp.where(live, col, B * nb * ppb - 1), axis=1, reverse=True)
    return ends[-1], rows, blks, tab[jnp.where(held >= 0, held, ahead)].reshape(-1)


def _kernel(rows_ref, blks_ref, len_ref, _pages_ref, q_ref, *refs,
            ps, ppb, n_p, scale, softcap, quant):
    n_in = 4 if quant else 2
    pages = [refs[i * ppb:(i + 1) * ppb] for i in range(n_in)]  # K, V[, K scale, V scale]
    o_ref, acc_ref, m_ref, l_ref = refs[n_in * ppb:]
    s = pl.program_id(0)
    row, blk = rows_ref[s], blks_ref[s]
    valid = len_ref[row]

    @pl.when(blk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def block(i):
        # the block's pages side by side: (KV, ppb * ps, hd), in f32
        parts = [r[...].astype(jnp.float32) for r in pages[i]]
        if quant:
            # dequant-on-gather: each int8 page and its (ps, 1) scale
            # column were copied together; one broadcast multiply restores
            # f32 operands before the MXU pass
            parts = [x * sc[...].astype(jnp.float32) for x, sc in zip(parts, pages[i + 2])]
        return jnp.concatenate(parts, axis=1)

    q = q_ref[0].astype(jnp.float32)                 # (KV, G, hd)
    k, v = block(0), block(1)
    qk = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
    ) * scale                                        # (KV, G, ppb * ps)
    if softcap:
        # gemma-style logit softcap, applied pre-mask so capped scores
        # match the dense decode path bit-for-bit
        qk = jnp.tanh(qk / softcap) * softcap
    tpos = blk * ppb * ps + jax.lax.broadcasted_iota(jnp.int32, qk.shape, 2)
    # slots past the length hold a stale page: masked scores weigh it 0
    qk = jnp.where(tpos < valid, qk, NEG_INF)
    m_prev = m_ref[...]                              # (KV, G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(qk, axis=-1, keepdims=True))
    p = jnp.exp(qk - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    n_blk = jnp.maximum((live_pages(valid, ps, n_p) + ppb - 1) // ppb, 1)

    @pl.when(blk == n_blk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


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
    if l2_tab is not None:
        # two-level gather: logical block ip -> table page -> data page
        block_tab = l2_tab[block_tab].reshape(B, -1)
    n_p = block_tab.shape[1]
    ppb = pages_per_block(ps, KV, hd, pool_k.dtype.itemsize, n_p)
    steps, rows, blks, pages = _work_list(lengths, block_tab, ps, ppb)
    n = rows.shape[0]

    def page_spec(j, cols):
        # slot j of step s holds physical page pages[j * n + s]
        return pl.BlockSpec(
            (None, KV, ps, cols), lambda s, rows, blks, lens, pages: (pages[j * n + s], 0, 0, 0))

    pools = [pool_k, pool_v] + ([pool_ks, pool_vs] if quant else [])
    row = pl.BlockSpec((1, KV, G, hd), lambda s, rows, blks, lens, pages: (rows[s], 0, 0, 0))
    kernel = functools.partial(
        _kernel, ps=ps, ppb=ppb, n_p=n_p, scale=1.0 / (hd ** 0.5), softcap=softcap,
        quant=quant,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(steps,),
        in_specs=[row] + [page_spec(j, p.shape[-1]) for p in pools for j in range(ppb)],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((KV, G, hd), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        # a row's steps run in order: the softmax state carries across them
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="paged_attention_grouped",
        interpret=interpret,
    )(rows, blks, lengths, pages, q, *(p for p in pools for _ in range(ppb)))


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
