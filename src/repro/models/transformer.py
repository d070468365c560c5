"""Decoder stack: superblock pattern -> scan over superblocks.

A *superblock* is one repetition of ``cfg.block_pattern`` (e.g. 1 layer for
dense archs; 1 attn + 7 mamba for Jamba; 7 mLSTM + 1 sLSTM for xLSTM). All
superblocks are structurally identical, so their parameters are stacked on a
leading axis and the stack is a single ``lax.scan`` — keeping the HLO (and
compile time at 512 devices) independent of depth. Remat wraps the scan body.

Cache: a pytree whose leaves carry a leading (n_superblocks,) axis; the scan
consumes/produces it as xs/ys.
"""
from __future__ import annotations

from typing import Any, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import attention as attn
from repro.models import mamba as mam
from repro.models import xlstm as xl
from repro.models.common import (
    ModelConfig,
    ParamDef,
    apply_norm,
    embed_defs,
    embed_tokens,
    init_tree,
    norm_defs,
    shape_tree,
    stack_defs,
)
from repro.models.mlp import mlp, mlp_defs
from repro.models.moe import moe_defs, moe_ffn

MIXER_KINDS = ("attn", "mamba", "mlstm", "slstm")


@jax.custom_vjp
def _loop_barrier(tree):
    """``optimization_barrier`` that is transparent to reverse-mode AD.

    The barrier primitive has no differentiation rule; training (jax.grad)
    through the superblock scan needs one. The pass-through VJP keeps the
    barrier in the primal graph (where it blocks loop-invariant hoisting of
    weight gathers / upcasts / dequants) while the cotangent flows through
    untouched."""
    return jax.lax.optimization_barrier(tree)


def _loop_barrier_fwd(tree):
    return jax.lax.optimization_barrier(tree), None


def _loop_barrier_bwd(_, ct):
    return (ct,)


_loop_barrier.defvjp(_loop_barrier_fwd, _loop_barrier_bwd)


# ---------------------------------------------------------------------------
# Param / cache definitions
# ---------------------------------------------------------------------------


def superblock_defs(cfg: ModelConfig) -> dict:
    defs: dict = {}
    for i, kind in enumerate(cfg.block_pattern):
        defs[f"l{i}_norm"] = norm_defs(cfg)
        if kind == "attn":
            defs[f"l{i}_mixer"] = attn.attention_defs(cfg)
        elif kind == "mamba":
            defs[f"l{i}_mixer"] = mam.mamba_defs(cfg)
        elif kind == "mlstm":
            defs[f"l{i}_mixer"] = xl.mlstm_defs(cfg)
        elif kind == "slstm":
            defs[f"l{i}_mixer"] = xl.slstm_defs(cfg)
        else:
            raise ValueError(kind)
        if cfg.cross_attn:
            defs[f"l{i}_cross_norm"] = norm_defs(cfg)
            defs[f"l{i}_cross"] = attn.attention_defs(cfg, cross=True)
        if cfg.d_ff > 0:
            defs[f"l{i}_ffn_norm"] = norm_defs(cfg)
            if cfg.layer_has_moe(i):
                defs[f"l{i}_ffn"] = moe_defs(cfg)
            else:
                defs[f"l{i}_ffn"] = mlp_defs(cfg)
    return defs


def param_defs(cfg: ModelConfig) -> dict:
    defs = dict(embed_defs(cfg))
    defs["blocks"] = stack_defs(superblock_defs(cfg), cfg.n_superblocks)
    defs["final_norm"] = norm_defs(cfg)
    return defs


def init(rng: jax.Array, cfg: ModelConfig) -> Any:
    return init_tree(rng, param_defs(cfg), cfg.param_dtype)


def param_shapes(cfg: ModelConfig) -> Any:
    return shape_tree(param_defs(cfg), cfg.param_dtype)


def _stack_shape(defs: Mapping, n: int) -> Any:
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), defs
    )


def cache_defs(cfg: ModelConfig, batch: int, cap: int, enc_len: int = 0) -> dict:
    """ShapeDtypeStructs for the full decode cache (leading n_sb axis)."""
    per_sb: dict = {}
    for i, kind in enumerate(cfg.block_pattern):
        if kind == "attn":
            per_sb[f"l{i}_mixer"] = attn.kv_cache_defs(cfg, batch, cap)
        elif kind == "mamba":
            per_sb[f"l{i}_mixer"] = mam.mamba_cache_defs(cfg, batch)
        elif kind == "mlstm":
            per_sb[f"l{i}_mixer"] = xl.mlstm_cache_defs(cfg, batch)
        elif kind == "slstm":
            per_sb[f"l{i}_mixer"] = xl.slstm_cache_defs(cfg, batch)
        if cfg.cross_attn:
            assert enc_len > 0
            per_sb[f"l{i}_cross"] = {
                "k": jax.ShapeDtypeStruct((batch, enc_len, cfg.n_heads, cfg.hd), cfg.compute_dtype),
                "v": jax.ShapeDtypeStruct((batch, enc_len, cfg.n_heads, cfg.hd), cfg.compute_dtype),
            }
    return {"blocks": _stack_shape(per_sb, cfg.n_superblocks)}


def init_cache(cfg: ModelConfig, batch: int, cap: int, enc_len: int = 0) -> dict:
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cache_defs(cfg, batch, cap, enc_len))


def paged_cache_defs(cfg: ModelConfig, batch: int, num_pages: int, page_size: int) -> dict:
    """Paged decode cache: attention layers share a per-layer page pool
    (no per-slot max_len stripes — serving/paging.py hands out pages);
    recurrent mixers (mamba/xlstm) keep O(1) per-slot state as before."""
    if cfg.cross_attn:
        raise NotImplementedError("paged cache does not support cross-attention")
    per_sb: dict = {}
    for i, kind in enumerate(cfg.block_pattern):
        if kind == "attn":
            per_sb[f"l{i}_mixer"] = attn.paged_kv_pool_defs(cfg, num_pages, page_size)
        elif kind == "mamba":
            per_sb[f"l{i}_mixer"] = mam.mamba_cache_defs(cfg, batch)
        elif kind == "mlstm":
            per_sb[f"l{i}_mixer"] = xl.mlstm_cache_defs(cfg, batch)
        elif kind == "slstm":
            per_sb[f"l{i}_mixer"] = xl.slstm_cache_defs(cfg, batch)
    return {"blocks": _stack_shape(per_sb, cfg.n_superblocks)}


def init_paged_cache(cfg: ModelConfig, batch: int, num_pages: int, page_size: int) -> dict:
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), paged_cache_defs(cfg, batch, num_pages, page_size)
    )


def chunk_state_defs(cfg: ModelConfig, batch: int = 1) -> dict:
    """ShapeDtypeStructs for the chunked-prefill recurrent carry: one entry
    per NON-attention mixer (attention chunks live directly in the KV
    cache/pool). The carry is deliberately OUTSIDE the decode cache: while a
    sequence is mid-prefill, batched decode steps for other slots still
    sweep every slot's in-cache recurrent state with garbage updates — the
    engine keeps the authoritative state here and installs it into the slot
    only when the last chunk completes."""
    per_sb: dict = {}
    for i, kind in enumerate(cfg.block_pattern):
        if kind == "mamba":
            per_sb[f"l{i}_mixer"] = mam.mamba_cache_defs(cfg, batch)
        elif kind == "mlstm":
            per_sb[f"l{i}_mixer"] = xl.mlstm_cache_defs(cfg, batch)
        elif kind == "slstm":
            per_sb[f"l{i}_mixer"] = xl.slstm_cache_defs(cfg, batch)
    return {"blocks": _stack_shape(per_sb, cfg.n_superblocks)}


def init_chunk_state(cfg: ModelConfig, batch: int = 1) -> dict:
    """Zero carry for the first chunk of a chunked prefill (fresh sequence);
    attention-only models get an empty (leafless) tree."""
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), chunk_state_defs(cfg, batch))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _constrain(ctx, x: jax.Array, seq_shard: bool = False) -> jax.Array:
    if ctx is None:
        return x
    seq_ax = None
    if seq_shard and x.shape[1] % ctx.tp_size == 0:
        # Megatron-style sequence parallelism: activations between blocks live
        # seq-sharded over TP, so XLA emits reduce-scatter + all-gather pairs
        # instead of all-reduces — half the TP wire bytes.
        seq_ax = ctx.tp_axis
    sh = jax.sharding.NamedSharding(ctx.mesh, P(ctx.batch_spec_for(x.shape[0]), seq_ax, None))
    return jax.lax.with_sharding_constraint(x, sh)


def _superblock(
    cfg: ModelConfig,
    ctx,
    p: Mapping,
    x: jax.Array,
    positions,
    mode: str,
    cache_sb: Optional[Mapping],
    cache_index,
    enc_out,
    causal: bool,
    valid=None,
    chunk_sb=None,
):
    new_cache: dict = {}
    new_chunk: dict = {}
    aux = jnp.zeros((), jnp.float32)
    x = _constrain(ctx, x, cfg.seq_shard_activations)
    # Block loop-invariant code motion out of the layer scan: without the
    # barrier XLA hoists (a) FSDP weight all-gathers (materializing every
    # layer's gathered experts at once — 100s of GB for llama4/jamba),
    # (b) bf16->f32 weight upcasts (CPU backend), (c) int8->bf16 KV-cache
    # dequants — all per-layer transients that must stay inside the loop.
    p = _loop_barrier(p)
    if cache_sb is not None:
        cache_sb = _loop_barrier(cache_sb)
    if chunk_sb is not None:
        chunk_sb = _loop_barrier(chunk_sb)
    # Paged prefill: attention layers write straight through the sequence's
    # block-table row into the shared page pool; recurrent mixers run from a
    # zero state (a fresh sequence) and their final state lands in the slot.
    paged_pf = isinstance(cache_index, attn.PagedPrefillIndex)
    # Chunked prefill: attention layers write this chunk at its offset;
    # recurrent mixers resume from (and return) the explicit chunk_sb carry
    # while the in-cache slot state is passed through untouched — the engine
    # installs the carry only after the final chunk (see chunk_state_defs).
    chunk_pf = isinstance(
        cache_index, (attn.ChunkPrefillIndex, attn.PagedChunkPrefillIndex)
    )
    recurrent = {"mamba": mam.mamba_mixer, "mlstm": xl.mlstm_mixer, "slstm": xl.slstm_mixer}
    for i, kind in enumerate(cfg.block_pattern):
        h = apply_norm(cfg, p[f"l{i}_norm"], x)
        c_in = cache_sb.get(f"l{i}_mixer") if cache_sb is not None else None
        if kind == "attn":
            with jax.named_scope("attn"):
                h, c_out = attn.self_attention(
                    cfg, p[f"l{i}_mixer"], h, positions, mode, c_in, cache_index, causal=causal
                )
        elif chunk_pf and chunk_sb is not None:
            s_in = chunk_sb[f"l{i}_mixer"]
            h, s_out = recurrent[kind](cfg, p[f"l{i}_mixer"], h, mode, s_in, valid=valid)
            new_chunk[f"l{i}_mixer"] = s_out
            c_out = c_in
        elif paged_pf and c_in is not None:
            zero = jax.tree.map(lambda l: jnp.zeros((1,) + l.shape[1:], l.dtype), c_in)
            h, c_part = recurrent[kind](cfg, p[f"l{i}_mixer"], h, mode, zero, valid=valid)
            c_out = jax.tree.map(
                lambda full, part: jax.lax.dynamic_update_slice_in_dim(
                    full, part.astype(full.dtype), cache_index.slot, axis=0
                ),
                c_in,
                c_part,
            )
        else:
            h, c_out = recurrent[kind](cfg, p[f"l{i}_mixer"], h, mode, c_in, valid=valid)
        x = x + h
        if cache_sb is not None:
            new_cache[f"l{i}_mixer"] = c_out
        if cfg.cross_attn:
            h = apply_norm(cfg, p[f"l{i}_cross_norm"], x)
            if mode == "train":
                kv = attn.cross_kv(cfg, p[f"l{i}_cross"], enc_out)
            elif mode == "prefill":
                kv = attn.cross_kv(cfg, p[f"l{i}_cross"], enc_out)
                new_cache[f"l{i}_cross"] = jax.tree.map(lambda a: a.astype(cfg.compute_dtype), kv)
            else:  # decode
                kv = cache_sb[f"l{i}_cross"]
                new_cache[f"l{i}_cross"] = kv
            x = x + attn.cross_attention(cfg, p[f"l{i}_cross"], h, kv)
        if cfg.d_ff > 0:
            h = apply_norm(cfg, p[f"l{i}_ffn_norm"], x)
            with jax.named_scope("mlp"):
                if cfg.layer_has_moe(i):
                    h, a = moe_ffn(cfg, ctx, p[f"l{i}_ffn"], h, valid=valid)
                    aux = aux + a
                else:
                    h = mlp(cfg, p[f"l{i}_ffn"], h)
            x = x + h
        x = _constrain(ctx, x, cfg.seq_shard_activations)
    return (
        x,
        (new_cache if cache_sb is not None else None),
        (new_chunk if chunk_sb is not None else None),
        aux,
    )


def _remat_wrap(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def run_stack(
    cfg: ModelConfig,
    ctx,
    blocks_params,
    x: jax.Array,
    positions,
    mode: str,
    cache: Optional[Mapping] = None,
    cache_index=None,
    enc_out=None,
    causal: bool = True,
    valid=None,
    chunk_state=None,
):
    """Scan the superblock stack. Returns (x, new_cache, new_chunk_state,
    aux); ``new_chunk_state`` is None unless ``chunk_state`` (the chunked
    prefill recurrent carry, scanned alongside the cache) was given."""
    remat = mode == "train" and cfg.remat != "none"

    if cache is None:
        def body(carry, p_sb):
            xx, aux = carry
            xx, _, _, a = _superblock(cfg, ctx, p_sb, xx, positions, mode, None, cache_index, enc_out, causal, valid)
            return (xx, aux + a), None

        body = _remat_wrap(cfg, body) if remat else body
        (x, aux), _ = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), blocks_params, unroll=cfg.scan_unroll
        )
        return x, None, None, aux

    if chunk_state is not None:
        def body(carry, sb):
            xx, aux = carry
            p_sb, c_sb, s_sb = sb
            xx, c_new, s_new, a = _superblock(
                cfg, ctx, p_sb, xx, positions, mode, c_sb, cache_index, enc_out,
                causal, valid, chunk_sb=s_sb,
            )
            return (xx, aux + a), (c_new, s_new)

        body = _remat_wrap(cfg, body) if remat else body
        (x, aux), (new_blocks, new_state) = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)),
            (blocks_params, cache["blocks"], chunk_state["blocks"]),
            unroll=cfg.scan_unroll,
        )
        return x, {"blocks": new_blocks}, {"blocks": new_state}, aux

    def body(carry, sb):
        xx, aux = carry
        p_sb, c_sb = sb
        xx, c_new, _, a = _superblock(cfg, ctx, p_sb, xx, positions, mode, c_sb, cache_index, enc_out, causal, valid)
        return (xx, aux + a), c_new

    body = _remat_wrap(cfg, body) if remat else body
    (x, aux), new_blocks = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), (blocks_params, cache["blocks"]),
        unroll=cfg.scan_unroll,
    )
    return x, {"blocks": new_blocks}, None, aux


def forward(
    cfg: ModelConfig,
    ctx,
    params: Mapping,
    tokens: Optional[jax.Array] = None,
    inputs_embeds: Optional[jax.Array] = None,
    positions=None,
    mode: str = "train",
    cache: Optional[Mapping] = None,
    cache_index=None,
    enc_out=None,
    n_valid=None,
    chunk_state=None,
) -> Tuple[jax.Array, Optional[Mapping], jax.Array]:
    """Returns (hidden (B,S,d) post-final-norm, new_cache, moe_aux) — or,
    when ``chunk_state`` is given (chunked prefill), the 4-tuple
    (hidden, new_cache, new_chunk_state, moe_aux).

    ``n_valid`` (B,) marks right-padded prefill: tokens at positions >=
    n_valid[b] are padding and must be identity for every stateful update —
    causal attention ignores them for free, recurrent mixers and the MoE
    router receive the derived ``valid`` mask. (Chunked prefill caveat: the
    MoE capacity competition is per-CHUNK, so expert drops can differ from a
    whole-prompt prefill when capacity binds.)"""
    if inputs_embeds is not None:
        x = inputs_embeds.astype(cfg.compute_dtype)
    else:
        x = embed_tokens(cfg, params, tokens)
    valid = None
    if n_valid is not None:
        S = x.shape[1]
        nv = jnp.asarray(n_valid, jnp.int32).reshape(-1, 1)
        valid = jnp.arange(S, dtype=jnp.int32)[None, :] < nv
    x = _constrain(ctx, x)
    x, new_cache, new_chunk, aux = run_stack(
        cfg, ctx, params["blocks"], x, positions, mode, cache, cache_index, enc_out,
        valid=valid, chunk_state=chunk_state,
    )
    x = apply_norm(cfg, params["final_norm"], x)
    if chunk_state is not None:
        return x, new_cache, new_chunk, aux
    return x, new_cache, aux
