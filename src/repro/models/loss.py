"""Losses: chunked vocab-parallel cross-entropy (+ z-loss, MoE aux).

The unembedding is sharded over the TP axis on the vocab dim. Materializing
(B, S, V) logits replicated would cost e.g. 1M tokens x 202k vocab x 4 B
~ 800 GB for llama4 — instead we (a) keep logits TP-sharded via a sharding
constraint, (b) scan over ``cfg.ce_chunks`` sequence chunks so the live
logits slice is (B, S/chunks, V/tp), and (c) avoid one-hot materialization
by an iota-mask gather that stays sharded.
"""
from __future__ import annotations

from typing import Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import ModelConfig, unembed_weight
from repro.models.quant import qeinsum

IGNORE = -1


def _chunk_ce(cfg: ModelConfig, ctx, w, x_c: jax.Array, labels_c: jax.Array):
    """x_c: (B, C, d); labels_c: (B, C) int32. Returns (sum_loss, sum_z2, count)."""
    logits = qeinsum("bcd,dv->bcv", x_c, w).astype(jnp.float32)
    if ctx is not None:
        vocab_ax = ctx.tp_axis if logits.shape[-1] % ctx.tp_size == 0 else None
        logits = jax.lax.with_sharding_constraint(
            logits,
            jax.sharding.NamedSharding(
                ctx.mesh, P(ctx.batch_spec_for(logits.shape[0]), None, vocab_ax)
            ),
        )
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    z = jax.nn.logsumexp(logits, axis=-1)                       # (B, C)
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
    ll = jnp.sum(
        jnp.where(vocab_iota == labels_c[..., None], logits, 0.0), axis=-1
    )                                                           # (B, C)
    valid = labels_c != IGNORE
    loss = jnp.where(valid, z - ll, 0.0)
    return loss.sum(), jnp.where(valid, z * z, 0.0).sum(), valid.sum()


def lm_loss(
    cfg: ModelConfig,
    ctx,
    params: Mapping,
    hidden: jax.Array,          # (B, S, d) — final-normed
    labels: jax.Array,          # (B, S) int32, IGNORE to mask
    z_weight: float = 1e-4,
) -> Tuple[jax.Array, dict]:
    B, S, d = hidden.shape
    w = unembed_weight(cfg, params)
    nc = cfg.ce_chunks if S % cfg.ce_chunks == 0 else 1
    if nc == 1:
        sl, sz, cnt = _chunk_ce(cfg, ctx, w, hidden, labels)
    else:
        C = S // nc
        xs = (
            hidden.reshape(B, nc, C, d).swapaxes(0, 1),
            labels.reshape(B, nc, C).swapaxes(0, 1),
        )

        @jax.checkpoint  # recompute the logits chunk in bwd instead of saving
        def body(carry, args):
            x_c, l_c = args
            sl, sz, cnt = _chunk_ce(cfg, ctx, w, x_c, l_c)
            return (carry[0] + sl, carry[1] + sz, carry[2] + cnt), None

        (sl, sz, cnt), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)), xs
        )
    denom = jnp.maximum(cnt, 1).astype(jnp.float32)
    loss = sl / denom
    zloss = z_weight * sz / denom
    return loss + zloss, {"ce": loss, "z": zloss, "tokens": denom}


def next_tokens_all(cfg: ModelConfig, ctx, params: Mapping, hidden: jax.Array) -> jax.Array:
    """Greedy next-token ids at EVERY position: (B, S, d) -> (B, S) int32.

    The speculative-decode verify pass needs the greedy continuation after
    each verified position in one shot. Argmax is monotone under the tanh
    softcap, so (matching ``next_tokens``) the cap is skipped — ids are
    identical either way and the (B, S, V) logits slice stays transient."""
    with jax.named_scope("head"):
        w = unembed_weight(cfg, params)
        logits = qeinsum("bsd,dv->bsv", hidden, w).astype(jnp.float32)
        if ctx is not None:
            vocab_ax = ctx.tp_axis if logits.shape[-1] % ctx.tp_size == 0 else None
            logits = jax.lax.with_sharding_constraint(
                logits, jax.sharding.NamedSharding(ctx.mesh, P(None, None, vocab_ax))
            )
    with jax.named_scope("sample"):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def next_tokens(cfg: ModelConfig, ctx, params: Mapping, hidden_last: jax.Array) -> jax.Array:
    """Greedy next-token ids from final hidden states (B, 1|S, d) -> (B,).

    Argmax over the TP-sharded vocab dim stays a cheap sharded reduce —
    serve_step outputs token ids, never full logits.
    """
    with jax.named_scope("head"):
        w = unembed_weight(cfg, params)
        x = hidden_last[:, -1, :]
        logits = qeinsum("bd,dv->bv", x, w).astype(jnp.float32)
        if ctx is not None:
            vocab_ax = ctx.tp_axis if logits.shape[-1] % ctx.tp_size == 0 else None
            logits = jax.lax.with_sharding_constraint(
                logits, jax.sharding.NamedSharding(ctx.mesh, P(None, vocab_ax))
            )
    with jax.named_scope("sample"):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
