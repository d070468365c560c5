"""Shared model substrate: configs, parameter definitions, norms, embeddings.

Pure-JAX (no flax): parameters are nested dicts of arrays; every module
exposes ``*_defs(cfg) -> dict[name, ParamDef]`` so that initialization and
PartitionSpec trees are derived from a single source of truth.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Callable, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# ---------------------------------------------------------------------------
# Configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoECfg:
    n_experts: int
    top_k: int
    every_k: int = 1            # MoE FFN on layers where (layer_idx % every_k == every_k - 1)
    capacity_factor: float = 1.25
    aux_weight: float = 0.01
    fsdp_experts: bool = False  # shard expert weights over 'data' too; all-gather at use


@dataclass(frozen=True)
class MambaCfg:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    chunk: int = 256
    dt_rank: int = 0            # 0 -> ceil(d_model / 16)


@dataclass(frozen=True)
class XLSTMCfg:
    chunk: int = 64
    proj_factor: float = 2.0    # mLSTM up-projection factor
    conv: int = 4
    slstm_ff_factor: float = 1.375  # sLSTM post-FFN factor (4/3 rounded up to /64)


@dataclass(frozen=True)
class EncoderCfg:
    """Encoder stack for enc-dec archs (whisper). Frontend is a stub:
    input_specs() provides precomputed frame embeddings of length n_ctx."""

    n_layers: int
    n_ctx: int
    n_heads: int
    d_ff: int


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0           # 0 -> d_model // n_heads
    block_pattern: tuple = ("attn",)  # mixer types per superblock
    qkv_bias: bool = False
    pos: str = "rope"           # rope | mrope | none | learned
    rope_theta: float = 10000.0
    mrope_sections: tuple = (16, 24, 24)
    norm: str = "rmsnorm"       # rmsnorm | layernorm
    act: str = "silu"
    gated_mlp: bool = True
    moe: Optional[MoECfg] = None
    mamba: Optional[MambaCfg] = None
    xlstm: Optional[XLSTMCfg] = None
    encoder: Optional[EncoderCfg] = None
    cross_attn: bool = False    # decoder layers carry cross-attention (enc-dec)
    inputs: str = "tokens"      # tokens | embeds (vlm backbone)
    tie_embeddings: bool = False
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16
    remat: str = "full"         # full | dots | none
    attn_chunk: int = 1024      # query-chunk size for chunked causal attention
    ce_chunks: int = 8          # sequence chunks for vocab-parallel CE
    kv_quant: bool = False      # int8 KV cache
    kv_cache_dtype: Any = None  # non-quantized KV cache storage dtype
                                # (None -> compute_dtype; ignored when kv_quant)
    use_pallas: bool = False    # select Pallas kernels (TPU target); jnp ref path on CPU
    logit_softcap: float = 0.0
    # --- perf-variant knobs (EXPERIMENTS.md §Perf) ---
    weights_int8: bool = False        # weight-only int8 serving (quant.py)
    attn_scores_bf16: bool = False    # materialize attention scores in bf16
    seq_shard_activations: bool = False  # Megatron-SP: shard seq over TP between blocks
    moe_token_gather: bool = False    # decode MoE: gather tokens, keep experts sharded
    scan_unroll: int = 1              # unroll factor for the layer scan (1 = loop)

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def kv_dtype(self):
        """Storage dtype of non-quantized KV caches/pools."""
        return self.kv_cache_dtype if self.kv_cache_dtype is not None else self.compute_dtype

    @property
    def n_superblocks(self) -> int:
        assert self.n_layers % len(self.block_pattern) == 0, (
            f"{self.name}: n_layers={self.n_layers} not divisible by "
            f"pattern length {len(self.block_pattern)}"
        )
        return self.n_layers // len(self.block_pattern)

    def layer_has_moe(self, pos_in_superblock: int) -> bool:
        if self.moe is None:
            return False
        k = self.moe.every_k
        return pos_in_superblock % k == k - 1

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Parameter definitions: one source of truth for shape / sharding / init
# ---------------------------------------------------------------------------

# Logical axis names used in ParamDef specs; resolved to mesh axes by
# repro.sharding.axes.Rules.
EMBED = "embed"      # d_model dims of weights          -> replicated (or fsdp)
TP = "tp"            # tensor-parallel dim (heads/ff/vocab/d_inner) -> 'model'
FSDP = "fsdp"        # fully-sharded dim                -> 'data'
STACK = "stack"      # superblock stacking dim          -> replicated
NULL = None


@dataclass(frozen=True)
class ParamDef:
    shape: tuple
    axes: tuple           # logical axis per dim (same length as shape)
    init: str = "normal"  # normal | zeros | ones
    scale: float = 0.0    # 0 -> 1/sqrt(fan_in)

    def fan_in(self) -> int:
        if len(self.shape) == 1:
            return self.shape[0]
        return self.shape[-2]


def init_param(rng: jax.Array, d: ParamDef, dtype) -> jax.Array:
    if d.init == "zeros":
        return jnp.zeros(d.shape, dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, dtype)
    scale = d.scale if d.scale else 1.0 / math.sqrt(max(1, d.fan_in()))
    return (jax.random.normal(rng, d.shape, jnp.float32) * scale).astype(dtype)


def init_tree(rng: jax.Array, defs, dtype) -> Any:
    """defs: nested dict of ParamDef -> same-structure dict of arrays."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=lambda x: isinstance(x, ParamDef))
    rngs = jax.random.split(rng, len(leaves))
    out = [init_param(r, d, dtype) for r, d in zip(rngs, leaves)]
    return jax.tree.unflatten(treedef, out)


def shape_tree(defs, dtype) -> Any:
    """ShapeDtypeStruct tree (no allocation) — used by the dry-run."""
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, dtype),
        defs,
        is_leaf=lambda x: isinstance(x, ParamDef),
    )


def stack_defs(defs: Any, n: int) -> Any:
    """Prepend a superblock-stacking dim to every ParamDef in a tree."""

    def f(d: ParamDef) -> ParamDef:
        return ParamDef((n,) + d.shape, (STACK,) + d.axes, d.init, d.scale)

    return jax.tree.map(f, defs, is_leaf=lambda x: isinstance(x, ParamDef))


# ---------------------------------------------------------------------------
# Norms / activations
# ---------------------------------------------------------------------------


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * w.astype(jnp.float32)).astype(x.dtype)


def layernorm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def norm_defs(cfg: ModelConfig, d: int = 0) -> dict:
    d = d or cfg.d_model
    if cfg.norm == "layernorm":
        return {"w": ParamDef((d,), (NULL,), "ones"), "b": ParamDef((d,), (NULL,), "zeros")}
    return {"w": ParamDef((d,), (NULL,), "ones")}


@jax.named_scope("norm")
def apply_norm(cfg: ModelConfig, p: Mapping, x: jax.Array) -> jax.Array:
    if cfg.norm == "layernorm":
        return layernorm(x, p["w"], p["b"])
    if cfg.use_pallas:
        from repro.kernels.rmsnorm import ops as rms_ops

        return rms_ops.rmsnorm(x, p["w"])
    return rmsnorm(x, p["w"])


def activation(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.act == "gelu":
        return jax.nn.gelu(x)
    return jax.nn.silu(x)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def embed_defs(cfg: ModelConfig) -> dict:
    # Embedding table: vocab-sharded over TP (Megatron VocabParallelEmbedding —
    # SPMD lowers the gather as mask-local-rows + psum of partial embeddings;
    # the d-sharded alternative trips an SPMD resharding bug under the
    # microbatch scan). Unembed: vocab sharded over TP for vocab-parallel CE.
    d = {"embedding": ParamDef((cfg.vocab_size, cfg.d_model), (TP, NULL), scale=1.0)}
    if not cfg.tie_embeddings:
        d["unembed"] = ParamDef((cfg.d_model, cfg.vocab_size), (NULL, TP))
    return d


@jax.named_scope("embed")
def embed_tokens(cfg: ModelConfig, p: Mapping, tokens: jax.Array) -> jax.Array:
    x = p["embedding"][tokens]
    return x.astype(cfg.compute_dtype)


def unembed_weight(cfg: ModelConfig, p: Mapping) -> jax.Array:
    if cfg.tie_embeddings:
        return p["embedding"].T
    return p["unembed"]
