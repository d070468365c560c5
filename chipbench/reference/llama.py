"""Plain reference of a llama-architecture decoder: float32, full-sequence
causal attention, no kernels, no cache, no batching tricks.

Follows the published llama description (RMSNorm before attention and MLP,
rotary positions on query and key with the half-split rotation, grouped-query
attention scaled by 1/sqrt(head_dim), SwiGLU MLP, final RMSNorm, output
head, which is the embedding's transpose where ``tie_embeddings`` is set).
``SUPPORTS`` lists the values of each model feature it computes; ``check``
refuses a configuration outside them. It reads the configuration file's
``model`` sizes and regenerates its
weights layer by layer from the seed (``weights.layer_source``); it imports
nothing of the program. Matrix products run at ``Precision.HIGHEST`` so that
a TPU computes them in float32.

The lower-precision controls round both operands of every weight product
and compute the rest as in float32: ``compute="fp8"`` to float8 e4m3 (per
row of the activations, per column of the weights, each scaled to the
format's range), the control of a bfloat16 configuration;
``compute="bf16"`` to bfloat16, the control of a float32 one.
"""
from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
Q_BLOCK = 512           # query rows per attention block


SUPPORTS = {"tie_embeddings": (False, True), "qkv_bias": (False,), "norm": ("rmsnorm",),
            "gated_mlp": (True,)}


def check(m: dict) -> None:
    """Raises unless the ``model`` sizes state a model this reference computes."""
    outside = {k: m[k] for k, ok in SUPPORTS.items() if m[k] not in ok}
    if outside:
        raise ValueError(f"the llama reference does not compute {outside}")


def top_shapes(m: dict) -> Dict[str, tuple]:
    d, V = m["d_model"], m["vocab_size"]
    shapes = {"embedding": (V, d), "final_norm": (d,)}
    if not m["tie_embeddings"]:
        shapes["unembed"] = (d, V)
    return shapes


def block_shapes(m: dict) -> Dict[str, tuple]:
    d, H, KV, hd, f = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"], m["d_ff"]
    return {"attn_norm": (d,), "wq": (d, H * hd), "wk": (d, KV * hd), "wv": (d, KV * hd),
            "wo": (H * hd, d), "mlp_norm": (d,), "w1": (d, f), "w3": (d, f), "w2": (f, d)}


def _fp8(x, axis):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, compute):
    if compute == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    elif compute == "bf16":
        x, w = (a.astype(jnp.bfloat16).astype(jnp.float32) for a in (x, w))
    return jnp.einsum("...d,df->...f", x, w, precision=HI)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (R, T, heads, hd) at positions 0..T-1."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("m", "compute"))
def _block(x, w, m, compute):
    R, T, _ = x.shape
    H, KV, hd, eps = m.n_heads, m.n_kv_heads, m.head_dim, m.rms_norm_eps
    G = H // KV
    h = _rmsnorm(x, w["attn_norm"], eps)
    q = _rope(_mm(h, w["wq"], compute).reshape(R, T, H, hd), m.rope_theta)
    k = _rope(_mm(h, w["wk"], compute).reshape(R, T, KV, hd), m.rope_theta)
    v = _mm(h, w["wv"], compute).reshape(R, T, KV, hd)
    q = q.reshape(R, T, KV, G, hd)
    outs = []
    for s in range(0, T, Q_BLOCK):
        qb = q[:, s:s + Q_BLOCK]
        sc = jnp.einsum("rqkgh,rtkh->rkgqt", qb, k, precision=HI) / np.sqrt(hd)
        qpos = s + jnp.arange(qb.shape[1])
        mask = qpos[:, None] >= jnp.arange(T)[None, :]
        sc = jnp.where(mask, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("rkgqt,rtkh->rqkgh", p, v, precision=HI))
    o = jnp.concatenate(outs, axis=1).reshape(R, T, H * hd)
    x = x + _mm(o, w["wo"], compute)
    h = _rmsnorm(x, w["mlp_norm"], eps)
    g = jax.nn.silu(_mm(h, w["w1"], compute)) * _mm(h, w["w3"], compute)
    return x + _mm(g, w["w2"], compute)


@functools.partial(jax.jit, static_argnames=("m", "compute"))
def _head(x, rows, w, m, compute):
    """Logits at the gathered rows: x (R, T, d), rows (R, W) -> (R, W, V)."""
    xw = jnp.take_along_axis(x, rows[:, :, None], axis=1)
    head = w["embedding"].T if m.tie_embeddings else w["unembed"]
    return _mm(_rmsnorm(xw, w["final_norm"], m.rms_norm_eps), head, compute)


class _Sizes(tuple):
    """Hashable view of the ``model`` sizes for jit's static arguments."""

    def __new__(cls, m: dict):
        keys = ("n_heads", "n_kv_heads", "head_dim", "rms_norm_eps", "rope_theta",
                "tie_embeddings")
        obj = super().__new__(cls, tuple(m[k] for k in keys))
        for k, v in zip(keys, obj):
            setattr(obj, k, v)
        return obj


def logits(m: dict, top, block, tokens: List[np.ndarray], rows: List[np.ndarray],
           compute: str = "highest", pad_to: int = 256) -> jax.Array:
    """Reference logits of each sequence at its ``rows`` (positions whose next
    token is wanted), computed layer by layer. ``top(0)`` / ``block(i)`` give
    the weights of the embedding and head / of block i (1-based), as float32.
    Returns (R, W, V) on the device; row lists shorter than W are padded with
    their last row."""
    R = len(tokens)
    T = -(-max(len(t) for t in tokens) // pad_to) * pad_to
    W = max(len(r) for r in rows)
    tok = np.zeros((R, T), np.int32)
    rw = np.zeros((R, W), np.int32)
    for i, (t, r) in enumerate(zip(tokens, rows)):
        tok[i, :len(t)] = t
        rw[i, :len(r)] = r
        rw[i, len(r):] = r[-1]
    sizes = _Sizes(m)
    t0 = top(0)
    x = jnp.take(t0["embedding"], jnp.asarray(tok), axis=0)
    for layer in range(1, m["n_layers"] + 1):
        x = _block(x, block(layer), sizes, compute)
    return _head(x, jnp.asarray(rw), t0, sizes, compute)
