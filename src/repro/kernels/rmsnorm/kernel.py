"""Fused RMSNorm — Pallas TPU kernel (rows tiled through VMEM, f32 math)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) * w_ref[...].astype(jnp.float32)).astype(
        o_ref.dtype
    )


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm_2d(x: jax.Array, w: jax.Array, eps: float = 1e-6,
               block_rows: int = 256, *, interpret: bool) -> jax.Array:
    R, D = x.shape
    block_rows = min(block_rows, R)
    assert R % block_rows == 0, (R, block_rows)
    return pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(R // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda r: (r, 0)),
            pl.BlockSpec((D,), lambda r: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, D), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((R, D), x.dtype),
        name="rmsnorm_2d",
        interpret=interpret,
    )(x, w)
