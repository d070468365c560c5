"""Seeded random weights, made by the benchmark on the device.

The configuration's ``layout`` maps each leaf path of the program's
parameter tree (``"blocks/l0_mixer/wq"``) to a plain weight name, and its
``weights.rules`` give each plain name an init rule. A weight's values
depend only on the seed, its plain name and its layer, so the reference
regenerates any one layer by itself and never reads what the program holds.

Rules: ``unit`` draws N(0, 1); ``fan_in`` draws N(0, 1) / sqrt(rows);
``fan_out`` draws N(0, 1) / sqrt(columns) (an embedding that is also the
output head, so that its logits have the scale of a ``fan_in`` head's);
``norm`` draws 1 + 0.1 N(0, 1). Values are drawn in float32 and rounded to
the served dtype; the reference rounds them the same way, so both run the
same weights.
"""
from __future__ import annotations

import math
import zlib
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed (wider than 31 bits too)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def draw(key: jax.Array, name: str, rule: str, layer, shape: Tuple[int, ...]) -> jax.Array:
    """float32 values of weight ``name`` in ``layer`` (0 for unstacked)."""
    k = jax.random.fold_in(jax.random.fold_in(key, layer), zlib.crc32(name.encode()) & 0x7FFFFFFF)
    z = jax.random.normal(k, shape, jnp.float32)
    if rule == "unit":
        return z
    if rule == "fan_in":
        return z / math.sqrt(shape[-2])
    if rule == "fan_out":
        return z / math.sqrt(shape[-1])
    if rule == "norm":
        return 1.0 + 0.1 * z
    raise ValueError(f"unknown init rule {rule!r}")


def _path(p) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in p)


def make_params(shapes, leaves: Dict[str, str], rules: Dict[str, str], stacked: str,
                seed: int, dtype):
    """The program's parameter tree (``shapes``: its ShapeDtypeStruct tree),
    filled from the seed in one jitted call. Leaves under ``stacked`` carry a
    leading layer axis and are drawn layer by layer inside the call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [_path(p) for p, _ in flat]
    missing = [p for p in paths if p not in leaves]
    if missing:
        raise KeyError(f"layout names no weight for {missing}")

    def build(key):
        out = []
        for path, (_, s) in zip(paths, flat):
            name = leaves[path]
            rule = rules[name]
            if path.startswith(stacked + "/"):
                layers = jnp.arange(s.shape[0], dtype=jnp.uint32)
                v = jax.lax.map(
                    lambda i, n=name, r=rule, sh=s.shape[1:]: draw(key, n, r, i + 1, sh).astype(dtype),
                    layers)
            else:
                v = draw(key, name, rule, 0, s.shape).astype(dtype)
            out.append(v)
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(seed_key(seed))


def layer_source(seed: int, shapes: Dict[str, Tuple[Tuple[int, ...], str]], dtype) -> Callable:
    """For the reference: ``get(layer) -> {name: float32 array}`` for the
    weights ``shapes`` names (``name -> (shape, rule)``), rounded through the
    served dtype. Layer 0 holds the unstacked weights, layer i + 1 block i."""
    key = seed_key(seed)

    @jax.jit
    def one(layer):
        return {n: draw(key, n, r, layer, sh).astype(dtype).astype(jnp.float32)
                for n, (sh, r) in shapes.items()}

    return lambda layer: one(jnp.uint32(layer))
