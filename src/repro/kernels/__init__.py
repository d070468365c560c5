"""Pallas kernels for the serving hot path (paged decode attention and its
prefill-write twin, flash and dense decode attention, rmsnorm, chunkwise
mLSTM). Each kernel package holds ``kernel.py`` (the ``pallas_call``),
``ops.py`` (the model-facing wrapper) and ``ref.py`` (the jnp oracle)."""
from __future__ import annotations

import jax


def interpret() -> bool:
    """Whether Pallas kernels run in the interpreter: exactly when JAX's
    default backend is the CPU, which has no Mosaic compiler. Every
    ``ops.py`` asks at trace time, so a TPU run always compiles its
    kernels."""
    return jax.default_backend() == "cpu"
