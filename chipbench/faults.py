"""Faults planted in the timed path, to show that ``correct`` catches them.
Each takes one engine and breaks its decode program where it is produced;
none is used by a benchmark run.

- ``token``: the token of slot 0 is altered (the next id);
- ``cache``: the step returns the paged pools unchanged, so the keys and
  values of decoded tokens are never written.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def token(eng) -> None:
    inner, vocab = eng._decode, eng.cfg.vocab_size

    def decode(*args):
        nxt, cache = inner(*args)
        return nxt.at[0].set((nxt[0] + 1) % vocab), cache

    eng._decode = decode


def cache(eng) -> None:
    inner = eng._decode

    def decode(params, pools, *rest):
        before = jax.tree.map(jnp.copy, pools)
        nxt, _ = inner(params, pools, *rest)
        return nxt, before

    eng._decode = decode


FAULTS = {"token": token, "cache": cache}
