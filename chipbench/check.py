"""How ``correct`` is decided: the served tokens of a seeded sample of
finished requests against the plain reference.

For each sampled request the reference runs once over its prompt and its
served tokens, and each served token's logit is compared with the
reference's best logit at that position: the gap is 0 where the served token
is the reference's greedy choice and grows with how far below the best it
lies. The widest gap over all sampled tokens is compared with its limit.
"""
from __future__ import annotations

from typing import Dict, List

import jax.numpy as jnp
import numpy as np

import weights


def sample(finished: List[dict], n: int, seed: int) -> List[dict]:
    """``n`` finished requests drawn from the seed: the longest (prompt plus
    output) first, then one from each tier not yet in the sample, then the
    rest at random."""
    if not finished:
        return []
    rng = np.random.default_rng([int(seed), 9])
    order = [finished[i] for i in rng.permutation(len(finished))]
    picked = [max(order, key=lambda r: len(r["prompt"]) + len(r["out"]))]
    for r in order:
        if r["tier"] not in {p["tier"] for p in picked}:
            picked.append(r)
    for r in order:
        if len(picked) >= n:
            break
        if all(r is not p for p in picked):
            picked.append(r)
    return picked


def reference_logits(parts, cfg: dict, seed: int, reqs: List[dict], compute: str):
    ref = parts.reference(cfg["reference"])
    m, rules = cfg["model"], cfg["weights"]["rules"]
    dtype = jnp.dtype(cfg["weights"]["dtype"])
    top = weights.layer_source(seed, {k: (s, rules[k]) for k, s in ref.top_shapes(m).items()}, dtype)
    blk = weights.layer_source(seed, {k: (s, rules[k]) for k, s in ref.block_shapes(m).items()}, dtype)
    tokens = [np.asarray(r["prompt"] + r["out"][:-1]) for r in reqs]
    rows = [np.arange(len(r["prompt"]) - 1, len(r["prompt"]) - 1 + len(r["out"])) for r in reqs]
    return ref.logits(m, top, blk, tokens, rows, compute)


def _served(reqs: List[dict]):
    W = max(len(r["out"]) for r in reqs)
    served = np.zeros((len(reqs), W), np.int32)
    valid = np.zeros((len(reqs), W), bool)
    for i, r in enumerate(reqs):
        served[i, :len(r["out"])] = r["out"]
        valid[i, :len(r["out"])] = True
    return jnp.asarray(served), valid


def gaps(logits, picked) -> np.ndarray:
    """Best logit minus the picked token's logit, per position (R, W)."""
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, picked[:, :, None], axis=-1)[..., 0]
    return np.asarray(best - got)


def compare(parts, cfg: dict, seed: int, reqs: List[dict], control: bool = False) -> Dict[str, float]:
    """Widest and mean gap of the served tokens. With ``control`` the tokens
    judged are, in their place, those that the reference computed in the
    configuration's ``control`` precision (the one below its own) puts first
    at each served position."""
    served, valid = _served(reqs)
    ref = reference_logits(parts, cfg, seed, reqs, "highest")
    if control:
        low = reference_logits(parts, cfg, seed, reqs, cfg["control"])
        served = jnp.argmax(low, axis=-1).astype(jnp.int32)
    g = gaps(ref, served)[valid]
    return {"max_logit_gap": float(g.max()), "mean_logit_gap": float(g.mean()),
            "greedy_mismatch": int((g > 0).sum()), "tokens": int(valid.sum())}
