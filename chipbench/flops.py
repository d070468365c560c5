"""Work a request needs, counted from the configuration's shapes.

The arithmetic of the program's ``launch/flops.py`` (2 FLOPs per parameter
per token, the embedding gather excluded), plus attention: 4 * heads *
head_dim FLOPs per layer for each key a query attends to (its scores and its
weighted values). The output head counts once per emitted token. The bytes
of the paged decode are those it must move: the live key and value pages of
each decoding sequence, its query and its output.
"""
from __future__ import annotations


def body_params(m: dict) -> int:
    """Parameters of the transformer blocks' matrix products."""
    d, H, KV, hd, f = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"], m["d_ff"]
    return m["n_layers"] * (d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * f)


def head_params(m: dict) -> int:
    return m["d_model"] * m["vocab_size"]


def token_flops(m: dict, position: int, emits: bool) -> float:
    """FLOPs of one token at ``position`` (attending to position + 1 keys);
    ``emits``: its logits are needed (the last prompt token, every decoded
    one)."""
    attn = 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * (position + 1)
    return 2.0 * body_params(m) + attn + (2.0 * head_params(m) if emits else 0.0)


def span_flops(m: dict, start: int, stop: int, emits_last: bool) -> float:
    """FLOPs of the tokens at positions start .. stop - 1 (one prefill chunk,
    no logits but possibly the last one's)."""
    n = stop - start
    if n <= 0:
        return 0.0
    keys = n * (start + stop + 1) / 2.0                   # sum of (p + 1)
    attn = 4.0 * m["n_layers"] * m["n_heads"] * m["head_dim"] * keys
    return 2.0 * body_params(m) * n + attn + (2.0 * head_params(m) if emits_last else 0.0)


def paged_decode_bytes(m: dict, position: int, page: int, kv_bytes: int, act_bytes: int) -> float:
    """Bytes one decoding sequence's paged attention must move over all
    layers: the key and value pages covering positions 0..position, its
    query and its output."""
    pages = -(-(position + 1) // page)
    kv = pages * page * m["n_kv_heads"] * m["head_dim"] * 2 * kv_bytes
    qo = 2 * m["n_heads"] * m["head_dim"] * act_bytes
    return float(m["n_layers"] * (kv + qo))


def paged_decode_flops(m: dict, position: int) -> float:
    """FLOPs of one decoding sequence's attention over all layers."""
    return 4.0 * m["n_layers"] * m["n_heads"] * m["head_dim"] * (position + 1)
