"""Work counted from shapes (``flops.py``) against hand counts for both
configurations of the benchmark."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import flops  # noqa: E402


def model(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())["model"]


# per layer: wq d*H*hd, wk and wv d*KV*hd each, wo H*hd*d, SwiGLU 3*d*f
HAND = {
    "smollm-360m": {
        "layer": 960 * 960 + 2 * 960 * 320 + 960 * 960 + 3 * 960 * 2560,     # 9,830,400
        "layers": 32, "head": 960 * 49152, "attn_per_key": 4 * 32 * 15 * 64,
    },
    "granite-8b-18l": {
        "layer": 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096 + 3 * 4096 * 14336,   # 218,103,808
        "layers": 18, "head": 4096 * 49152, "attn_per_key": 4 * 18 * 32 * 128,
    },
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_params(name):
    m, h = model(name), HAND[name]
    assert flops.body_params(m) == h["layers"] * h["layer"]
    assert flops.head_params(m) == h["head"]


def test_hand_totals():
    assert HAND["smollm-360m"]["layer"] == 9_830_400
    assert HAND["granite-8b-18l"]["layer"] == 218_103_808
    # Granite's 18 layers with embedding and head: 4.33 B parameters
    g = model("granite-8b-18l")
    assert flops.body_params(g) + 2 * flops.head_params(g) == pytest.approx(4.33e9, rel=1e-3)


@pytest.mark.parametrize("name", sorted(HAND))
def test_token_and_span(name):
    m, h = model(name), HAND[name]
    body = 2 * h["layers"] * h["layer"]
    # a decoded token at position 99 attends to 100 keys and needs logits
    assert flops.token_flops(m, 99, True) == body + h["attn_per_key"] * 100 + 2 * h["head"]
    assert flops.token_flops(m, 99, False) == body + h["attn_per_key"] * 100
    # a 32-token chunk at offset 0 ending the prompt: keys 1 + 2 + ... + 32 = 528
    assert flops.span_flops(m, 0, 32, True) == 32 * body + h["attn_per_key"] * 528 + 2 * h["head"]
    # a later chunk: positions 32..63 attend to 33..64 keys (1552 in all)
    assert flops.span_flops(m, 32, 64, False) == 32 * body + h["attn_per_key"] * 1552
    assert flops.span_flops(m, 5, 5, True) == 0.0


def test_smollm_token_by_hand():
    # 2 * 314,572,800 + 4*32*15*64*100 + 2 * 47,185,920
    assert flops.token_flops(model("smollm-360m"), 99, True) == 735_805_440


def test_paged_decode_bytes_and_flops():
    g = model("granite-8b-18l")
    # position 100: 101 keys on ceil(101/16) = 7 pages of 16; K and V of 8
    # heads of 128 in bf16, plus q and out of 32 heads of 128 in bf16
    per_layer = 7 * 16 * 8 * 128 * 2 * 2 + 2 * 32 * 128 * 2
    assert per_layer == 475_136
    assert flops.paged_decode_bytes(g, 100, 16, 2, 2) == 18 * per_layer
    assert flops.paged_decode_flops(g, 100) == 4 * 18 * 32 * 128 * 101
    s = model("smollm-360m")
    # position 15 fills exactly one page
    assert flops.paged_decode_bytes(s, 15, 16, 2, 2) == 32 * (16 * 5 * 64 * 2 * 2 + 2 * 15 * 64 * 2)
