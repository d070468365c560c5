"""The metric readers under ``metrics/`` on observations built by hand: each
gives the hand-computed value, and each returns None where it finds nothing
to read."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import flops  # noqa: E402
import spec  # noqa: E402

PARTS = spec.Parts(BENCH)
NAMES = [m["name"] for m in json.loads((BENCH.parent / "BENCHMARK.json").read_text())["end_to_end"]
         + json.loads((BENCH.parent / "BENCHMARK.json").read_text())["per_layer"]
         if m["name"] != "setup_s"]
MODEL = PARTS.config("smollm-360m")["model"]
PEAKS = PARTS.peaks("TPU v5 lite")


def obs():
    """21 finished requests due at 100 + i, first token 0.1 * i later, gaps
    between tokens 0.01 * (i + 1), finished i / 2 after being due; the router
    started each 0.001 * i after its arrival. Two tiers stepped in the traced
    window (records: tier, t0, t1, prefill spans, decoded positions)."""
    reqs = []
    for i in range(21):
        due = 100.0 + i
        first = due + 0.1 * i
        reqs.append({"rid": i, "due": due, "done": True, "tier": "elastic",
                     "arrival_t": due, "start_t": due + 0.001 * i, "finish_t": due + i / 2,
                     "token_times": [first, first + 0.01 * (i + 1), first + 0.02 * (i + 1)]})
    reqs.append({"rid": 99, "due": 120.5, "done": False, "tier": None, "arrival_t": 120.5,
                 "start_t": None, "finish_t": None, "token_times": []})
    steps = [
        ("elastic", 110.000, 110.010, [(0, 32, False)], [40, 41, 42]),
        ("elastic", 110.010, 110.030, [], [41, 42, 43, 50]),
        ("elastic", 110.030, 110.036, [(32, 40, True)], []),
        ("batch", 110.005, 110.025, [], [7]),
    ]
    trace = {"window_s": 2.0, "busy_s": 1.5, "ops": {"paged_attention_grouped": 1e-5},
             "modules": {"jit_decode_all": (0.03, 3)}}
    return {"model": MODEL, "peaks": PEAKS, "page": 16, "requests": reqs,
            "stamps": [r["token_times"] for r in reqs], "t0": 100.0, "close": 120.0,
            "t_end": 150.0, "steps": steps, "trace": trace}


def read(name, o):
    return PARTS.metric(name).read(o)


def test_latency_tails():
    o = obs()
    # np.percentile's linear rule: the 95th of 21 values is the 20th smallest
    assert read("first_token_p95_ms", o) == pytest.approx(1e3 * 0.1 * 19)
    assert read("resp_p95_ms", o) == pytest.approx(1e3 * 19 / 2)
    assert read("queue_wait_p95_ms", o) == pytest.approx(1e3 * 0.001 * 19)
    # 42 gaps, two of 0.01 * (i + 1) for each i: the 95th is at rank 38.95
    gaps = sorted(0.01 * (i + 1) for i in range(21) for _ in range(2))
    assert read("itl_p95_ms", o) == pytest.approx(1e3 * (gaps[38] + 0.95 * (gaps[39] - gaps[38])))


def test_window_rate():
    o = obs()
    # requests 0..17 have all three stamps before 120; request 18 two
    # (119.8, 119.99, then 120.18); 19 and 20 none
    assert read("out_tok_s", o) == pytest.approx((3 * 18 + 2) / 20.0)


def test_step_readers():
    o = obs()
    # the elastic tier decoded 7 tokens, the batch tier 1: elastic is read
    assert read("decode_batch_mean", o) == pytest.approx(7 / 2)
    assert read("step_ms", o) == pytest.approx(1e3 * 0.036 / 3)
    assert read("decode_dev_ms", o) == pytest.approx(10.0)
    assert read("idle_share", o) == pytest.approx(25.0)


def test_step_mfu():
    o = obs()
    work = (flops.span_flops(MODEL, 0, 32, False) + flops.span_flops(MODEL, 32, 40, True)
            + sum(flops.token_flops(MODEL, p, True) for p in [40, 41, 42, 41, 42, 43, 50, 7]))
    # union of the step intervals: 110.000-110.036
    assert read("step_mfu", o) == pytest.approx(100 * work / (0.036 * 197e12))


def test_paged_attn_roofline():
    o = obs()
    pos = [40, 41, 42, 41, 42, 43, 50, 7]
    nbytes = sum(flops.paged_decode_bytes(MODEL, p, 16, 2, 2) for p in pos)
    # bytes bound: the attention of 8 decode tokens is far below the FLOP bound
    assert nbytes / 819e9 > sum(flops.paged_decode_flops(MODEL, p) for p in pos) / 197e12
    assert read("paged_attn_roofline", o) == pytest.approx(100 * nbytes / 819e9 / 1e-5)


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read_gives_nothing(name):
    empty = {"model": MODEL, "peaks": PEAKS, "page": 16, "requests": [], "stamps": [],
             "t0": 0.0, "close": 1.0, "t_end": 1.0, "steps": None, "trace": None}
    assert read(name, empty) is None
