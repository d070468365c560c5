"""The traffic generator (``traffic.py`` with ``arrivals/`` and ``lengths/``):
deterministic per seed, the same work for every seed, lengths clipped, and
payloads on the intended side of Algorithm 1's threshold D."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import spec  # noqa: E402
import traffic  # noqa: E402

PARTS = spec.Parts(BENCH)
BIG = 2**31 + 2**20 + 7           # wider than 32 signed bits
MIXES = {"chat": "smollm-360m", "batch": "granite-8b-18l"}


def sched(mix, seed, seconds=20.0):
    return traffic.schedule(PARTS, PARTS.traffic(mix), seed, seconds, 49152)


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_same_seed_same_schedule(mix):
    a, b = sched(mix, BIG), sched(mix, BIG)
    assert [(r.due_s, r.prompt, r.payload_bytes) for r in a.requests] == \
           [(r.due_s, r.prompt, r.payload_bytes) for r in b.requests]


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_every_seed_carries_the_same_work(mix):
    """The seed draws the token ids only: arrivals and lengths are fixed."""
    a, b = sched(mix, BIG), sched(mix, 12345)
    assert [(r.due_s, len(r.prompt), r.payload_bytes) for r in a.requests] == \
           [(r.due_s, len(r.prompt), r.payload_bytes) for r in b.requests]
    assert [r.prompt for r in a.requests] != [r.prompt for r in b.requests]


def test_poisson_gaps_fill_the_span():
    p = {"rate_per_s": 2.0}
    arrivals = PARTS.arrivals("poisson")
    n = arrivals.count(p, 30.0)
    t = arrivals.times(p, n, 30.0, np.random.default_rng(5))
    assert n == 60 and np.all(np.diff(t) > 0) and 0 < t[0] and t[-1] < 30.0
    # another order of the same n + 1 gaps, which fill the span exactly
    u = arrivals.times(p, n, 30.0, np.random.default_rng(6))
    assert not np.allclose(t, u)
    gaps = lambda x: np.sort(np.diff(np.r_[0.0, x, 30.0]))
    np.testing.assert_allclose(gaps(t), gaps(u), rtol=1e-9)


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_window_and_lead_in(mix):
    t = PARTS.traffic(mix)
    s = sched(mix, BIG, 40.0)
    rate, lead = t["arrivals"]["rate_per_s"], t["lead_in_s"]
    assert len(s.window) == round(rate * 40.0)
    assert all(0 <= r.due_s < 40.0 for r in s.window)
    lead_in = [r for r in s.requests if r.due_s < 0]
    assert len(lead_in) == round(rate * lead)
    assert all(-lead <= r.due_s < 0 for r in lead_in)
    assert [r.due_s for r in s.requests] == sorted(r.due_s for r in s.requests)
    assert all(1 <= tok < 49152 for r in s.requests for tok in r.prompt)


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_lengths_clipped(mix):
    p = PARTS.traffic(mix)["prompt_tokens"]
    lens = PARTS.lengths(p["dist"]).quantiles(p, 5000)
    assert lens.min() >= p["min"] and lens.max() <= p["max"]
    if p["dist"] == "lognormal":
        assert np.median(lens) == pytest.approx(p["median"], rel=0.01)
        assert lens.min() == p["min"] and lens.max() == p["max"]      # both clips bind


def test_payload_side_of_D():
    from repro.launch.serve import build_parser

    for mix, config in MIXES.items():
        cfg = PARTS.config(config)
        D = build_parser().parse_args(cfg["launcher_args"]).D
        pay = [r.payload_bytes for r in sched(mix, BIG).requests]
        if mix == "chat":
            assert max(pay) < D          # Algorithm 1 line 3 or lines 10-18
        else:
            assert min(pay) > D          # line 6: the batch tier
        bpt = PARTS.traffic(mix)["payload_bytes_per_token"]
        assert all(p == bpt * len(r.prompt) for p, r in zip(pay, sched(mix, BIG).requests))


def test_mix_and_cell_agree():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        t = PARTS.traffic(w["traffic"])
        cfg = PARTS.config(w["config"])
        longest = t["prompt_tokens"]["max"] + t["output_tokens"]
        assert longest <= cfg["row_tokens"], w["name"]
