"""Cross-request prefix caching: multi-turn chat TTFT, token parity, and
the cache-off overhead bound.

PR 7 added an engine-level prefix cache (ROADMAP "Open items"): a radix
tree over the paged KV pool retires finished sequences' full pages and
re-attaches them to later prompts sharing the prefix, so prefill runs only
on the unmatched tail. The workload that motivates it is multi-turn chat:
every turn re-submits the whole conversation so far plus a short new user
message, so without the cache prefill cost grows linearly with history —
exactly the TTFT the StraightLine placer tries to protect on interactive
tiers.

Scenario: one conversation, ``TURNS`` turns. Turn k's prompt is the full
history (system prompt + every prior turn's prompt tail + generated reply)
plus a fresh user message; the engine generates a fixed-length reply that
is appended to the history. The cold engine (``prefix_cache=False``)
prefills the whole prompt every turn; the warm engine matches the history
in the tree and prefills only the new tail. TTFT is measured per turn as
``seq.token_times[0] - seq.submit_t`` driving ``step()`` directly; the
gate compares the median over turns >= 2 (turn 1 is a miss for both).
Outputs must be byte-identical — the cache must never change what the
model computes, only skip recomputing it.

The overhead leg re-runs a unique-prompt workload (zero hits possible) on
both engines: cache-on pays hashing + tree insert on every release, and
that must stay within 5% of cache-off throughput.

    PYTHONPATH=src:. python benchmarks/prefix_cache.py [--fast]

``--fast`` (CI smoke) shrinks the conversation and asserts the same
bounds — warm TTFT must improve >= 3x and the no-hit overhead must stay
<= 5% — so the cache cannot silently regress to full prefill or tax
workloads that never hit it.
"""
from __future__ import annotations

import argparse
import gc
import statistics
import time

from benchmarks.common import emit

IMPROVE = 3.0        # acceptance bar: median warm TTFT improves >= 3x
OVERHEAD = 0.95      # acceptance bar: no-hit cache-on throughput >= 0.95x off
REPS = 3             # min-of-median across reps: the cache's prefill skip is
                     # STRUCTURAL and recurs every rep; GC / scheduler spikes
                     # do not and must not decide the medians


def build(cfg, params, maxlen, ps, new_tok, chunk, cache):
    from repro.serving.engine import PagedEngineConfig, PagedInferenceEngine

    return PagedInferenceEngine(
        cfg,
        PagedEngineConfig(page_size=ps, num_pages=1 + 4 * maxlen // ps, max_slots=2,
                          max_seq_len=maxlen, max_new_tokens=new_tok,
                          chunk_tokens=chunk, prefix_cache=cache),
        params=params,
    )


def run_turn(eng, prompt):
    """Submit one turn and step it to completion; returns (ttft_s, out)."""
    sid = eng.submit(prompt)
    for _ in range(10000):
        for seq in eng.step():
            if seq.sid == sid:
                return seq.token_times[0] - seq.submit_t, list(seq.out)
    raise AssertionError("turn did not finish")


def conversation(eng, sys_prompt, user_msgs):
    """Play the multi-turn chat; returns (per-turn TTFTs, per-turn outputs)."""
    history = list(sys_prompt)
    ttfts, outs = [], []
    gc.collect()
    gc.disable()
    try:
        for msg in user_msgs:
            prompt = history + list(msg)
            ttft, out = run_turn(eng, prompt)
            ttfts.append(ttft)
            outs.append(out)
            history = prompt + out
    finally:
        gc.enable()
    return ttfts, outs


def chat_leg(engines, sys_prompt, user_msgs, new_tok):
    """Cold vs warm multi-turn chat; returns the median-TTFT improvement."""
    med = {}
    all_outs = {}
    for label, eng in engines.items():
        meds = []
        for _ in range(REPS):
            if eng.prefix_cache is not None:
                eng.prefix_cache.drop()       # every rep starts from a cold tree
            ttfts, outs = conversation(eng, sys_prompt, user_msgs)
            meds.append(statistics.median(ttfts[1:]))  # turn 1 misses on both
            all_outs[label] = outs
        med[label] = min(meds)
        emit(f"prefix_cache.chat.{label}", med[label] * 1e3,
             f"median_ttft_ms_turns2plus;turns={len(user_msgs)};reps={REPS}")
    assert all_outs["warm"] == all_outs["cold"], (
        "prefix cache changed greedy outputs vs full prefill"
    )
    for out in all_outs["warm"]:
        assert len(out) == new_tok, f"turn stopped short ({len(out)} tokens)"
    pc = engines["warm"].prefix_cache
    improve = med["cold"] / max(med["warm"], 1e-9)
    emit("prefix_cache.chat.improvement", 0.0,
         f"x{improve:.1f}_median_ttft;hit_rate={pc.hit_rate:.2f};"
         f"matched_tokens={pc.matched_tokens_total};identical_outputs=True")
    print(
        f"chat: median TTFT {med['cold']*1e3:.1f}ms -> {med['warm']*1e3:.1f}ms "
        f"({improve:.1f}x) over {len(user_msgs)} turns, hit rate {pc.hit_rate:.2f}, "
        f"identical greedy outputs"
    )
    assert pc.hit_rate > 0.0, "warm engine never hit the cache"
    return improve


def overhead_leg(engines, prompts):
    """Unique prompts (no hits possible): cache-on must stay within the
    overhead bound of cache-off wall time."""
    wall = {}
    for label, eng in engines.items():
        per_prompt = [[] for _ in prompts]    # per-prompt times across reps
        for _ in range(REPS):
            if eng.prefix_cache is not None:
                eng.prefix_cache.drop()       # reps must not hit earlier reps
            gc.collect()
            gc.disable()
            try:
                for i, p in enumerate(prompts):
                    t0 = time.perf_counter()
                    run_turn(eng, p)
                    per_prompt[i].append(time.perf_counter() - t0)
            finally:
                gc.enable()
        # sum of per-prompt minima: a one-off scheduler spike on one prompt
        # in one rep cannot decide the ratio, the structural cost recurs
        wall[label] = sum(min(ts) for ts in per_prompt)
        emit(f"prefix_cache.overhead.{label}", wall[label] * 1e3,
             f"unique_prompt_wall_ms;n={len(prompts)};reps={REPS}")
    ratio = wall["cold"] / max(wall["warm"], 1e-9)   # throughput on / off
    emit("prefix_cache.overhead.ratio", 0.0, f"throughput_on_over_off=x{ratio:.3f}")
    print(
        f"overhead: unique-prompt wall {wall['cold']*1e3:.1f}ms off -> "
        f"{wall['warm']*1e3:.1f}ms on ({ratio:.3f}x throughput)"
    )
    return ratio


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke: smaller conversation, same >=3x / <=5% bounds")
    args = ap.parse_args()

    import numpy as np

    from repro.configs.registry import get_config

    turns = 4 if args.fast else 6
    sys_len = 160 if args.fast else 384
    maxlen = 384 if args.fast else 1024
    ps, chunk, new_tok, user_len = 16, 32, 8, 12
    cfg = get_config("smollm-360m", smoke=True).replace(attn_chunk=64)
    rng = np.random.default_rng(0)
    sys_prompt = list(rng.integers(1, cfg.vocab_size, sys_len))
    user_msgs = [list(rng.integers(1, cfg.vocab_size, user_len)) for _ in range(turns)]
    unique = [list(rng.integers(1, cfg.vocab_size, 96)) for _ in range(6)]

    params = None
    engines = {}
    for label, cache in (("cold", False), ("warm", True)):
        engines[label] = build(cfg, params, maxlen, ps, new_tok, chunk, cache)
        params = engines[label].params
        engines[label].prewarm()
        # compile the decode + chunk + (warm) cache-attach path before timing
        engines[label].generate([sys_prompt[:40]])

    improve = chat_leg(engines, sys_prompt, user_msgs, new_tok)
    ratio = overhead_leg(engines, unique)

    assert improve >= IMPROVE, (
        f"prefix cache must improve median multi-turn TTFT >= {IMPROVE}x, "
        f"got {improve:.2f}x"
    )
    assert ratio >= OVERHEAD, (
        f"cache-on must keep >= {OVERHEAD}x cache-off throughput on unique "
        f"prompts, got {ratio:.3f}x"
    )
    print(
        f"OK — multi-turn prompts skip cached prefill: median TTFT improved >= "
        f"{IMPROVE}x, outputs identical, no-hit overhead within "
        f"{(1 - OVERHEAD) * 100:.0f}%"
    )


if __name__ == "__main__":
    from repro.jax_cache import enable_compile_cache

    enable_compile_cache()
    main()
