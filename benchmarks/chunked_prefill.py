"""Chunked prefill vs whole-prompt prefill: inter-token decode latency
while a long prompt lands on a busy engine.

The PR 4 step loop had a head-of-line blocking bug (ROADMAP "Open items"):
admitting a sequence ran its ENTIRE prompt prefill inside ``step()``, so
every decoding slot stalled for the full prefill — a max-length prompt
arriving on an interactive tier spiked that tier's inter-token latency by
orders of magnitude, exactly the latency objective StraightLine's placer is
supposed to protect. Chunked prefill (``chunk_tokens``) absorbs the prompt
over many iterations under a per-step token budget, so decoding slots keep
emitting a token every iteration and the worst-case gap is bounded by ~one
chunk of prefill work.

Scenario (per engine kind, dense and paged): one short interactive request
is mid-decode when a max-length prompt is submitted. We drive ``step()``
directly and wall-time every step in which the interactive sequence was
decoding; the max step time IS its max inter-token gap. Both engines must
produce the exact greedy tokens of the serialized baseline (chunking must
not change outputs) with zero failures.

    PYTHONPATH=src:. python benchmarks/chunked_prefill.py [--fast]

``--fast`` (CI smoke) shrinks the workload and asserts the bound — the
chunked max gap must improve on the unchunked one by >= the same 2x bar —
so chunking cannot silently regress to whole-prompt prefill.
"""
from __future__ import annotations

import argparse
import gc
import time

from benchmarks.common import emit

IMPROVE = 2.0        # acceptance bar: max inter-token gap improves >= 2x
REPS = 3             # min-of-max across reps: a STRUCTURAL stall (the whole-
                     # prompt prefill step) recurs every rep; a one-off GC /
                     # scheduler spike does not and must not decide the gap


def build(kind, cfg, params, maxlen, ps, new_tok, chunk):
    from repro.serving.engine import (
        EngineConfig,
        InferenceEngine,
        PagedEngineConfig,
        PagedInferenceEngine,
    )

    if kind == "dense":
        return InferenceEngine(
            cfg,
            EngineConfig(max_slots=2, max_len=maxlen, max_new_tokens=new_tok,
                         bucket_unit=ps, chunk_tokens=chunk),
            params=params,
        )
    return PagedInferenceEngine(
        cfg,
        PagedEngineConfig(page_size=ps, num_pages=1 + 2 * maxlen // ps, max_slots=2,
                          max_seq_len=maxlen, max_new_tokens=new_tok, chunk_tokens=chunk),
        params=params,
    )


def interactive_gaps(eng, short, long_prompt):
    """Serve ``short`` (decoding) with ``long_prompt`` landing mid-flight;
    returns (max inter-token gap of the short sequence, outs by sid). GC is
    paused around the stepping so a collection pause cannot masquerade as a
    prefill stall."""
    done = {}
    sid_s = eng.submit(short)
    # bring the interactive sequence into steady-state decode
    for _ in range(2):
        for s in eng.step():
            done[s.sid] = s
    seq_s = next(s for s in eng.slot_seq if s is not None and s.sid == sid_s)
    sid_l = eng.submit(long_prompt)
    max_gap = 0.0
    gc.collect()
    gc.disable()
    try:
        for _ in range(10000):
            n_before = len(seq_s.out)
            t0 = time.perf_counter()
            for s in eng.step():
                done[s.sid] = s
            dt = time.perf_counter() - t0
            if len(seq_s.out) > n_before:
                max_gap = max(max_gap, dt)  # a step the interactive seq waited on
            if len(done) == 2:
                return max_gap, {sid_s: done[sid_s].out, sid_l: done[sid_l].out}
    finally:
        gc.enable()
    raise AssertionError("sequences did not finish")


def run_kind(kind, cfg, params, maxlen, ps, new_tok, chunk, short, long_prompt):
    results = {}
    outs = {}
    for label, ct in (("unchunked", 0), ("chunked", chunk)):
        eng = build(kind, cfg, params, maxlen, ps, new_tok, ct)
        params = eng.params
        eng.prewarm()
        # warm the decode + (for chunked) the carry-install path so the
        # measured gaps are steady-state, not first-call compiles
        eng.generate([short[:3]])
        gaps = []
        for _ in range(REPS):
            gap, out = interactive_gaps(eng, short, long_prompt)
            gaps.append(gap)
        results[label] = min(gaps)
        outs[label] = sorted(out.items())
        emit(f"chunked_prefill.{kind}.{label}", results[label] * 1e3,
             f"max_intertoken_gap_ms;chunk={ct};reps={REPS}")
    assert outs["chunked"] == outs["unchunked"], (
        f"{kind}: chunked greedy outputs diverge from whole-prompt prefill"
    )
    for sid, out in outs["chunked"]:
        assert len(out) == new_tok, f"{kind}: sid {sid} stopped short ({len(out)} tokens)"
    improve = results["unchunked"] / max(results["chunked"], 1e-9)
    emit(f"chunked_prefill.{kind}.improvement", 0.0,
         f"x{improve:.1f}_max_gap;identical_outputs=True")
    print(
        f"{kind}: max inter-token gap {results['unchunked']*1e3:.1f}ms -> "
        f"{results['chunked']*1e3:.1f}ms ({improve:.1f}x) with chunk={chunk}, "
        f"identical greedy outputs"
    )
    return improve, params


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke: smaller workload, same >=2x gap bound")
    args = ap.parse_args()

    import numpy as np

    from repro.configs.registry import get_config

    maxlen = 512 if args.fast else 1024
    ps, chunk, new_tok = 16, 32, 12
    cfg = get_config("smollm-360m", smoke=True).replace(attn_chunk=64)
    short = list(np.random.default_rng(0).integers(1, cfg.vocab_size, 5))
    long_prompt = list(
        np.random.default_rng(1).integers(1, cfg.vocab_size, maxlen - new_tok - 1)
    )

    params = None
    improvements = {}
    for kind in ("dense", "paged"):
        improvements[kind], params = run_kind(
            kind, cfg, params, maxlen, ps, new_tok, chunk, short, long_prompt
        )
    for kind, improve in improvements.items():
        assert improve >= IMPROVE, (
            f"{kind}: chunked prefill must improve the max inter-token decode gap "
            f">= {IMPROVE}x while a max-length prompt prefills, got {improve:.2f}x"
        )
    print(
        f"OK — long prompts are absorbed chunk-by-chunk: worst inter-token decode "
        f"gap improved >= {IMPROVE}x on both engines, outputs identical, zero failures"
    )


if __name__ == "__main__":
    from repro.jax_cache import enable_compile_cache

    enable_compile_cache()
    main()
