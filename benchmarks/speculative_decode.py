"""N-gram speculative decoding vs plain batched decode: tokens per step.

PR 8's tentpole: each decoding slot proposes up to ``spec_tokens`` draft
tokens from its OWN context (prompt-lookup — no draft model, no extra
weights) and the engine verifies the whole draft in one multi-token pass at
the decode frontier, accepting the longest run that matches the greedy
chain. A decode-bound engine is memory-bandwidth-bound, so the verify pass
amortizes one weight sweep over k+1 positions: when the workload is
repetitive (code, templated text, self-repeating generations) the engine
emits several tokens per step instead of one — with BYTE-IDENTICAL output,
because only greedy-matching tokens are ever accepted.

Workload: a small vocabulary makes the smoke model's greedy continuations
settle into short cycles (the degenerate-but-honest stand-in for natural
repetitiveness; the proposer sees only token ids either way). We run the
same prompts through a spec-off and a spec-on paged engine sharing params,
count engine steps to drain, and report tokens/step = tokens_emitted /
steps for each. The acceptance bar is the RATIO of the two.

    PYTHONPATH=src:. python benchmarks/speculative_decode.py [--fast]

``--fast`` (CI smoke) shrinks the workload and asserts the bar — spec-on
must emit >= 1.5x the tokens per step of spec-off at byte-identical
outputs, so speculation cannot silently regress to plain decode (a
never-accepting proposer fails the bar; a token-changing one fails the
parity assert).
"""
from __future__ import annotations

import argparse

from benchmarks.common import emit

IMPROVE = 1.5        # acceptance bar: tokens/step improves >= 1.5x
REPS = 3             # max-of-reps tokens/step per engine: acceptance is a
                     # property of the token streams (deterministic), reps
                     # only absorb scheduling noise in the step loop


def build(cfg, params, maxlen, ps, new_tok, spec):
    from repro.serving.engine import PagedEngineConfig, PagedInferenceEngine

    return PagedInferenceEngine(
        cfg,
        PagedEngineConfig(page_size=ps, num_pages=1 + 4 * maxlen // ps,
                          max_slots=4, max_seq_len=maxlen,
                          max_new_tokens=new_tok, spec_tokens=spec),
        params=params,
    )


def drain(eng, prompts):
    """Submit all prompts, step to drain; returns (outs, steps, tokens)."""
    t0 = eng.tokens_emitted
    done = {}
    sids = [eng.submit(p) for p in prompts]
    steps = 0
    while eng.waiting or any(s is not None for s in eng.slot_seq):
        for s in eng.step():
            done[s.sid] = s
        steps += 1
        assert steps < 100_000
    return [list(done[sid].out) for sid in sids], steps, eng.tokens_emitted - t0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke: smaller workload, same >=1.5x bar")
    args = ap.parse_args()

    from repro.configs.registry import get_config

    new_tok = 48 if args.fast else 160
    maxlen = 256 if args.fast else 512
    ps, spec = 8, 4
    cfg = get_config("smollm-360m", smoke=True).replace(attn_chunk=64, vocab_size=24)
    prompts = [
        [1, 2, 3, 4, 5, 1, 2, 3, 4, 5],
        [7, 8, 9, 7, 8, 9],
        [3, 1, 4, 1, 5, 9, 2, 6],
        [2, 4, 2, 4, 2, 4, 2, 4],
    ]

    results = {}
    outs = {}
    params = None
    for label, k in (("spec_off", 0), ("spec_on", spec)):
        eng = build(cfg, params, maxlen, ps, new_tok, k)
        params = eng.params
        eng.prewarm()
        best = 0.0
        for _ in range(REPS):
            out, steps, tokens = drain(eng, prompts)
            best = max(best, tokens / steps)
        results[label] = best
        outs[label] = out
        extra = ""
        if k:
            rate = eng.spec_accepted / max(1, eng.spec_proposed)
            extra = f";accept_rate={rate:.2f};proposed={eng.spec_proposed}"
            assert eng.spec_accepted > 0, "the proposer never had a draft accepted"
        eng.allocator.check_invariants()
        assert eng.allocator.used_pages == 0, "pages leaked after drain"
        emit(f"speculative_decode.paged.{label}", results[label],
             f"tokens_per_step;k={k};reps={REPS}{extra}")

    assert outs["spec_on"] == outs["spec_off"], (
        "speculative decoding changed the greedy token stream"
    )
    improve = results["spec_on"] / max(results["spec_off"], 1e-9)
    emit("speculative_decode.paged.improvement", 0.0,
         f"x{improve:.2f}_tokens_per_step;identical_outputs=True")
    print(
        f"paged: {results['spec_off']:.2f} -> {results['spec_on']:.2f} tokens/step "
        f"({improve:.2f}x) with k={spec}, byte-identical greedy outputs"
    )
    assert improve >= IMPROVE, (
        f"speculative decoding must emit >= {IMPROVE}x tokens per step on the "
        f"repetitive workload at identical outputs, got {improve:.2f}x"
    )
    print(
        f"OK — drafts verified in one multi-token pass: >= {IMPROVE}x tokens/step "
        f"at byte-identical outputs, pages fully reclaimed"
    )


if __name__ == "__main__":
    from repro.jax_cache import enable_compile_cache

    enable_compile_cache()
    main()
