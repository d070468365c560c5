"""N-gram speculative decoding (PR 8 tentpole): greedy-token parity.

Speculation must be a pure throughput change — the accepted stream IS the
greedy stream, byte for byte, on every covered architecture combination:
dense + paged engines, chunked + unchunked prefill, prefix cache warm and
cold, and across preemption-mid-speculation restarts. A small vocabulary
makes the smoke model's greedy output repetitive (it settles into short
cycles), so the prompt-lookup proposer genuinely fires and every parity
test also asserts ``spec_accepted > 0`` — a proposer that never proposes
would pass parity vacuously.

The page-accounting side (verify-window reservation, rejected-tail trim)
is covered property-style by the ``speculate`` op in
tests/test_paging.py's allocator interleaving harness; here the engines'
end-to-end page hygiene is asserted instead (invariants + fully drained
pool after every run).
"""
import dataclasses

import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.serving.engine import (
    EngineConfig,
    InferenceEngine,
    PagedEngineConfig,
    PagedInferenceEngine,
)
from repro.serving.scheduler import EngineLoop

# Small vocab => repetitive greedy output => the n-gram proposer fires.
VOCAB = 24
NEW = 48
MAXLEN = 128
PS = 8

# Prompts with repeated n-grams (the proposer also matches inside prompts)
PROMPTS = [
    [1, 2, 3, 4, 5, 1, 2, 3, 4, 5],
    [7, 8, 9, 7, 8, 9],
    [3, 1, 4, 1, 5, 9, 2, 6],
]


@pytest.fixture(scope="module")
def cfg():
    return get_config("smollm-360m", smoke=True).replace(
        attn_chunk=64, vocab_size=VOCAB
    )


def _dense(cfg, params=None, **kw):
    e = EngineConfig(max_slots=4, max_len=MAXLEN, max_new_tokens=NEW, **kw)
    return InferenceEngine(cfg, e, params=params)


def _paged(cfg, params=None, num_pages=1 + 4 * MAXLEN // PS, **kw):
    e = PagedEngineConfig(page_size=PS, num_pages=num_pages, max_slots=4,
                          max_seq_len=MAXLEN, max_new_tokens=NEW, **kw)
    return PagedInferenceEngine(cfg, e, params=params)


def _outs(seqs):
    return [list(s.out) for s in seqs]


# ---------------------------------------------------------------------------
# Parity: speculation must not change a single token
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["dense", "paged"])
@pytest.mark.parametrize("chunk", [0, 32])
def test_spec_matches_plain_greedy(cfg, kind, chunk):
    """Spec-on and spec-off engines sharing params emit identical tokens,
    with and without chunked prefill, and the speculated run genuinely
    accepts (non-vacuous parity)."""
    make = _dense if kind == "dense" else _paged
    off = make(cfg, chunk_tokens=chunk)
    base = _outs(off.generate(PROMPTS))
    on = make(cfg, params=off.params, chunk_tokens=chunk, spec_tokens=4)
    assert _outs(on.generate(PROMPTS)) == base
    assert on.spec_proposed > 0 and on.spec_accepted > 0
    assert on.tokens_emitted == sum(len(o) for o in base)
    if kind == "paged":
        on.allocator.check_invariants()
        assert on.allocator.used_pages == 0


def test_spec_matches_plain_greedy_prefix_cache_warm_and_cold(cfg):
    """Speculation composes with the prefix cache: the cold pass and the
    warm pass (same prompts resubmitted — prefill skipped from the radix
    tree) both reproduce the spec-off stream, and release-to-cache inserts
    post-rollback tables (invariants hold with pages retained warm)."""
    off = _paged(cfg, prefix_cache=True)
    cold_base = _outs(off.generate(PROMPTS))
    warm_base = _outs(off.generate(PROMPTS))

    on = _paged(cfg, params=off.params, prefix_cache=True, spec_tokens=4)
    assert _outs(on.generate(PROMPTS)) == cold_base
    cold_accepted = on.spec_accepted
    assert cold_accepted > 0
    warm = on.generate(PROMPTS)
    assert _outs(warm) == warm_base
    assert any(s.cached_tokens > 0 for s in warm), "warm pass never hit the cache"
    assert on.spec_accepted > cold_accepted
    on.allocator.check_invariants()
    on.prefix_cache.check_invariants()
    assert on.allocator.used_pages == on.prefix_cache.cached_pages


def test_preemption_mid_speculation_restart_parity(cfg):
    """A sequence preempted while speculating resumes via recompute and —
    because the proposer is deterministic in the context alone — re-emits
    the exact unpreempted continuation. Ample vs tight pools, spec on
    both; the tight pool must actually preempt."""
    prompts = [p[:6] for p in PROMPTS] + [[2, 4, 2, 4, 2, 4]]
    ample = PagedInferenceEngine(
        cfg,
        PagedEngineConfig(page_size=4, num_pages=81, max_slots=4,
                          max_seq_len=64, max_new_tokens=24, spec_tokens=4),
    )
    a = ample.generate(prompts)
    assert ample.preemptions == 0 and ample.spec_accepted > 0
    # 19 usable pages against 4 x 8 at full length: sequences that finish
    # early on long accepted runs free theirs, so the pool must be well
    # short of the sum for the growth of the rest to preempt
    tight = PagedInferenceEngine(
        cfg,
        PagedEngineConfig(page_size=4, num_pages=20, max_slots=4,
                          max_seq_len=64, max_new_tokens=24, spec_tokens=4),
        params=ample.params,
    )
    t = tight.generate(prompts)
    assert tight.preemptions > 0, "tight pool never preempted"
    assert _outs(a) == _outs(t)
    tight.allocator.check_invariants()
    assert tight.allocator.used_pages == 0


def test_spec_through_engine_loop_records_throughput(cfg):
    """The shared step loop is spec-transparent (same tokens as the
    serialized generate) and records the new throughput metrics: the
    tokens-per-step gauge reads >0 and the accepted-run histogram holds
    one observation per verify pass."""
    off = _paged(cfg)
    base = _outs(off.generate(PROMPTS))
    eng = _paged(cfg, params=off.params, spec_tokens=4)
    loop = EngineLoop(eng)                        # manual stepping
    sids = [loop.submit(p) for p in PROMPTS]
    done = {}
    for _ in range(400):
        for s in loop.step_once():
            done[s.sid] = s
        if len(done) == len(sids):
            break
    assert [list(done[sid].out) for sid in sids] == base
    labels = {"engine": loop.name}
    assert loop.registry.gauge("engine_tokens_per_step", labels).value > 0
    hist = loop.registry.histogram("spec_accepted_run", labels)
    assert hist.total > 0, "no verify pass was observed"
    assert hist.sum == float(eng.spec_accepted)   # one observation per verify


# ---------------------------------------------------------------------------
# Config gate + accounting
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["jamba-1.5-large-398b", "xlstm-350m"])
def test_spec_rejects_recurrent_architectures(arch):
    """Verify replays positions statelessly; recurrent mixers carry state a
    rolled-back verify cannot restore — spec_tokens must refuse them."""
    cfg = get_config(arch, smoke=True).replace(attn_chunk=64)
    with pytest.raises(ValueError, match="attention-only"):
        InferenceEngine(cfg, EngineConfig(max_slots=2, max_len=64, spec_tokens=2))


def test_spec_parity_on_moe_arch():
    """Speculation covers every attention-only decoder, MoE blocks
    included (ample expert capacity => exact greedy, the test_engine
    trick) — parity must hold beyond the dense llama family."""
    moe_cfg = get_config("dbrx-132b", smoke=True).replace(
        attn_chunk=64, vocab_size=VOCAB
    )
    moe_cfg = moe_cfg.replace(
        moe=dataclasses.replace(moe_cfg.moe, capacity_factor=8.0)
    )
    off = _paged(moe_cfg)
    base = _outs(off.generate(PROMPTS))
    on = _paged(moe_cfg, params=off.params, spec_tokens=4)
    assert _outs(on.generate(PROMPTS)) == base
    assert on.spec_accepted > 0
    on.allocator.check_invariants()
    assert on.allocator.used_pages == 0


def test_spec_capacity_snapshot_and_acceptance_helper(cfg):
    """capacity_now exports the speculation counters and the telemetry
    helper derives the acceptance rate from them (None before any
    proposal — no fake 0.0 during warm-up)."""
    from repro.core.telemetry import spec_acceptance

    eng = _paged(cfg, spec_tokens=4)
    snap = eng.capacity_now()
    assert snap["spec_tokens"] == 4
    assert snap["spec_proposed"] == snap["spec_accepted"] == 0
    assert spec_acceptance(snap) is None
    eng.generate(PROMPTS)
    snap = eng.capacity_now()
    rate = spec_acceptance(snap)
    assert rate is not None and 0.0 < rate <= 1.0
    assert snap["tokens_emitted"] == eng.tokens_emitted > 0
