"""Continuous-batching step loop (serving/scheduler.py EngineLoop).

Covers the invariants the shared step loop must not break: token parity
with the serialized ``generate`` baseline (batching must not change greedy
outputs), conservation + exactly-once through the router's two-phase
``submit_fn``/``wait_fn`` execution path under submitter threads x engines
(mirroring tests/test_router_concurrency.py), fairness (no admitted
sequence starves while later arrivals finish), and a deterministic
admit-during-step interleaving test (a sequence submitted while a batched
step is in flight is admitted at the next step and still decodes exactly)."""
import threading
import time

import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core import Request, StraightLinePolicy, Thresholds, Tier
from repro.core.router import Backend, StraightLineRouter
from repro.serving.engine import (
    EngineConfig,
    InferenceEngine,
    PagedEngineConfig,
    PagedInferenceEngine,
)
from repro.serving.scheduler import EngineLoop

PROMPT, NEW, MAXLEN, PS = 5, 4, 64, 16


@pytest.fixture(scope="module")
def cfg():
    return get_config("smollm-360m", smoke=True).replace(attn_chunk=64)


def _paged(cfg, slots=2, pools=2, new=NEW):
    return PagedInferenceEngine(
        cfg,
        PagedEngineConfig(page_size=PS, num_pages=1 + pools * MAXLEN // PS,
                          max_slots=slots, max_seq_len=MAXLEN, max_new_tokens=new),
    )


def _prompts(cfg, n, base=0):
    return [
        list(np.random.default_rng(base + i).integers(1, cfg.vocab_size, PROMPT))
        for i in range(n)
    ]


# ---------------------------------------------------------------------------
# Parity: the step loop batches, it must not change tokens
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_loop_matches_serialized_generate(cfg, kind):
    """Concurrent submitters through one EngineLoop produce exactly the
    tokens the serialized lock-holding generate produces."""
    if kind == "dense":
        eng = InferenceEngine(cfg, EngineConfig(max_slots=2, max_len=MAXLEN, max_new_tokens=NEW))
    else:
        eng = _paged(cfg)
    prompts = _prompts(cfg, 5)
    base = [s.out for s in eng.generate(prompts)]
    outs = [None] * len(prompts)
    with EngineLoop(eng) as loop:
        def worker(i):
            outs[i] = loop.wait(loop.submit(prompts[i]), 120).out

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert outs == base
    assert all(s is None for s in eng.slot_seq)
    if kind == "paged":
        eng.allocator.check_invariants()
        assert eng.allocator.free_pages == eng.pcfg.num_pages - 1


def test_loop_generate_is_drop_in(cfg):
    eng = _paged(cfg)
    prompts = _prompts(cfg, 3)
    base = [s.out for s in eng.generate(prompts)]
    with EngineLoop(eng) as loop:
        got = [s.out for s in loop.generate(prompts, timeout=120)]
    assert got == base


def test_loop_capacity_exports_occupancy(cfg):
    """The loop's capacity_now() adds the occupancy/queue gauges telemetry
    consumes (active_slots, batch_occupancy, queue_depth, loop_steps)."""
    from repro.core.telemetry import batch_occupancy, queue_depth

    eng = _paged(cfg)
    loop = EngineLoop(eng)                         # not started: deterministic
    snap = loop.capacity_now()
    assert snap["active_slots"] == 0 and snap["batch_occupancy"] == 0.0
    assert batch_occupancy(snap) == 0.0 and queue_depth(snap) == 0
    sids = [loop.submit(p) for p in _prompts(cfg, 3)]
    assert loop.capacity_now()["queue_depth"] == 3
    loop.step_once()                               # admits 2 (slots), decodes
    snap = loop.capacity_now()
    assert snap["active_slots"] == 2 and snap["batch_occupancy"] == 1.0
    assert snap["queue_depth"] == 1 and snap["loop_steps"] == 1
    for _ in range(40):
        loop.step_once()
        if all(loop.engine.slot_seq[i] is None for i in range(2)) and not loop.engine.waiting:
            break
    for sid in sids:
        assert len(loop.wait(sid, 0).out) == NEW


# ---------------------------------------------------------------------------
# Fairness: FIFO admission, every active slot advances every step
# ---------------------------------------------------------------------------


def test_fairness_no_admitted_sequence_starves(cfg):
    """Under continuous submission pressure, sequences finish in submission
    order (equal lengths) and each finishes within a bounded number of steps
    of its admission — later arrivals can never starve an earlier one."""
    eng = _paged(cfg, slots=2)
    loop = EngineLoop(eng)                         # stepped manually
    prompts = _prompts(cfg, 8)
    finish_step = {}
    sids = [loop.submit(prompts[0]), loop.submit(prompts[1])]
    next_i = 2
    for step in range(100):
        # keep the queue pressurized: one new arrival per step
        if next_i < len(prompts):
            sids.append(loop.submit(prompts[next_i]))
            next_i += 1
        for seq in loop.step_once():
            finish_step[seq.sid] = step
        if len(finish_step) == len(prompts):
            break
    assert len(finish_step) == len(prompts), "a sequence never finished (starved)"
    order = [sid for sid, _ in sorted(finish_step.items(), key=lambda kv: (kv[1], kv[0]))]
    assert order == sids, "equal-length sequences must finish in submission order"
    # bounded latency: with 2 slots and NEW tokens each, a sequence waits at
    # most ceil(queue_ahead / slots) generations before admission
    waves = -(-len(prompts) // 2)
    assert max(finish_step.values()) <= waves * (NEW + 2), "tail latency unbounded"


# ---------------------------------------------------------------------------
# Deterministic admit-during-step interleaving
# ---------------------------------------------------------------------------


def test_admit_during_step_interleaves_next_step(cfg):
    """A sequence submitted while a batched step is IN FLIGHT is admitted at
    the next step, joins the live decode batch, and still produces exactly
    its serialized tokens. The step entry blocks on a test-controlled event
    (before the engine lock), so the interleaving is deterministic."""
    eng = _paged(cfg, slots=2, new=8)
    prompts = _prompts(cfg, 2, base=40)
    expect = [s.out for s in eng.generate(prompts)]

    orig_step = eng.step
    entered, release = threading.Event(), threading.Event()

    def gated_step():
        entered.set()
        assert release.wait(30)
        return orig_step()

    eng.step = gated_step
    eng.peak_active = 0
    loop = EngineLoop(eng).start()
    try:
        sid0 = loop.submit(prompts[0])
        assert entered.wait(10), "step loop never woke for the first submit"
        sid1 = loop.submit(prompts[1])     # lands while step 1 is in flight
        eng.step = orig_step               # only the first step is gated
        release.set()
        out0 = loop.wait(sid0, 60).out
        out1 = loop.wait(sid1, 60).out
    finally:
        release.set()
        loop.stop()
    assert [out0, out1] == expect
    assert eng.peak_active == 2, "late submit was not interleaved into the batch"


def test_timed_out_wait_abandons_future_without_leaking(cfg):
    """A wait that times out reaps its future immediately and the sequence's
    eventual result is discarded — timed-out requests must not grow the
    loop's registry without bound (long-running service leak regression)."""
    eng = _paged(cfg, slots=1)
    loop = EngineLoop(eng)                         # manual stepping
    sid = loop.submit(_prompts(cfg, 1)[0])
    with pytest.raises(TimeoutError):
        loop.wait(sid, 0.0)                        # nothing stepped yet
    assert sid not in loop._futures and sid in loop._abandoned
    with pytest.raises(KeyError):
        loop.wait(sid, 0.0)                        # abandoned == unknown
    for _ in range(30):
        loop.step_once()
        if all(s is None for s in eng.slot_seq) and not eng.waiting:
            break
    assert not loop._futures and not loop._unclaimed and not loop._abandoned
    eng.allocator.check_invariants()


def test_stop_unblocks_waiters_and_poisoned_loop_rejects(cfg):
    eng = _paged(cfg, slots=1, new=8)

    def boom():
        raise RuntimeError("device on fire")

    prompts = _prompts(cfg, 1)
    loop = EngineLoop(eng).start()
    sid = loop.submit(prompts[0])
    eng.step = boom
    with pytest.raises(RuntimeError, match="engine loop failed"):
        loop.wait(sid, 10)
    with pytest.raises(RuntimeError, match="engine loop failed"):
        loop.submit(prompts[0])
    loop.stop()


# ---------------------------------------------------------------------------
# Router soak through the two-phase submit_fn/wait_fn path
# ---------------------------------------------------------------------------


def test_soak_router_step_loop_conservation_exactly_once(cfg):
    """Submitter threads x engine loops through the router's two-phase
    execution path, hedging enabled: conservation and exactly-once metrics
    hold, every result carries the exact serialized-engine tokens, and the
    engines drain completely."""
    engines = {
        Tier.FLASK: _paged(cfg, slots=1),
        Tier.DOCKER: _paged(cfg, slots=2),
        Tier.SERVERLESS: _paged(cfg, slots=2),
    }
    for eng in engines.values():
        eng.prewarm()
    loops = {t: EngineLoop(e).start() for t, e in engines.items()}

    def prompt_for(rid):
        return list(np.random.default_rng(rid).integers(1, cfg.vocab_size, PROMPT))

    def backend(tier, loop, capacity, eng):
        return Backend(
            tier,
            run=lambda req: loop.wait(loop.submit(prompt_for(req.rid)), 120).out,
            capacity=capacity, queue_cap=64,
            capacity_fn=lambda: eng.admission_capacity(PROMPT + NEW),
            stats_fn=loop.capacity_now,
            submit_fn=lambda req: loop.submit(prompt_for(req.rid)),
            wait_fn=lambda sid, timeout: loop.wait(sid, timeout).out,
        )

    router = StraightLineRouter(
        {
            Tier.FLASK: backend(Tier.FLASK, loops[Tier.FLASK], 1, engines[Tier.FLASK]),
            Tier.DOCKER: backend(Tier.DOCKER, loops[Tier.DOCKER], 2, engines[Tier.DOCKER]),
            Tier.SERVERLESS: backend(
                Tier.SERVERLESS, loops[Tier.SERVERLESS], 2, engines[Tier.SERVERLESS]
            ),
        },
        policy=StraightLinePolicy(Thresholds(F=1e9, D=1e6)),
        hedge_after_s=0.05,
        results_cap=256,
    )
    router.start(2)
    submitted, sub_lock = [], threading.Lock()

    def submitter(base):
        for i in range(6):
            rid = base + i
            router.submit(Request(rid=rid, arrival_t=0.0, data_size=100.0, timeout_s=120.0))
            with sub_lock:
                submitted.append(rid)

    threads = [threading.Thread(target=submitter, args=(k * 100,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    router.drain(timeout=120)
    router.stop()
    for loop in loops.values():
        loop.stop()

    m = router.metrics
    recorded = [r.rid for r in m.completed + m.failed]
    assert m.total == len(submitted)
    assert len(recorded) == len(set(recorded)), "a request recorded metrics twice"
    assert set(recorded) == set(submitted), "lost or invented rids"
    assert not m.failed, [r.fail_reason for r in m.failed]
    # spot-check real tokens: exactly what a lone serialized engine produces
    probe = _paged(cfg, slots=1)
    expect = probe.generate([prompt_for(submitted[0])])[0].out
    assert router.result(submitted[0], timeout=5) == expect
    for rid in submitted[1:]:
        assert len(router.result(rid, timeout=5)) == NEW
    for eng in engines.values():
        assert all(s is None for s in eng.slot_seq)
        eng.allocator.check_invariants()
        assert eng.allocator.free_pages == eng.pcfg.num_pages - 1


# ---------------------------------------------------------------------------
# Lifecycle registries (PR 8 satellites): stop() hygiene + snapshot clamping
# ---------------------------------------------------------------------------


def test_stop_clears_unclaimed_and_abandoned_registries(cfg):
    """Regression: stop() failed pending futures but left ``_unclaimed``
    results and ``_abandoned`` sids behind, so a stopped-then-restarted
    loop carried orphaned registry state forever. Both must be cleared —
    nothing will ever claim them once their waiters are gone."""
    eng = _paged(cfg, slots=1, new=2)
    loop = EngineLoop(eng)                         # manual stepping
    direct = eng.submit(_prompts(cfg, 1)[0])       # no future: loop can't hand it off
    for _ in range(30):
        loop.step_once()
        if all(s is None for s in eng.slot_seq) and not eng.waiting:
            break
    assert direct in loop._unclaimed
    sid = loop.submit(_prompts(cfg, 1, base=7)[0])
    with pytest.raises(TimeoutError):
        loop.wait(sid, 0.0)                        # abandons: never stepped again
    assert sid in loop._abandoned
    loop.stop()
    assert not loop._unclaimed and not loop._abandoned and not loop._futures
    # the abandoned sequence is still in the engine: holding the engine lock
    # keeps the restarted thread from finishing it (and parking it in
    # ``_unclaimed``) before the registry is read
    with eng.lock:
        loop.start()                               # restart begins with a clean registry
        assert not loop._unclaimed and not loop._abandoned
    loop.stop()


def test_capacity_now_clamps_sparse_engine_snapshots(cfg):
    """Regression: ``capacity_now`` read ``num_slots`` with a different
    default at each use, so a sparse snapshot (an engine exporting
    ``free_slots`` but not ``num_slots``, or neither) produced a negative
    active-slot count. One default, clamped once: occupancy is always in
    [0, 1] and ``active_slots`` never negative."""

    class _Stub:
        def __init__(self, snap):
            self._snap = snap

        def capacity_now(self):
            return dict(self._snap)

    for snap in ({}, {"free_slots": 5}, {"num_slots": 4},
                 {"num_slots": 2, "free_slots": 9},
                 {"free_slots": 0, "prefilling_slots": 3}):
        out = EngineLoop(_Stub(snap)).capacity_now()
        assert out["active_slots"] >= 0, snap
        assert 0.0 <= out["batch_occupancy"] <= 1.0, snap
    full = EngineLoop(_Stub({"num_slots": 4, "free_slots": 1,
                             "prefilling_slots": 1})).capacity_now()
    assert full["active_slots"] == 2 and full["batch_occupancy"] == 0.5
