"""Paged KV-cache subsystem: allocator invariants, kernel parity, engine v2
preemption/resume/fork, and live-capacity placement feedback."""
import dataclasses
from collections import Counter

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_fallback import given, settings, st

from repro.configs.registry import get_config
from repro.core import CapacityGauge, Request, StraightLinePolicy, Thresholds, Tier
from repro.core.router import Backend, StraightLineRouter
from repro.serving.engine import (
    EngineConfig,
    InferenceEngine,
    PagedEngineConfig,
    PagedInferenceEngine,
)
from repro.serving.paging import NULL_PAGE, BlockAllocator, OutOfPages, PageTable
from repro.serving.prefix_cache import PrefixCache


# ---------------------------------------------------------------------------
# BlockAllocator / PageTable
# ---------------------------------------------------------------------------


def test_allocator_alloc_free_invariants():
    a = BlockAllocator(num_pages=8, page_size=4)
    assert a.free_pages == 7                      # page 0 reserved
    p1 = a.alloc(3)
    assert len(set(p1)) == 3 and NULL_PAGE not in p1
    p2 = a.alloc(4)
    assert not (set(p1) & set(p2))                # never hand out a page twice
    assert a.free_pages == 0
    assert not a.can_alloc(1)
    with pytest.raises(OutOfPages):
        a.alloc(1)
    a.free(p1)
    assert a.free_pages == 3
    a.check_invariants()
    with pytest.raises(ValueError):
        a.free([p1[0]])                           # double free detected


def test_allocator_all_or_nothing():
    a = BlockAllocator(num_pages=4, page_size=4)
    with pytest.raises(OutOfPages):
        a.alloc(5)
    assert a.free_pages == 3                      # failed alloc leaks nothing
    a.check_invariants()


def test_allocator_refcounts_shared_pages():
    a = BlockAllocator(num_pages=6, page_size=4)
    pages = a.alloc(2)
    assert a.ref_count(pages[0]) == 1
    a.share(pages[0])
    assert a.ref_count(pages[0]) == 2
    a.free([pages[0]])                            # one owner drops
    assert a.ref_count(pages[0]) == 1
    assert pages[0] not in list(a._free)          # still held by the other
    a.free([pages[0], pages[1]])
    assert a.free_pages == 5
    a.check_invariants()


def test_page_table_fork_shares_full_pages_and_cows_partial():
    a = BlockAllocator(num_pages=10, page_size=4)
    t = PageTable(4, a.alloc(3), num_tokens=9)    # 2 full pages + 1 partial
    f = t.fork(a)
    assert f.pages[:2] == t.pages[:2]             # full prefix shared
    assert f.pages[2] != t.pages[2]               # partial page copied-on-write
    assert a.ref_count(t.pages[0]) == 2 and a.ref_count(t.pages[2]) == 1
    t.release(a)
    assert a.ref_count(f.pages[0]) == 1           # fork still holds the prefix
    f.release(a)
    a.check_invariants()
    assert a.used_pages == 0


def test_bucket_lengths_enumerates_exactly_the_bucket_fixed_points():
    from repro.serving.paging import bucket_lengths, bucket_tokens, num_buckets

    for unit, cap in [(16, 256), (16, 96), (4, 4), (8, 100)]:
        ls = bucket_lengths(unit, cap)
        assert len(ls) == num_buckets(unit, cap)       # one shape per compile
        assert ls == sorted(set(ls))
        # every enumerated length is a fixed point of bucket_tokens — i.e.
        # prewarm compiles exactly the shapes real traffic will request
        assert all(bucket_tokens(n, unit, cap) == n for n in ls)


def test_page_table_row_pads_with_null_page():
    t = PageTable(4, [3, 5], num_tokens=6)
    assert t.row(4) == [3, 5, NULL_PAGE, NULL_PAGE]
    with pytest.raises(ValueError):
        t.row(1)


@given(
    ops=st.lists(
        st.tuples(
            st.sampled_from(["admit", "preempt", "fork", "grow", "free",
                             "speculate"]),
            st.integers(0, 15),            # which live table the op targets
            st.integers(1, 12),            # admit ctx length / spec k+accepted
        ),
        min_size=1,
        max_size=80,
    )
)
@settings(max_examples=120, deadline=None)
def test_allocator_pagetable_invariants_under_random_interleavings(ops):
    """Drive the orders the concurrent router runtime can produce — admit,
    preempt (release + later re-admit), fork (hedged copy: prefix sharing +
    CoW), grow, free, speculate (reserve the verify window's pages up
    front, accept a shorter run, trim the rejected tail) — against
    BlockAllocator/PageTable and assert after every step that (a) the
    allocator's free/used partition is exact, and (b) every page's
    ref-count equals the number of live tables holding it. Finally
    releasing everything must return the pool to fully free."""
    PS = 4
    alloc = BlockAllocator(num_pages=13, page_size=PS)
    tables = []                                        # live sequences
    parked = []                                        # preempted, pages released

    def check():
        alloc.check_invariants()
        held = Counter(p for t in tables for p in t.pages)
        for page, n in held.items():
            assert alloc.ref_count(page) == n, (page, n, alloc.ref_count(page))
        assert alloc.used_pages == len(held)
        assert alloc.free_pages == alloc.num_pages - 1 - len(held)

    for op, idx, n_tokens in ops:
        if op == "admit":
            from_parked = bool(parked)
            ctx = parked.pop(idx % len(parked)) if parked else n_tokens
            need = PageTable.pages_needed(ctx + 1, PS)
            if alloc.can_alloc(need):
                tables.append(PageTable(PS, alloc.alloc(need), num_tokens=ctx))
            elif from_parked:
                parked.append(ctx)                     # re-park the preempted ctx
        elif op == "preempt" and tables:
            t = tables.pop(idx % len(tables))
            parked.append(t.num_tokens)                # recompute-resume keeps only the ctx
            t.release(alloc)
        elif op == "fork" and tables:
            src = tables[idx % len(tables)]
            try:
                tables.append(src.fork(alloc))
            except OutOfPages:
                pass                                   # failed fork must leak nothing
        elif op == "grow" and tables:
            t = tables[idx % len(tables)]
            if t.capacity_tokens <= t.num_tokens and alloc.can_alloc(1):
                t.append_pages(alloc.alloc(1))
            t.num_tokens = min(t.num_tokens + 1, t.capacity_tokens)
        elif op == "free" and tables:
            tables.pop(idx % len(tables)).release(alloc)
        elif op == "speculate" and tables:
            # the paged engine's verify window: allocate pages covering
            # L..L+k up front, accept m <= k+1 tokens, trim back to
            # max(pre-spec pages, accepted coverage) — the freed tail must
            # be exactly the speculative overshoot, never a shared page
            t = tables[idx % len(tables)]
            L, k = t.num_tokens, 1 + n_tokens % 4
            n0 = len(t.pages)
            need = PageTable.pages_needed(L + k + 1, PS) - n0
            if need > 0:
                if not alloc.can_alloc(need):
                    check()
                    continue
                t.append_pages(alloc.alloc(need))
            m = 1 + (idx + n_tokens) % (k + 1)         # accepted run, 1..k+1
            keep = max(n0, PageTable.pages_needed(L + m, PS))
            t.trim(keep, alloc)
            t.num_tokens = L + m
            assert len(t.pages) >= PageTable.pages_needed(L + m, PS)
        check()

    for t in tables:
        t.release(alloc)
    alloc.check_invariants()
    assert alloc.used_pages == 0
    assert alloc.free_pages == alloc.num_pages - 1


def _stream(family: int, n: int):
    """Deterministic token stream for one prompt family: families sharing a
    base share a 10-token prefix (2.5 pages at PS=4 — real prefix overlap
    AND mid-node splits), then diverge."""
    base = family % 3
    return [base if i < 10 else base + 3 * (1 + family % 2) for i in range(n)]


@given(
    ops=st.lists(
        st.tuples(
            st.sampled_from(["admit", "preempt", "fork", "grow", "free",
                             "cache", "evict"]),
            st.integers(0, 15),            # which live sequence the op targets
            st.integers(0, 5),             # prompt family (shared prefixes)
            st.integers(1, 14),            # admit context length / evict count
        ),
        min_size=1,
        max_size=80,
    )
)
@settings(max_examples=120, deadline=None)
def test_prefix_cache_allocator_invariants_under_random_interleavings(ops):
    """The radix-tree prefix cache interleaved with the full sequence
    lifecycle — admit (match-on-admit: acquire + alloc the suffix), preempt
    (drop every reference, cache survives), fork (prefix sharing + path
    pin), grow, free, release-to-cache (insert full pages, free the tail)
    and LRU eviction — asserting after every op that (a) the allocator's
    free/used partition is exact, (b) every page's ref-count equals live
    tables holding it plus the tree's single reference, and (c) the tree's
    structural/counter invariants hold. Finally releasing everything and
    dropping the cache must return the pool to fully free."""
    PS = 4
    alloc = BlockAllocator(num_pages=17, page_size=PS)
    cache = PrefixCache(alloc, PS)
    live = []                                   # (table, tokens, node-or-None)

    def check():
        alloc.check_invariants()
        cache.check_invariants()
        held = Counter(p for t, _, _ in live for p in t.pages)
        tree_pages = set(cache.pages())
        for page in set(held) | tree_pages:
            expect = held.get(page, 0) + (1 if page in tree_pages else 0)
            assert alloc.ref_count(page) == expect, (page, expect)
        assert alloc.used_pages == len(set(held) | tree_pages)
        assert alloc.free_pages == alloc.num_pages - 1 - alloc.used_pages

    for op, idx, family, n in ops:
        if op == "admit":
            toks = _stream(family, n)
            need = PageTable.pages_needed(len(toks) + 1, PS)
            pages, node, matched = cache.acquire(toks)
            if alloc.can_alloc(need - len(pages)):
                t = PageTable(PS, pages + alloc.alloc(need - len(pages)),
                              num_tokens=len(toks))
                live.append((t, toks, node))
            else:
                cache.cancel(pages, node)        # failed admission leaks nothing
        elif op == "preempt" and live:           # == free: recompute-resume
            t, _, node = live.pop(idx % len(live))
            if node is not None:
                cache.release(node)
            t.release(alloc)
        elif op == "fork" and live:
            t, toks, node = live[idx % len(live)]
            try:
                f = t.fork(alloc)
            except OutOfPages:
                continue                         # failed fork must leak nothing
            live.append((f, list(toks), cache.pin(node) if node is not None else None))
        elif op == "grow" and live:
            t, toks, node = live[idx % len(live)]
            if t.capacity_tokens <= t.num_tokens:
                if not alloc.can_alloc(1):
                    continue
                t.append_pages(alloc.alloc(1))
            toks.append(_stream(family, t.num_tokens + 1)[-1])
            t.num_tokens += 1
        elif op == "free" and live:
            t, _, node = live.pop(idx % len(live))
            if node is not None:
                cache.release(node)
            t.release(alloc)
        elif op == "cache" and live:             # release-to-cache
            t, toks, node = live.pop(idx % len(live))
            if node is not None:
                cache.release(node)
            n_full = len(toks) // PS
            cache.insert(toks, t.pages[:n_full])
            alloc.free(t.pages[n_full:])
        elif op == "evict":
            cache.evict(n)
        check()

    for t, _, node in live:
        if node is not None:
            cache.release(node)
        t.release(alloc)
    cache.drop()
    alloc.check_invariants()
    assert alloc.used_pages == 0
    assert alloc.free_pages == alloc.num_pages - 1


# ---------------------------------------------------------------------------
# Paged attention kernel vs pure-jnp reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("lens,KV,G,hd,ps,P", [
    pytest.param([1, 5, 17, 32], 2, 3, 16, 8, 4, id="lens0"),
    pytest.param([8, 8, 8, 8], 2, 3, 16, 8, 4, id="lens1"),
    pytest.param([31, 2, 16, 1], 2, 3, 16, 8, 4, id="lens2"),
    # several blocks of ``pages_per_block`` pages a row (8 here) and a dead
    # block in every batch: rows of 1, ps, ppb * ps, ppb * ps + 1 tokens and
    # a full row side by side, at SmolLM-360M's and Granite-8B's head shapes
    pytest.param([1, 16, 128, 129, 272], 5, 3, 64, 16, 17, id="walk-smollm"),
    pytest.param([272, 1, 129, 2, 16, 128], 8, 4, 128, 16, 17, id="walk-granite"),
])
def test_paged_attention_kernel_matches_ref(lens, KV, G, hd, ps, P):
    from repro.kernels.paged_attention.kernel import pages_per_block, paged_attention_grouped
    from repro.kernels.paged_attention.ref import paged_attention_ref

    rng = np.random.default_rng(0)
    B, NP = len(lens), 1 + len(lens) * P
    ppb = pages_per_block(ps, KV, hd, 4, P)
    assert P == ppb or P >= 2 * ppb + 1       # one block, or a dead one besides
    q = jnp.asarray(rng.normal(size=(B, KV, G, hd)), jnp.float32)
    pk = jnp.asarray(rng.normal(size=(NP, KV, ps, hd)), jnp.float32)
    pv = jnp.asarray(rng.normal(size=(NP, KV, ps, hd)), jnp.float32)
    # distinct physical pages per sequence, padded with the null page
    perm = rng.permutation(np.arange(1, NP))[: B * P].reshape(B, P)
    tab = np.where(
        np.arange(P)[None, :] < -(-np.asarray(lens) // ps)[:, None], perm, NULL_PAGE
    )
    o_kernel = paged_attention_grouped(
        q, pk, pv, jnp.asarray(tab, jnp.int32), jnp.asarray(lens, jnp.int32), interpret=True
    )
    o_ref = paged_attention_ref(q, pk, pv, jnp.asarray(tab, jnp.int32), jnp.asarray(lens, jnp.int32))
    assert jnp.allclose(o_kernel, o_ref, atol=1e-5), float(jnp.max(jnp.abs(o_kernel - o_ref)))


@pytest.mark.parametrize("softcap", [20.0, 5.0])
def test_paged_attention_kernel_softcap_matches_ref(softcap):
    """The decode kernel's gemma-style logit softcap must match the jnp
    oracle (and differ from the uncapped scores — the cap is really on)."""
    from repro.kernels.paged_attention.kernel import paged_attention_grouped
    from repro.kernels.paged_attention.ref import paged_attention_ref

    rng = np.random.default_rng(1)
    B, KV, G, hd, ps, P, NP = 3, 2, 2, 16, 8, 4, 16
    q = jnp.asarray(rng.normal(size=(B, KV, G, hd)), jnp.float32) * 4.0
    pk = jnp.asarray(rng.normal(size=(NP, KV, ps, hd)), jnp.float32)
    pv = jnp.asarray(rng.normal(size=(NP, KV, ps, hd)), jnp.float32)
    perm = rng.permutation(np.arange(1, NP))[: B * P].reshape(B, P)
    lens = jnp.asarray([7, 19, 30], jnp.int32)
    tab = jnp.asarray(perm, jnp.int32)
    o_kernel = paged_attention_grouped(q, pk, pv, tab, lens, interpret=True, softcap=softcap)
    o_ref = paged_attention_ref(q, pk, pv, tab, lens, softcap=softcap)
    o_uncapped = paged_attention_ref(q, pk, pv, tab, lens)
    assert jnp.allclose(o_kernel, o_ref, atol=1e-5), float(jnp.max(jnp.abs(o_kernel - o_ref)))
    assert not jnp.allclose(o_ref, o_uncapped, atol=1e-5), "softcap had no effect"


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("Lp,n_real", [(16, 2), (32, 3), (8, 1)])
def test_paged_prefill_write_kernel_matches_ref(Lp, n_real, dtype):
    """The Pallas prefill-write scatter lands exactly where the jnp ref
    does: the sequence's real pages get the transposed K/V chunks, bucket
    padding is absorbed by the null page, and every untouched page of the
    pool is preserved bit-for-bit (input/output aliasing)."""
    from repro.kernels.paged_attention.kernel import paged_prefill_write_grouped
    from repro.kernels.paged_attention.ref import paged_prefill_write_ref

    rng = np.random.default_rng(2)
    KV, hd, ps, NP, P = 2, 16, 8, 12, 6
    pool_k = jnp.asarray(rng.normal(size=(NP, KV, ps, hd)), jnp.float32).astype(dtype)
    pool_v = jnp.asarray(rng.normal(size=(NP, KV, ps, hd)), jnp.float32).astype(dtype)
    k = jnp.asarray(rng.normal(size=(1, Lp, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, Lp, KV, hd)), jnp.float32)
    real = rng.permutation(np.arange(1, NP))[:n_real]
    tab = np.full(P, NULL_PAGE, np.int32)
    tab[:n_real] = real
    tab = jnp.asarray(tab)
    rk, rv = paged_prefill_write_ref(pool_k, pool_v, k, v, tab)
    gk, gv = paged_prefill_write_grouped(pool_k, pool_v, k, v, tab, interpret=True)
    touched = np.zeros(NP, bool)
    touched[np.asarray(real)] = True
    # real pages carry the scattered prompt; the null page is garbage by
    # contract (duplicate pad writes race) and excluded from parity
    assert jnp.array_equal(jnp.asarray(gk)[touched], jnp.asarray(rk)[touched])
    assert jnp.array_equal(jnp.asarray(gv)[touched], jnp.asarray(rv)[touched])
    untouched = ~touched
    untouched[NULL_PAGE] = False
    assert jnp.array_equal(jnp.asarray(gk)[untouched], jnp.asarray(pool_k)[untouched])
    assert jnp.array_equal(jnp.asarray(gv)[untouched], jnp.asarray(pool_v)[untouched])


def test_paged_prefill_write_dispatch_ragged_falls_back():
    """ops.paged_prefill_write: page-multiple prompts use the Pallas kernel,
    ragged ones (bucketing off) the ref — both must agree with the ref."""
    from repro.kernels.paged_attention import ops as pa_ops
    from repro.kernels.paged_attention.ref import paged_prefill_write_ref

    rng = np.random.default_rng(3)
    KV, hd, ps, NP = 2, 8, 4, 8
    pool = jnp.asarray(rng.normal(size=(NP, KV, ps, hd)), jnp.float32)
    tab = jnp.asarray([3, 5, 0, 0], jnp.int32)
    for Lp in (8, 7):                       # page multiple, then ragged
        k = jnp.asarray(rng.normal(size=(1, Lp, KV, hd)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, Lp, KV, hd)), jnp.float32)
        gk, gv = pa_ops.paged_prefill_write(pool, pool, k, v, tab, use_pallas=True)
        rk, rv = paged_prefill_write_ref(pool, pool, k, v, tab)
        mask = np.zeros(NP, bool)
        mask[[3, 5]] = True
        assert jnp.array_equal(jnp.asarray(gk)[mask], jnp.asarray(rk)[mask]), Lp
        assert jnp.array_equal(jnp.asarray(gv)[mask], jnp.asarray(rv)[mask]), Lp


def test_softcap_dense_and_paged_engines_agree():
    """Gemma-style logit softcap now serves paged: the paged engine must
    emit exactly the dense engine's greedy tokens under softcap (regression
    for the paged_kv_pool_defs NotImplementedError)."""
    cfg = _smoke("smollm-360m").replace(logit_softcap=8.0)
    dense = InferenceEngine(cfg, EngineConfig(max_slots=2, max_len=64, max_new_tokens=4))
    d = dense.generate(PROMPTS)
    paged = PagedInferenceEngine(
        cfg,
        PagedEngineConfig(page_size=8, num_pages=17, max_slots=4, max_seq_len=64, max_new_tokens=4),
        params=dense.params,
    )
    p = paged.generate(PROMPTS)
    assert [s.out for s in d] == [s.out for s in p]
    paged.allocator.check_invariants()
    assert paged.allocator.used_pages == 0


# ---------------------------------------------------------------------------
# Paged engine v2
# ---------------------------------------------------------------------------

PROMPTS = [[1, 2, 3, 4], [5, 6, 7], [9, 10, 11, 12, 13]]


def _smoke(arch):
    cfg = get_config(arch, smoke=True).replace(attn_chunk=64)
    if cfg.moe is not None:
        # ample expert capacity => exact greedy (same trick as test_engine)
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    return cfg


@pytest.mark.parametrize("arch", ["smollm-360m", "jamba-1.5-large-398b", "xlstm-350m"])
def test_paged_engine_matches_dense_engine(arch):
    """Paged continuous batching must be a pure memory-layout change: same
    greedy tokens as the dense v1 engine (attn layers paged; recurrent
    mixers keep per-slot state)."""
    cfg = _smoke(arch)
    dense = InferenceEngine(cfg, EngineConfig(max_slots=2, max_len=64, max_new_tokens=4))
    d = dense.generate(PROMPTS)
    paged = PagedInferenceEngine(
        cfg,
        PagedEngineConfig(page_size=8, num_pages=17, max_slots=4, max_seq_len=64, max_new_tokens=4),
        params=dense.params,
    )
    p = paged.generate(PROMPTS)
    assert [s.out for s in d] == [s.out for s in p]
    paged.allocator.check_invariants()
    assert paged.allocator.used_pages == 0        # every page returned


def test_paged_engine_admission_gated_on_pages_not_slots():
    cfg = _smoke("smollm-360m")
    eng = PagedInferenceEngine(
        cfg,
        # 3 usable pages of 4 tokens; 8 slots — pages are the binding constraint
        PagedEngineConfig(page_size=4, num_pages=4, max_slots=8, max_seq_len=8, max_new_tokens=2),
    )
    for p in ([1, 2, 3, 4], [4, 5, 6, 7], [7, 8, 9, 1]):
        eng.submit(p)                             # each needs ceil(5/4) = 2 pages
    eng._admit()
    active = sum(1 for s in eng.slot_seq if s is not None)
    assert active == 1                            # only 1 more page after the first
    assert len(eng.waiting) == 2                  # rest held back by the free list
    assert eng.free_slots() == 7                  # slots were never the limit


def test_preemption_and_resume_identical_tokens():
    """Page exhaustion preempts the newest sequence; recompute-resume must
    reproduce the exact unpreempted continuation (greedy determinism)."""
    cfg = _smoke("smollm-360m")
    prompts = [[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 12], [2, 4, 6, 1]]
    ample = PagedInferenceEngine(
        cfg,
        PagedEngineConfig(page_size=4, num_pages=33, max_slots=4, max_seq_len=32, max_new_tokens=8),
    )
    a = ample.generate(prompts)
    assert ample.preemptions == 0
    # 9 usable pages: all 4 admit with 2 pages, growth to a 3rd page starves
    tight = PagedInferenceEngine(
        cfg,
        PagedEngineConfig(page_size=4, num_pages=10, max_slots=4, max_seq_len=32, max_new_tokens=8),
        params=ample.params,
    )
    t = tight.generate(prompts)
    assert tight.preemptions > 0
    assert [s.out for s in a] == [s.out for s in t]
    tight.allocator.check_invariants()
    assert tight.allocator.used_pages == 0


def test_fork_shares_prefix_pages_and_clones_continuation():
    cfg = _smoke("smollm-360m")
    eng = PagedInferenceEngine(
        cfg,
        PagedEngineConfig(page_size=4, num_pages=20, max_slots=4, max_seq_len=32, max_new_tokens=8),
    )
    sid = eng.submit([1, 2, 3, 4, 5, 6])
    eng.step()
    eng.step()                                    # a few tokens in, mid-page
    src_slot = next(i for i, s in enumerate(eng.slot_seq) if s is not None)
    shared = eng.tables[src_slot].pages[0]
    csid = eng.fork(sid)
    assert csid is not None
    assert eng.allocator.ref_count(shared) == 2   # prefix page shared, not copied
    done = {}
    for _ in range(40):
        for s in eng.step():
            done[s.sid] = s.out
        if len(done) == 2:
            break
    assert done[sid] == done[csid]                # greedy clones stay identical
    eng.allocator.check_invariants()
    assert eng.allocator.used_pages == 0


def test_stop_conditions_apply_to_prefill_emitted_token():
    """max_new_tokens=1 must yield exactly one token, delivered by the same
    step() that admitted the sequence — in both engines."""
    cfg = _smoke("smollm-360m")
    dense = InferenceEngine(cfg, EngineConfig(max_slots=2, max_len=32, max_new_tokens=1))
    paged = PagedInferenceEngine(
        cfg,
        PagedEngineConfig(page_size=8, num_pages=9, max_slots=2, max_seq_len=32, max_new_tokens=1),
        params=dense.params,
    )
    for eng in (dense, paged):
        eng.submit([1, 2, 3])
        out = eng.step()                          # admission alone finishes it
        assert len(out) == 1 and len(out[0].out) == 1 and out[0].done
    # greedy EOS emitted straight from prefill also stops immediately
    probe = InferenceEngine(
        cfg, EngineConfig(max_slots=1, max_len=32, max_new_tokens=8), params=dense.params
    ).generate([[1, 2, 3]])[0]
    eos = probe.out[0]
    eng2 = PagedInferenceEngine(
        cfg,
        PagedEngineConfig(page_size=8, num_pages=9, max_slots=1, max_seq_len=32,
                          max_new_tokens=8, eos_id=eos),
        params=dense.params,
    )
    s = eng2.generate([[1, 2, 3]])[0]
    assert s.out == [eos]
    eng2.allocator.check_invariants()
    assert eng2.allocator.used_pages == 0


def test_engine_capacity_telemetry_moves_with_load():
    cfg = _smoke("smollm-360m")
    eng = PagedInferenceEngine(
        cfg,
        PagedEngineConfig(page_size=4, num_pages=9, max_slots=4, max_seq_len=16, max_new_tokens=8),
    )
    before = eng.capacity_now()
    assert before["free_pages"] == 8
    eng.submit([1, 2, 3, 4, 5])
    eng.step()
    during = eng.capacity_now()
    assert during["free_pages"] < before["free_pages"]
    assert during["free_slots"] == 3
    assert eng.admission_capacity(est_tokens=5) < before["free_pages"]


# ---------------------------------------------------------------------------
# Live capacity feedback into Algorithm 1
# ---------------------------------------------------------------------------


def _router(flask_fn=None, docker_fn=None, queue_cap=64):
    mk = lambda t, cap, fn: Backend(
        t, run=lambda req: "ok", capacity=cap, queue_cap=queue_cap, capacity_fn=fn
    )
    return StraightLineRouter(
        {
            Tier.FLASK: mk(Tier.FLASK, 1, flask_fn),
            Tier.DOCKER: mk(Tier.DOCKER, 4, docker_fn),
            Tier.SERVERLESS: mk(Tier.SERVERLESS, 16, None),
        },
        policy=StraightLinePolicy(Thresholds(F=1e9, D=1e6)),
    )


def test_router_free_counts_only_capacity_not_queue_headroom():
    r = _router()
    b = r.backends[Tier.FLASK]
    assert r._free(Tier.FLASK) == 1
    b.inflight = 1
    assert r._free(Tier.FLASK) == 0               # busy tier is NOT available
    assert b.queue_cap > 0                        # ...even with queue headroom


def test_router_falls_back_to_static_capacity_when_probe_goes_dark():
    gauge = CapacityGauge()                       # nothing registered
    r = _router(flask_fn=lambda: gauge.free("flask"))
    assert r._free(Tier.FLASK) == 1               # None probe -> static capacity


def test_router_placement_follows_live_capacity_probe():
    gauge = CapacityGauge()
    free = {"flask": 1}
    gauge.register("flask", lambda: free["flask"])
    r = _router(flask_fn=lambda: gauge.free("flask"))
    t1 = r.submit(Request(rid=0, arrival_t=0.0, data_size=100.0))
    assert t1 == Tier.FLASK
    free["flask"] = 0                             # engine page pool exhausted
    t2 = r.submit(Request(rid=1, arrival_t=0.0, data_size=100.0))
    assert t2 == Tier.DOCKER                      # S_F empty -> fall through


def test_drain_runs_queued_work_even_when_probe_reports_zero():
    """Live capacity gates placement of NEW work; already-queued requests
    (e.g. Algorithm 1's unconditional big-payload -> docker path) must still
    drain when a probe is stuck at 0."""
    r = _router(docker_fn=lambda: 0)
    t = r.submit(Request(rid=0, arrival_t=0.0, data_size=5e6))
    assert t == Tier.DOCKER                       # r_d > D: placed regardless
    r.drain()
    assert not r.backends[Tier.DOCKER].queue
    assert r.metrics.total == 1 and not r.metrics.failed
    assert r.results[0] == "ok"


def test_submit_enforces_queue_cap_deflect_then_reject():
    """Admission control: a full backlog deflects to serverless instead of
    growing without bound; a full serverless queue rejects outright."""
    r = _router(queue_cap=1)
    big = lambda rid: Request(rid=rid, arrival_t=0.0, data_size=5e7)  # r_d > D
    assert r.submit(big(0)) == Tier.DOCKER        # placed, queued
    t1 = r.submit(big(1))
    assert t1 == Tier.SERVERLESS                  # docker backlog full -> deflect
    t2 = r.submit(big(2))
    assert t2 == Tier.SERVERLESS                  # even serverless is full...
    assert len(r.metrics.failed) == 1             # ...fast rejection, not queueing
    assert r.metrics.failed[0].fail_reason == "queue-full"
    assert len(r.backends[Tier.DOCKER].queue) == 1
    assert len(r.backends[Tier.SERVERLESS].queue) == 1
    r.drain()                                     # admitted work still completes
    assert r.metrics.total == 3 and len(r.metrics.failed) == 1


def test_retry_respects_serverless_queue_cap():
    """The failure-retry path must honor queue_cap too: with serverless
    saturated, a failing tier's request fails fast instead of growing the
    serverless backlog without bound."""
    from repro.core.router import StraightLineRouter

    def boom(req):
        raise RuntimeError("tier down")

    mk = lambda t, run, cap: Backend(t, run=run, capacity=cap, queue_cap=1)
    r = StraightLineRouter(
        {
            Tier.FLASK: mk(Tier.FLASK, boom, 1),
            Tier.DOCKER: mk(Tier.DOCKER, boom, 4),
            Tier.SERVERLESS: mk(Tier.SERVERLESS, lambda req: "ok", 16),
        },
        policy=StraightLinePolicy(Thresholds(F=1e9, D=1e6)),
    )
    r.backends[Tier.SERVERLESS].queue.append(
        Request(rid=99, arrival_t=0.0, data_size=1.0)
    )                                             # saturate the spill target
    r.submit(Request(rid=0, arrival_t=0.0, data_size=100.0))
    r.poll()                                      # flask run fails, cannot spill
    assert len(r.backends[Tier.SERVERLESS].queue) <= 1
    failed = [q for q in r.metrics.failed if q.rid == 0]
    assert failed and failed[0].fail_reason.startswith("error:")


def test_tiersim_free_slots_follows_capacity_probe():
    from repro.core.testbed import paper_tiers

    gauge = CapacityGauge()
    live = {"n": 5}
    gauge.register("flask", lambda: live["n"])
    tier = paper_tiers(seed=0)[Tier.FLASK]
    static = tier.free_slots()
    tier.capacity_probe = lambda: gauge.free("flask")
    assert tier.free_slots() == 5                 # live probe wins
    live["n"] = 0
    assert tier.free_slots() == 0
    gauge.unregister("flask")
    assert tier.free_slots() == static            # probe gone dark -> queue model


def test_place_all_big_payloads_consume_docker_availability():
    pol = StraightLinePolicy(Thresholds(F=1e9, D=1e3))
    reqs = [
        Request(rid=0, arrival_t=0.0, data_size=5e3),   # big -> docker
        Request(rid=1, arrival_t=0.0, data_size=10.0),  # moderate
    ]
    ds = pol.place_all(reqs, f_t=0.0, flask_free=0, docker_free=1)
    assert ds[0].tier == Tier.DOCKER
    assert ds[1].tier == Tier.SERVERLESS          # docker slot already consumed


# ---------------------------------------------------------------------------
# Chained (two-level) block tables
# ---------------------------------------------------------------------------


@given(
    ops=st.lists(
        st.tuples(
            st.sampled_from(["set", "grow", "trim", "clear", "pad"]),
            st.integers(0, 3),             # slot
            st.integers(0, 12),            # row length the op targets
        ),
        min_size=1,
        max_size=60,
    )
)
@settings(max_examples=120, deadline=None)
def test_chained_tables_match_flat_oracle_under_random_interleavings(ops):
    """ChainedTables is the engine's device-side view of per-slot page rows:
    drive the rewrite patterns the engine produces — whole-row set (admit /
    resume / fork), grow by one page, trim (spec-decode rollback), clear
    (release), and null-padded rows (the engine passes ``table.row(width)``
    verbatim) — and assert after every op that (a) ``flat_row`` re-derives
    exactly the flat row a one-level table would hold, and (b) the l2 row
    free-list/ownership invariants hold (no leak, no double-own, null row
    intact). Clearing every slot must return all table pages."""
    from repro.serving.paging import ChainedTables

    MAX_SLOTS, W1, TPP = 4, 3, 4
    ct = ChainedTables(MAX_SLOTS, W1, TPP)
    oracle = {s: [] for s in range(MAX_SLOTS)}   # slot -> non-null page list
    next_page = [1]

    def pages(n):
        out = list(range(next_page[0], next_page[0] + n))
        next_page[0] += n
        return out

    for op, slot, n in ops:
        if op == "set":
            oracle[slot] = pages(n)
            ct.set_row(slot, oracle[slot])
        elif op == "grow":
            if len(oracle[slot]) < W1 * TPP:
                oracle[slot] = oracle[slot] + pages(1)
            ct.set_row(slot, oracle[slot])
        elif op == "trim":
            oracle[slot] = oracle[slot][: n % (len(oracle[slot]) + 1)]
            ct.set_row(slot, oracle[slot])
        elif op == "clear":
            oracle[slot] = []
            ct.clear(slot)
        elif op == "pad":
            # engine-style: a full-width row with trailing null padding must
            # cost exactly the table pages the real prefix needs
            row = oracle[slot] + [NULL_PAGE] * (W1 * TPP - len(oracle[slot]))
            ct.set_row(slot, row)
        ct.check_invariants(MAX_SLOTS)
        for s in range(MAX_SLOTS):
            want = oracle[s] + [NULL_PAGE] * (W1 * TPP - len(oracle[s]))
            assert ct.flat_row(s) == want, (s, oracle[s])
        used_rows = sum(-(-len(r) // TPP) for r in oracle.values())
        assert ct.free_rows == ct.l2.shape[0] - 1 - used_rows

    for s in range(MAX_SLOTS):
        ct.clear(s)
    ct.check_invariants(MAX_SLOTS)
    assert ct.free_rows == ct.l2.shape[0] - 1


def test_chained_tables_reject_overlong_row():
    from repro.serving.paging import ChainedTables

    ct = ChainedTables(2, 2, 4)
    with pytest.raises(ValueError, match="chained capacity"):
        ct.set_row(0, list(range(1, 10)))
    ct.check_invariants(2)
    assert ct.free_rows == ct.l2.shape[0] - 1     # failed set leaks nothing
