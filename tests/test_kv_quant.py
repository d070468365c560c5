"""Int8 paged KV cache + chained block tables: kernel-vs-ref parity across
page sizes and activation dtypes, engine-level greedy token-match guards
(int8-vs-f32 across chunked / prefix-cache / spec-decode / preemption), the
dense bf16 cache counterpart, long-context admission through chained tables,
and the kv-memory telemetry export."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.serving.engine import (
    EngineConfig,
    InferenceEngine,
    PagedEngineConfig,
    PagedInferenceEngine,
)
from repro.serving.paging import NULL_PAGE

PROMPTS = [[1, 2, 3, 4], [5, 6, 7], [9, 10, 11, 12, 13]]


def _smoke(arch="smollm-360m"):
    cfg = get_config(arch, smoke=True).replace(attn_chunk=64)
    if cfg.moe is not None:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    return cfg


def _quant_pools(rng, NP, KV, ps, hd, n_filled):
    """An int8 pool quartet with pages [1, n_filled] holding quantized
    normal K/V (written via the ref quantizer) — plus the f32 originals
    reassembled from the same writes for bounded-error comparison."""
    from repro.kernels.paged_attention.ref import paged_prefill_write_quant_ref

    pool_k = jnp.zeros((NP, KV, ps, hd), jnp.int8)
    pool_v = jnp.zeros((NP, KV, ps, hd), jnp.int8)
    pool_ks = jnp.zeros((NP, KV, ps, 1), jnp.bfloat16)
    pool_vs = jnp.zeros((NP, KV, ps, 1), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, n_filled * ps, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, n_filled * ps, KV, hd)), jnp.float32)
    tab = jnp.asarray(np.arange(1, n_filled + 1), jnp.int32)
    pool_k, pool_v, pool_ks, pool_vs = paged_prefill_write_quant_ref(
        pool_k, pool_v, pool_ks, pool_vs, k, v, tab
    )
    return pool_k, pool_v, pool_ks, pool_vs, k, v


# ---------------------------------------------------------------------------
# Kernel vs jnp reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("src_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("ps,Lp", [(4, 8), (8, 16), (16, 32)])
def test_paged_prefill_write_quant_kernel_matches_ref(ps, Lp, src_dtype):
    """The fused quantize-at-write Pallas scatter must land bit-identical
    int8 values AND scales to the jnp reference on every touched page —
    across page sizes and f32/bf16 source activations (the quantizer
    upcasts to f32 first, so both dtypes share one code path)."""
    from repro.kernels.paged_attention.kernel import paged_prefill_write_grouped_quant
    from repro.kernels.paged_attention.ref import paged_prefill_write_quant_ref

    rng = np.random.default_rng(7)
    KV, hd, NP = 2, 16, 12
    n_real = Lp // ps
    pool_k = jnp.zeros((NP, KV, ps, hd), jnp.int8)
    pool_v = jnp.zeros((NP, KV, ps, hd), jnp.int8)
    pool_ks = jnp.zeros((NP, KV, ps, 1), jnp.bfloat16)
    pool_vs = jnp.zeros((NP, KV, ps, 1), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, Lp, KV, hd)), jnp.float32).astype(src_dtype)
    v = jnp.asarray(rng.normal(size=(1, Lp, KV, hd)), jnp.float32).astype(src_dtype)
    real = rng.permutation(np.arange(1, NP))[:n_real]
    tab = np.full(n_real + 2, NULL_PAGE, np.int32)
    tab[:n_real] = real
    tab = jnp.asarray(tab)
    refs = paged_prefill_write_quant_ref(pool_k, pool_v, pool_ks, pool_vs, k, v, tab)
    outs = paged_prefill_write_grouped_quant(
        pool_k, pool_v, pool_ks, pool_vs, k, v, tab, interpret=True
    )
    touched = np.zeros(NP, bool)
    touched[np.asarray(real)] = True
    untouched = ~touched
    untouched[NULL_PAGE] = False
    # scales are bit-exact; int8 values may differ by 1 LSB where a bf16
    # source puts the quotient within 1 ulp of a rounding tie (x/s vs the
    # compiler's reciprocal form) — f32 sources never hit a tie, so they
    # must be bit-exact
    for name, got, want in zip(("k", "v", "ks", "vs"), outs, refs):
        g, w = np.asarray(jnp.asarray(got)), np.asarray(jnp.asarray(want))
        assert np.array_equal(g[untouched], w[untouched]), name
        if name in ("ks", "vs") or src_dtype == jnp.float32:
            assert np.array_equal(g[touched], w[touched]), name
        else:
            d = np.abs(g[touched].astype(np.int32) - w[touched].astype(np.int32))
            assert d.max() <= 1 and (d > 0).mean() < 1e-3, (name, d.max(), (d > 0).mean())


@pytest.mark.parametrize("ps", [4, 8, 16])
def test_paged_attention_quant_kernel_matches_ref(ps):
    """Dequant-on-gather inside the decode kernel must match the jnp oracle
    (gather -> dequantize -> dense attention) on an int8 pool."""
    from repro.kernels.paged_attention.kernel import paged_attention_grouped
    from repro.kernels.paged_attention.ref import paged_attention_ref

    rng = np.random.default_rng(11)
    B, KV, G, hd, NP, n_filled = 3, 2, 2, 16, 12, 9
    pool_k, pool_v, pool_ks, pool_vs, _, _ = _quant_pools(rng, NP, KV, ps, hd, n_filled)
    q = jnp.asarray(rng.normal(size=(B, KV, G, hd)), jnp.float32)
    P = 3
    tab = jnp.asarray([[1, 2, 3], [4, 5, NULL_PAGE], [6, 7, 8]], jnp.int32)
    lens = jnp.asarray([3 * ps - 1, ps + 2, 2 * ps], jnp.int32)
    o_kernel = paged_attention_grouped(
        q, pool_k, pool_v, tab, lens, interpret=True, pool_ks=pool_ks, pool_vs=pool_vs
    )
    o_ref = paged_attention_ref(q, pool_k, pool_v, tab, lens, pool_ks=pool_ks, pool_vs=pool_vs)
    err = float(jnp.max(jnp.abs(o_kernel - o_ref)))
    assert err < 2e-5, err
    assert o_kernel.shape == (B, KV, G, hd) and P == tab.shape[1]


def test_paged_attention_quant_bounded_error_vs_f32():
    """The int8 decode output must stay within quantization-error distance
    of attention over the original f32 K/V — the bounded-logit-error guard
    behind the engine token-match tests."""
    from repro.kernels.paged_attention.kernel import paged_attention_grouped
    from repro.kernels.paged_attention.ref import paged_prefill_write_ref

    rng = np.random.default_rng(13)
    KV, G, hd, ps, NP, n_filled = 2, 3, 32, 8, 12, 8
    pool_k, pool_v, pool_ks, pool_vs, k, v = _quant_pools(rng, NP, KV, ps, hd, n_filled)
    f32_k = jnp.zeros((NP, KV, ps, hd), jnp.float32)
    f32_v = jnp.zeros((NP, KV, ps, hd), jnp.float32)
    tab = jnp.asarray(np.arange(1, n_filled + 1), jnp.int32)
    f32_k, f32_v = paged_prefill_write_ref(f32_k, f32_v, k, v, tab)
    q = jnp.asarray(rng.normal(size=(2, KV, G, hd)), jnp.float32)
    tab2 = jnp.stack([tab, tab])
    lens = jnp.asarray([n_filled * ps, n_filled * ps - 3], jnp.int32)
    o_q = paged_attention_grouped(
        q, pool_k, pool_v, tab2, lens, interpret=True, pool_ks=pool_ks, pool_vs=pool_vs
    )
    o_f = paged_attention_grouped(q, f32_k, f32_v, tab2, lens, interpret=True)
    err = float(jnp.max(jnp.abs(o_q - o_f)))
    # per-element quant error is <= absmax/254 ~ 2% relative; softmax mixing
    # keeps the output perturbation the same order
    assert err < 0.15, err
    assert err > 0.0, "quantization was a no-op — int8 leg not exercised"


@pytest.mark.parametrize("quant", [False, True])
def test_paged_attention_chained_matches_flat(quant):
    """A chained (l1 -> l2 -> page) table must produce EXACTLY the flat
    table's decode output — the indirection is pure addressing, quantized
    or not."""
    from repro.kernels.paged_attention.kernel import paged_attention_grouped
    from repro.kernels.paged_attention.ref import chain_rows

    rng = np.random.default_rng(17)
    B, KV, G, hd, ps, NP = 2, 2, 2, 16, 8, 12
    if quant:
        pool_k, pool_v, pool_ks, pool_vs, _, _ = _quant_pools(rng, NP, KV, ps, hd, 9)
    else:
        pool_k = jnp.asarray(rng.normal(size=(NP, KV, ps, hd)), jnp.float32)
        pool_v = jnp.asarray(rng.normal(size=(NP, KV, ps, hd)), jnp.float32)
        pool_ks = pool_vs = None
    flat = jnp.asarray([[3, 5, 1, NULL_PAGE], [3, 5, NULL_PAGE, NULL_PAGE]], jnp.int32)
    l2 = jnp.asarray([[NULL_PAGE, NULL_PAGE], [3, 5], [1, NULL_PAGE], [3, 5]], jnp.int32)
    l1 = jnp.asarray([[1, 2], [3, 0]], jnp.int32)
    assert jnp.array_equal(chain_rows(l1, l2), flat)
    q = jnp.asarray(rng.normal(size=(B, KV, G, hd)), jnp.float32)
    lens = jnp.asarray([2 * ps + 3, ps + 2], jnp.int32)
    o_flat = paged_attention_grouped(
        q, pool_k, pool_v, flat, lens, interpret=True, pool_ks=pool_ks, pool_vs=pool_vs
    )
    o_chain = paged_attention_grouped(
        q, pool_k, pool_v, l1, lens, interpret=True,
        pool_ks=pool_ks, pool_vs=pool_vs, l2_tab=l2,
    )
    assert jnp.array_equal(o_flat, o_chain)


# ---------------------------------------------------------------------------
# Engine-level greedy token match: int8 vs f32 storage
# ---------------------------------------------------------------------------

def _rand_prompts(seed, n, length):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, 512, length)] for _ in range(n)]


def _motif_prompts(seed, n, length):
    """Period-4 repetition so the n-gram speculative proposer actually
    fires (random prompts give it nothing to match)."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        motif = [int(t) for t in rng.integers(1, 512, 4)]
        out.append((motif * ((length + 3) // 4))[:length])
    return out


# Greedy token-match int8-vs-f32 is a property of the logit margins, not of
# the storage format: quantization shifts logits by a bounded amount (the
# kernel-level test above), so on prompts whose greedy gaps exceed it the
# token streams must be identical. The prompt seeds are fixed and the whole
# pipeline is deterministic — each variant exercises its path for real
# (two chunks, accepted proposals, actual preemptions). The random weights
# come from JAX's default threefry stream (partitionable since JAX 0.5), so
# a change of PRNG implementation calls for the seeds to be chosen again.
VARIANTS = {
    "plain":   dict(kw={}, num_pages=33, prompts=_rand_prompts(102, 4, 4)),
    "chunked": dict(kw={"chunk_tokens": 16}, num_pages=65,
                    prompts=_rand_prompts(200, 3, 20)),       # 16+4: two chunks
    "spec":    dict(kw={"spec_tokens": 3}, num_pages=65,
                    prompts=_motif_prompts(310, 3, 14)),
    "preempt": dict(kw={}, num_pages=10, prompts=_rand_prompts(102, 4, 4)),
}


@pytest.mark.parametrize("variant", ["plain", "chunked", "spec", "preempt"])
def test_paged_engine_int8_matches_f32_greedy(variant):
    """Int8 KV storage must be invisible to greedy decoding on every paged
    execution path — full prefill, chunked prefill, speculative decode
    (verify writes + gathers ride the quantized pool), and
    preemption/recompute-resume."""
    cfg = _smoke()
    spec = VARIANTS[variant]
    mk = lambda dt, p: PagedInferenceEngine(
        cfg,
        PagedEngineConfig(page_size=4, num_pages=spec["num_pages"], max_slots=4,
                          max_seq_len=32, max_new_tokens=8, cache_dtype=dt,
                          **spec["kw"]),
        params=p,
    )
    f32 = mk("f32", None)
    i8 = mk("int8", f32.params)
    assert f32.capacity_now()["kv_cache_dtype"] == "float32"
    assert i8.capacity_now()["kv_cache_dtype"] == "int8"
    a = f32.generate(spec["prompts"])
    b = i8.generate(spec["prompts"])
    assert [s.out for s in a] == [s.out for s in b]
    if variant == "preempt":
        assert i8.preemptions > 0
    if variant == "spec":
        assert i8.spec_accepted > 0
    i8.allocator.check_invariants()
    assert i8.allocator.used_pages == 0


def test_paged_engine_int8_matches_f32_with_prefix_cache():
    """Radix-tree prefix reuse over a quantized pool: cached int8 pages are
    re-attached verbatim, so the second wave (full prefix hits) must match
    the f32 engine token-for-token."""
    cfg = _smoke()
    mk = lambda dt, p: PagedInferenceEngine(
        cfg,
        PagedEngineConfig(page_size=4, num_pages=33, max_slots=4, max_seq_len=32,
                          max_new_tokens=6, prefix_cache=True, cache_dtype=dt),
        params=p,
    )
    f32 = mk("f32", None)
    i8 = mk("int8", f32.params)
    shared = [3, 1, 4, 1, 5, 9, 2, 6]
    waves = [[shared + [7], shared + [8]], [shared + [7], shared + [2, 7]]]
    for wave in waves:
        a = f32.generate(wave)
        b = i8.generate(wave)
        assert [s.out for s in a] == [s.out for s in b]
    assert i8.capacity_now()["prefix_hit_rate"] > 0
    i8.allocator.check_invariants()


def test_dense_engine_bf16_cache_matches_f32():
    """The dense engine's cheap counterpart: a bf16 KV cache must not
    change greedy tokens, and capacity telemetry must show the halved
    per-token footprint."""
    cfg = _smoke()
    f32 = InferenceEngine(cfg, EngineConfig(max_slots=2, max_len=64, max_new_tokens=4,
                                            cache_dtype="f32"))
    bf16 = InferenceEngine(cfg, EngineConfig(max_slots=2, max_len=64, max_new_tokens=4,
                                             cache_dtype="bf16"), params=f32.params)
    a = f32.generate(PROMPTS)
    b = bf16.generate(PROMPTS)
    assert [s.out for s in a] == [s.out for s in b]
    ca, cb = f32.capacity_now(), bf16.capacity_now()
    assert ca["kv_cache_dtype"] == "float32" and cb["kv_cache_dtype"] == "bfloat16"
    assert cb["kv_bytes_per_token"] == pytest.approx(ca["kv_bytes_per_token"] / 2)


def test_capacity_telemetry_reports_kv_bytes_per_token():
    """capacity_now() exports the storage dtype and measured bytes/token;
    int8 (values + bf16 scales) must land well under half of f32 — the
    number the placer uses to see a quantized tier's extra headroom."""
    cfg = _smoke()
    mk = lambda dt: PagedInferenceEngine(
        cfg,
        PagedEngineConfig(page_size=8, num_pages=17, max_slots=2, max_seq_len=64,
                          max_new_tokens=2, cache_dtype=dt),
    )
    snaps = {dt: mk(dt).capacity_now() for dt in ("f32", "bf16", "int8")}
    assert snaps["f32"]["kv_cache_dtype"] == "float32"
    assert snaps["bf16"]["kv_bytes_per_token"] == pytest.approx(
        snaps["f32"]["kv_bytes_per_token"] / 2
    )
    ratio = snaps["f32"]["kv_bytes_per_token"] / snaps["int8"]["kv_bytes_per_token"]
    assert ratio >= 1.8, ratio

    from repro.core.telemetry import CapacityGauge, kv_bytes_per_token, kv_cache_dtype

    assert kv_bytes_per_token(snaps["int8"]) == snaps["int8"]["kv_bytes_per_token"]
    assert kv_cache_dtype(snaps["int8"]) == "int8"
    assert kv_bytes_per_token({}) is None and kv_cache_dtype(None) is None
    g = CapacityGauge()
    g.register_stats("flask", lambda: snaps["int8"])
    assert g.kv_cache_dtype("flask") == "int8"
    assert g.kv_bytes_per_token("flask") == snaps["int8"]["kv_bytes_per_token"]


def test_cache_dtype_rejects_unknown_choice():
    cfg = _smoke()
    with pytest.raises(ValueError, match="cache_dtype"):
        PagedInferenceEngine(
            cfg,
            PagedEngineConfig(page_size=8, num_pages=17, max_slots=2,
                              max_seq_len=64, cache_dtype="fp8"),
        )


# ---------------------------------------------------------------------------
# Chained tables: long-context admission regression
# ---------------------------------------------------------------------------


def test_long_prompt_admitted_via_chained_tables():
    """Regression: a prompt longer than the flat block-table width used to
    be structurally unservable — the flat engine cannot even construct when
    table_width > num_pages - 1. Chained tables re-derive the admission cap
    from pool capacity: the same pool admits and COMPLETES the long prompt,
    and over-pool prompts get the new capacity-derived rejection."""
    cfg = _smoke()
    long_prompt = list(np.random.default_rng(0).integers(1, cfg.vocab_size, 200))
    flat = PagedEngineConfig(page_size=8, num_pages=33, max_slots=2,
                             max_seq_len=1024, max_new_tokens=4)
    with pytest.raises(ValueError, match="num_pages"):
        PagedInferenceEngine(cfg, flat)
    eng = PagedInferenceEngine(cfg, dataclasses.replace(flat, chained_tables=True))
    assert eng._len_cap == min(1024, flat.cache_tokens)
    seqs = eng.generate([long_prompt])
    assert len(seqs[0].out) == 4 and seqs[0].done
    eng.allocator.check_invariants()
    eng.chain.check_invariants(eng.pcfg.max_slots)
    assert eng.allocator.used_pages == 0
    # beyond the POOL (not the table): rejected at submit with the new cap
    over = list(np.random.default_rng(1).integers(1, cfg.vocab_size, 300))
    with pytest.raises(ValueError, match="length cap"):
        eng.submit(over)


@pytest.mark.parametrize("variant", ["plain", "chunked", "spec", "preempt"])
def test_chained_engine_matches_flat_engine(variant):
    """With geometry where both construct, chained indirection must be a
    pure addressing change: identical greedy tokens to the flat engine on
    every execution path, with table rows fully recycled at the end."""
    cfg = _smoke()
    spec = VARIANTS[variant]
    mk = lambda chained, p: PagedInferenceEngine(
        cfg,
        PagedEngineConfig(page_size=4, num_pages=spec["num_pages"], max_slots=4,
                          max_seq_len=32, max_new_tokens=8,
                          chained_tables=chained, **spec["kw"]),
        params=p,
    )
    flat = mk(False, None)
    chained = mk(True, flat.params)
    a = flat.generate(spec["prompts"])
    b = chained.generate(spec["prompts"])
    assert [s.out for s in a] == [s.out for s in b]
    if variant == "preempt":
        assert chained.preemptions > 0
    chained.allocator.check_invariants()
    chained.chain.check_invariants(chained.pcfg.max_slots)
    assert chained.allocator.used_pages == 0
    assert chained.chain.free_rows == chained.chain.l2.shape[0] - 1


def test_chained_plus_int8_long_context_end_to_end():
    """The two tentpole halves composed: an int8 pool addressed through
    chained tables serves a long prompt with tokens identical to the f32
    chained engine."""
    cfg = _smoke()
    long_prompt = list(np.random.default_rng(2).integers(1, cfg.vocab_size, 120))
    mk = lambda dt, p: PagedInferenceEngine(
        cfg,
        PagedEngineConfig(page_size=8, num_pages=33, max_slots=2, max_seq_len=1024,
                          max_new_tokens=4, chained_tables=True, cache_dtype=dt),
        params=p,
    )
    f32 = mk("f32", None)
    i8 = mk("int8", f32.params)
    a = f32.generate([long_prompt])
    b = i8.generate([long_prompt])
    assert [s.out for s in a] == [s.out for s in b]
    i8.allocator.check_invariants()
    assert i8.allocator.used_pages == 0
