"""Dense vs paged serving at an EQUAL cache-byte budget — plus the int8 leg.

The dense v1 engine reserves a full ``max_len`` KV stripe per slot, so its
concurrency ceiling is ``cache_tokens / max_len`` regardless of how short
the sequences actually are. The paged v2 engine hands out fixed-size pages
on demand, so the same byte budget admits ~``cache_tokens / actual_len``
sequences. This benchmark serves an identical short-request workload
through both engines over the same token budget and reports peak concurrent
sequences, decode steps, and throughput.

The quantized leg repeats the trick one level down: int8 pages (values +
per-(page-slot, head) bf16 scales) cost ~1/3.6 the bytes of f32 pages, so
at an equal BYTE budget the int8 pool holds proportionally more pages —
and therefore more concurrent residents — while greedy outputs must stay
token-identical to the f32 pool. Gated in CI via ``--fast``.

    PYTHONPATH=src python benchmarks/paged_decode.py [--fast]
"""
from __future__ import annotations

import sys
import time

from benchmarks.common import emit

MAX_LEN = 128          # dense per-slot reservation (tokens)
CACHE_TOKENS = 256     # shared budget: dense fits 2 slots, paged fits 16 pages
PAGE_SIZE = 16
PROMPT, NEW = 6, 8     # actual request size: ~14 tokens, 1/9th of MAX_LEN
N_REQ = 24

QUANT_F32_PAGES = 8    # f32 leg's usable pages — the byte budget
QUANT_N_REQ = 40       # enough pending work to fill the int8 pool's extra pages
QUANT_SLOTS = 32
QUANT_SEED = 7000      # the quant leg's own prompt stream: greedy margins on
                       # these prompts exceed the int8 perturbation, so token
                       # match is a real (and reproducible) guarantee


def run_dense(cfg, params):
    from repro.serving.engine import EngineConfig, InferenceEngine

    eng = InferenceEngine(
        cfg,
        EngineConfig(max_slots=CACHE_TOKENS // MAX_LEN, max_len=MAX_LEN, max_new_tokens=NEW),
        params=params,
    )
    return _serve(eng, N_REQ, 0), eng


def run_paged(cfg, params, cache_dtype="", num_pages=None, max_slots=None, n_req=N_REQ,
              seed_base=0):
    from repro.serving.engine import PagedEngineConfig, PagedInferenceEngine

    eng = PagedInferenceEngine(
        cfg,
        PagedEngineConfig(
            page_size=PAGE_SIZE,
            num_pages=num_pages or 1 + CACHE_TOKENS // PAGE_SIZE,  # +1: null page
            max_slots=max_slots or CACHE_TOKENS // PAGE_SIZE,
            max_seq_len=MAX_LEN,
            max_new_tokens=NEW,
            cache_dtype=cache_dtype,
        ),
        params=params,
    )
    return _serve(eng, n_req, seed_base), eng


def _serve(eng, n_req: int, seed_base: int = 0):
    import numpy as np

    for i in range(n_req):
        eng.submit(
            list(np.random.default_rng(seed_base + i).integers(1, eng.cfg.vocab_size, PROMPT))
        )
    peak = 0
    steps = 0
    done = []
    t0 = time.perf_counter()
    while len(done) < n_req and steps < 10_000:
        done.extend(eng.step())
        peak = max(peak, sum(1 for s in eng.slot_seq if s is not None))
        steps += 1
    dt = time.perf_counter() - t0
    toks = sum(len(s.out) for s in done)
    return {
        "peak_concurrent": peak,
        "steps": steps,
        "wall_s": dt,
        "toks_per_s": toks / dt,
        "outs": {s.sid: s.out for s in done},
    }


def quant_leg(cfg, params) -> None:
    """Int8 vs f32 paged pools at EQUAL cache bytes: size the int8 pool to
    the f32 leg's byte budget using the engines' measured bytes/token, then
    serve the same workload through both and require >= 1.8x peak residents
    with token-identical greedy outputs."""
    f32_res, f32_eng = run_paged(
        cfg, params, "f32", num_pages=1 + QUANT_F32_PAGES,
        max_slots=QUANT_SLOTS, n_req=QUANT_N_REQ, seed_base=QUANT_SEED,
    )
    bpt_f32 = f32_eng.capacity_now()["kv_bytes_per_token"]
    # a 1-usable-page probe is the cheapest way to measure int8 bytes/token
    from repro.serving.engine import PagedEngineConfig, PagedInferenceEngine

    probe = PagedInferenceEngine(
        cfg,
        PagedEngineConfig(page_size=PAGE_SIZE, num_pages=2, max_slots=1,
                          max_seq_len=PAGE_SIZE, max_new_tokens=1, cache_dtype="int8"),
        params=f32_eng.params,
    )
    bpt_int8 = probe.capacity_now()["kv_bytes_per_token"]
    budget_bytes = QUANT_F32_PAGES * PAGE_SIZE * bpt_f32
    int8_pages = int(budget_bytes // (PAGE_SIZE * bpt_int8))
    int8_res, _ = run_paged(
        cfg, f32_eng.params, "int8", num_pages=1 + int8_pages,
        max_slots=QUANT_SLOTS, n_req=QUANT_N_REQ, seed_base=QUANT_SEED,
    )

    assert int8_res["outs"] == f32_res["outs"], "int8 pool changed greedy tokens"
    ratio = int8_res["peak_concurrent"] / f32_res["peak_concurrent"]
    for name, r in (("paged_f32", f32_res), ("paged_int8", int8_res)):
        emit(
            f"paged_decode.{name}",
            r["wall_s"] / max(1, r["steps"]) * 1e6,
            f"peak_concurrent={r['peak_concurrent']};steps={r['steps']};toks_per_s={r['toks_per_s']:.0f}",
        )
    emit(
        "paged_decode.int8_capacity_ratio", 0.0,
        f"int8_vs_f32={ratio:.1f}x;bytes_per_token={bpt_f32:.0f}->{bpt_int8:.0f};"
        f"pages={QUANT_F32_PAGES}->{int8_pages}",
    )
    print(
        f"\nequal cache bytes ({budget_bytes:.0f}): f32 pool peaks at "
        f"{f32_res['peak_concurrent']} concurrent sequences "
        f"({QUANT_F32_PAGES} pages), int8 at {int8_res['peak_concurrent']} "
        f"({int8_pages} pages, {ratio:.1f}x)"
    )
    assert ratio >= 1.8, f"int8 pool should hold >=1.8x concurrent sequences, got {ratio:.1f}x"
    print("OK — identical tokens, >=1.8x concurrency from the same cache bytes")


def main() -> None:
    from repro.configs.registry import get_config

    cfg = get_config("smollm-360m", smoke=True).replace(attn_chunk=64)
    paged_res, paged_eng = run_paged(cfg, None)
    dense_res, _ = run_dense(cfg, paged_eng.params)
    assert dense_res["outs"] == paged_res["outs"], "engines disagree on tokens"

    ratio = paged_res["peak_concurrent"] / dense_res["peak_concurrent"]
    for name, r in (("dense", dense_res), ("paged", paged_res)):
        emit(
            f"paged_decode.{name}",
            r["wall_s"] / max(1, r["steps"]) * 1e6,
            f"peak_concurrent={r['peak_concurrent']};steps={r['steps']};toks_per_s={r['toks_per_s']:.0f}",
        )
    emit("paged_decode.concurrency_ratio", 0.0, f"paged_vs_dense={ratio:.1f}x")
    print(
        f"\nequal cache budget ({CACHE_TOKENS} tokens): dense peaks at "
        f"{dense_res['peak_concurrent']} concurrent sequences, paged at "
        f"{paged_res['peak_concurrent']} ({ratio:.1f}x)"
    )
    assert ratio >= 2.0, f"paged engine should serve >=2x concurrent sequences, got {ratio:.1f}x"
    print("OK — identical tokens, >=2x concurrency from the same cache bytes")

    quant_leg(cfg, paged_eng.params)


if __name__ == "__main__":
    from repro.jax_cache import enable_compile_cache

    enable_compile_cache()
    # --fast: same tiny smoke workload — the flag exists for CI-invocation
    # parity with the other serving benchmarks (both legs are already sized
    # for a sub-minute run on the smoke model)
    sys.argv = [a for a in sys.argv if a != "--fast"]
    main()
