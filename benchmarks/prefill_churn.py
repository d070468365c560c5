"""Prefill jit churn vs bucketed pad-aware prefill.

Both engines compile prefill once per distinct context shape. Without
bucketing, a heterogeneous traffic mix (the StraightLine setting: many apps,
many prompt lengths, preemption-resume multiplying lengths further) pays a
full XLA compile on the FIRST request at every new length — exactly the
time-to-first-token tail the placer is supposed to eliminate. Bucketing
right-pads every context to a power-of-two page multiple, so compilation is
O(num_buckets) and the tail disappears after warm-up.

This benchmark serves one request per distinct prompt length through each
engine with bucketing off/on and reports compile events plus p50/p99
time-to-first-token (the step that performs admission prefill).

    PYTHONPATH=src:. python benchmarks/prefill_churn.py
"""
from __future__ import annotations

import time

from benchmarks.common import emit
from repro.core.telemetry import percentile

PAGE = 4
MAX_SEQ = 64
LENGTHS = list(range(1, 19))     # 18 distinct prompt lengths
NEW = 2


def _serve_lengths(eng):
    """One request per length, measuring the admission step's wall time."""
    ttfts = []
    for L in LENGTHS:
        eng.submit([1 + (i % (eng.cfg.vocab_size - 1)) for i in range(L)])
        t0 = time.perf_counter()
        out = eng.step()                           # admit + prefill (+ decode)
        ttfts.append(time.perf_counter() - t0)
        for _ in range(50):
            if out:
                break
            out = eng.step()
    return ttfts


def _engines(cfg, params, bucket: bool):
    from repro.serving.engine import (
        EngineConfig,
        InferenceEngine,
        PagedEngineConfig,
        PagedInferenceEngine,
    )

    paged = PagedInferenceEngine(
        cfg,
        PagedEngineConfig(page_size=PAGE, num_pages=1 + MAX_SEQ // PAGE, max_slots=2,
                          max_seq_len=MAX_SEQ, max_new_tokens=NEW, bucket_prefill=bucket),
        params=params,
    )
    dense = InferenceEngine(
        cfg,
        EngineConfig(max_slots=2, max_len=MAX_SEQ, max_new_tokens=NEW,
                     bucket_unit=PAGE, bucket_prefill=bucket),
        params=paged.params,
    )
    return {"paged": paged, "dense": dense}


def main() -> None:
    from repro.configs.registry import get_config
    from repro.serving.paging import num_buckets

    cfg = get_config("smollm-360m", smoke=True).replace(attn_chunk=64)
    bound = num_buckets(PAGE, MAX_SEQ)
    results = {}
    params = None
    for bucket in (False, True):
        for name, eng in _engines(cfg, params, bucket).items():
            params = eng.params
            ttfts = _serve_lengths(eng)
            key = f"{name}.{'bucketed' if bucket else 'per_length'}"
            results[key] = (eng.compile_events, ttfts)
            emit(
                f"prefill_churn.{key}",
                percentile(ttfts, 50) * 1e6,
                f"compile_events={eng.compile_events};"
                f"p99_ttft_us={percentile(ttfts, 99) * 1e6:.0f};"
                f"lengths={len(LENGTHS)}",
            )

    for name in ("paged", "dense"):
        churn, _ = results[f"{name}.per_length"]
        bucketed, _ = results[f"{name}.bucketed"]
        assert churn == len(LENGTHS), (name, churn)
        assert bucketed <= bound, (name, bucketed, bound)
        print(
            f"{name}: {churn} prefill compiles for {len(LENGTHS)} lengths without "
            f"bucketing -> {bucketed} (bound {bound}) with bucketing"
        )
    print("OK — prefill compilation is O(num_buckets), not O(distinct lengths)")


if __name__ == "__main__":
    from repro.jax_cache import enable_compile_cache

    enable_compile_cache()
    main()
