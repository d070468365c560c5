"""Continuous-batching step loop vs the serialized ``generate`` baseline.

One paged engine, N concurrent requests. The baseline is the pre-loop
router-worker behavior: every caller runs ``engine.generate`` which holds
the engine lock end-to-end, so concurrent generations serialize on the
device — N requests cost ~N full generations of decode steps. The
``EngineLoop`` path submits all N into the shared step loop: every decode
step advances EVERY active sequence in one batched device call (the engine's
decode batch is max_slots wide whether 1 or N slots are live), so N
interleaved requests cost ~1 generation's worth of steps plus the serial
prefills.

Measures wall time / throughput for both paths on the SAME engine with
identical greedy outputs required per request — the speedup is real batching,
not lost work. ``--fast`` (CI smoke) shrinks the workload and asserts the
mechanism (requests truly interleave: peak_active > 1, outputs identical)
rather than the full >=4x throughput bar.

    PYTHONPATH=src:. python benchmarks/continuous_batching.py [--fast]
"""
from __future__ import annotations

import argparse
import threading
import time

from benchmarks.common import emit


def run_serialized(engine, prompts):
    """N threads x lock-holding generate: the pre-loop router-worker path."""
    outs = [None] * len(prompts)

    def worker(i):
        outs[i] = engine.generate([prompts[i]])[0].out

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(len(prompts))]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return time.perf_counter() - t0, outs


def run_batched(engine, prompts, timeout=600.0):
    """N threads submitting into one shared step loop."""
    from repro.serving.scheduler import EngineLoop

    outs = [None] * len(prompts)
    with EngineLoop(engine) as loop:

        def worker(i):
            outs[i] = loop.wait(loop.submit(prompts[i]), timeout).out

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(len(prompts))]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
    return wall, outs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke: tiny workload, assert interleaving not the 4x bar")
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=24)
    args = ap.parse_args()

    import numpy as np

    from repro.configs.registry import get_config
    from repro.serving.engine import PagedEngineConfig, PagedInferenceEngine

    n_conc = 6 if args.fast else args.concurrency
    new_tok = 12 if args.fast else args.new_tokens
    prompt_len, maxlen, ps = 6, 128, 16

    cfg = get_config("smollm-360m", smoke=True).replace(attn_chunk=64)
    engine = PagedInferenceEngine(
        cfg,
        PagedEngineConfig(page_size=ps, num_pages=1 + n_conc * maxlen // ps,
                          max_slots=n_conc, max_seq_len=maxlen, max_new_tokens=new_tok),
    )
    prompts = [
        list(np.random.default_rng(i).integers(1, cfg.vocab_size, prompt_len))
        for i in range(n_conc)
    ]
    engine.prewarm()
    engine.generate([prompts[0]])           # compile the decode step too
    engine.peak_active = 0

    ser_wall, ser_outs = run_serialized(engine, prompts)
    assert engine.peak_active == 1, "serialized baseline unexpectedly interleaved"
    engine.peak_active = 0
    bat_wall, bat_outs = run_batched(engine, prompts)

    assert bat_outs == ser_outs, "batched outputs diverge from serialized baseline"
    assert all(len(o) == new_tok for o in bat_outs), "a request failed / stopped short"
    assert engine.peak_active > 1, (
        "step loop regressed to serialized execution (no interleaving observed)"
    )

    n_tok = n_conc * new_tok
    speedup = ser_wall / bat_wall
    emit("continuous_batching.serialized", ser_wall / n_tok * 1e6,
         f"thr={n_tok/ser_wall:.1f}tok/s")
    emit("continuous_batching.step_loop", bat_wall / n_tok * 1e6,
         f"thr={n_tok/bat_wall:.1f}tok/s;peak_active={engine.peak_active}")
    emit("continuous_batching.speedup", 0.0,
         f"x{speedup:.1f}_at_{n_conc}_concurrent;identical_outputs=True")
    print(
        f"\n{n_conc} concurrent requests x {new_tok} tokens: serialized {ser_wall:.2f}s "
        f"-> step loop {bat_wall:.2f}s ({speedup:.1f}x), peak batch "
        f"{engine.peak_active}/{n_conc}, outputs identical, zero failures"
    )
    if args.fast:
        assert speedup > 1.0, f"step loop slower than serialized baseline ({speedup:.2f}x)"
        print("OK (fast) — requests interleave in one decode batch, outputs identical")
    else:
        assert speedup >= 4.0, (
            f"continuous batching must give >=4x at {n_conc} concurrent, got {speedup:.1f}x"
        )
        print(f"OK — >={4.0}x throughput on one engine at {n_conc} concurrent requests")


if __name__ == "__main__":
    from repro.jax_cache import enable_compile_cache

    enable_compile_cache()
    main()
