"""Observability overhead: tracing must be free when off, cheap when on.

The tentpole claim of the tracing/metrics subsystem is *zero-cost when
disabled*: every instrumentation site in the router/scheduler/engines is a
single ``if trace is not None`` branch, and a disabled ``Tracer`` returns
None from ``begin()``. This benchmark gates that claim on the hottest real
path — N concurrent requests through a paged engine's ``EngineLoop`` — in
three modes over identical workloads:

  off   — no tracer attached (``trace=None`` everywhere): the production
          default and the baseline wall time;
  null  — a ``Tracer(enabled=False)`` is consulted per request (the router
          path when tracing is configured off): must be indistinguishable
          from ``off``;
  on    — a live ``Trace`` per request PLUS a ``MonitorSampler`` sweeping
          the engine's ``capacity_now`` probe at 10 ms: bounded overhead.

Each mode runs R times interleaved (cancels thermal/jit drift) and the
best wall per mode is compared; host-side primitive costs (span/event
append, histogram observe) are emitted as microbenchmarks alongside.

    PYTHONPATH=src:. python benchmarks/observability_overhead.py [--fast]

Gates: null >= 0.90x off-throughput (≈0 disabled overhead) and
on >= 0.80x off-throughput (--fast; 0.85x full) — thresholds are lenient
against shared-runner timing noise, the expected gap is low single-digit
percent.
"""
from __future__ import annotations

import argparse
import threading
import time

from benchmarks.common import emit, timeit_us


def run_workload(engine, loop, prompts, tracer=None, sampler=None, timeout=600.0):
    """N threads submitting into one shared step loop; returns wall seconds.
    With a tracer, each request begins/finishes its own trace (the router's
    role in real serving)."""
    outs = [None] * len(prompts)

    def worker(i):
        trace = tracer.begin(i, bench=True) if tracer is not None else None
        seq = loop.wait(loop.submit(prompts[i], trace=trace), timeout)
        outs[i] = seq.out
        if tracer is not None:
            tracer.finish(trace, n_out=len(seq.out))

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(len(prompts))]
    if sampler is not None:
        sampler.start()
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if sampler is not None:
        sampler.stop()
    return wall, outs


def microbench():
    """Host-side primitive costs — what one instrumented site pays."""
    from repro.core.telemetry import Histogram, MetricsRegistry
    from repro.core.tracing import Trace, Tracer, trace_now

    trace = Trace(0)
    emit("observability.span_append_us",
         timeit_us(lambda: trace.add_span("s", 0.0, 1.0, lane="x", a=1), n=2000))
    emit("observability.event_append_us",
         timeit_us(lambda: trace.event("e", lane="x"), n=2000))
    hist = Histogram()
    emit("observability.hist_observe_us", timeit_us(lambda: hist.observe(0.01), n=5000))
    reg = MetricsRegistry()
    emit("observability.registry_counter_us",
         timeit_us(lambda: reg.counter("c", {"tier": "flask"}).inc(), n=5000))
    null = Tracer(enabled=False)
    emit("observability.null_begin_us", timeit_us(lambda: null.begin(0), n=10000))
    emit("observability.clock_us", timeit_us(trace_now, n=10000))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke: tiny workload, lenient gates")
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args()

    import numpy as np

    from repro.configs.registry import get_config
    from repro.core.telemetry import CapacityGauge, MetricsRegistry, MonitorSampler
    from repro.core.tracing import Tracer
    from repro.serving.engine import PagedEngineConfig, PagedInferenceEngine
    from repro.serving.scheduler import EngineLoop

    microbench()

    n_conc = 6 if args.fast else args.concurrency
    new_tok = 12 if args.fast else args.new_tokens
    repeats = 2 if args.fast else args.repeats
    prompt_len, maxlen, ps = 6, 128, 16

    cfg = get_config("smollm-360m", smoke=True).replace(attn_chunk=64)
    registry = MetricsRegistry()
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

    gauge = CapacityGauge()
    gauge.register_stats("bench", engine.capacity_now)

    null_tracer = Tracer(enabled=False)
    walls = {"off": [], "null": [], "on": []}
    outs_by_mode = {}
    with EngineLoop(engine, name="bench", registry=registry) as loop:
        for _ in range(repeats):            # interleave modes: cancels drift
            for mode in ("off", "null", "on"):
                tracer = {"off": None, "null": null_tracer, "on": Tracer()}[mode]
                sampler = (
                    MonitorSampler(gauge, interval_s=0.01, registry=registry)
                    if mode == "on" else None
                )
                wall, outs = run_workload(engine, loop, prompts, tracer, sampler)
                walls[mode].append(wall)
                outs_by_mode[mode] = outs

    assert outs_by_mode["off"] == outs_by_mode["null"] == outs_by_mode["on"], (
        "observability changed generated tokens"
    )
    n_tok = n_conc * new_tok
    best = {m: min(w) for m, w in walls.items()}
    for mode in ("off", "null", "on"):
        emit(f"observability_overhead.{mode}", best[mode] / n_tok * 1e6,
             f"thr={n_tok/best[mode]:.1f}tok/s")
    null_ratio = best["off"] / best["null"]      # >1 means null was FASTER
    on_ratio = best["off"] / best["on"]
    emit("observability_overhead.null_vs_off", 0.0, f"x{null_ratio:.3f}")
    emit("observability_overhead.on_vs_off", 0.0, f"x{on_ratio:.3f}")
    print(
        f"\n{n_conc} concurrent x {new_tok} tokens, best of {repeats}: "
        f"off {best['off']:.3f}s, disabled-tracer {best['null']:.3f}s "
        f"({null_ratio:.3f}x), tracing+sampler {best['on']:.3f}s ({on_ratio:.3f}x)"
    )

    null_floor, on_floor = (0.90, 0.80) if args.fast else (0.90, 0.85)
    assert null_ratio >= null_floor, (
        f"disabled tracer costs {(1-null_ratio)*100:.1f}% throughput "
        f"(floor {null_floor}x) — the zero-cost-when-disabled claim is broken"
    )
    assert on_ratio >= on_floor, (
        f"enabled tracing+sampling costs {(1-on_ratio)*100:.1f}% throughput "
        f"(floor {on_floor}x)"
    )
    print(f"OK — disabled tracing ≈ free ({null_ratio:.3f}x), enabled bounded "
          f"({on_ratio:.3f}x ≥ {on_floor}x)")


if __name__ == "__main__":
    from repro.jax_cache import enable_compile_cache

    enable_compile_cache()
    main()
