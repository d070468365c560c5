"""Serving launcher: StraightLine router over live paged-engine tiers.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \\
        --requests 32 [--smoke] [--use-pallas] [--F 10] [--D 4096] \\
        [--weights-int8] [--workers 4] [--prewarm] [--serialized]

The model runs at its published width unless ``--smoke`` selects the tiny
configuration that the CPU can serve. ``--use-pallas`` turns on the Pallas
kernels (paged decode attention, the paged prefill write, rmsnorm); they
compile on a TPU and run in the interpreter on the CPU.

Three tiers front ``PagedInferenceEngine``s with page size 16 and 1024-token
rows, each pool sized to hold every slot at full length: interactive (1
slot), batch (8 slots) and elastic (4 slots, built on first use — its cold
start is the weight load and compile). Requests carry seeded prompts of 16
to 512 tokens.

``--workers N`` runs the concurrent router runtime (N worker threads per
tier, bounded by each tier's capacity); 0 keeps the serial poll loop.
``--chunk-tokens N`` enables chunked prefill on every engine tier: prompts
are absorbed N tokens per step under ``--step-budget`` (0 = auto,
2*chunk), so a long prompt landing on the interactive tier no longer
stalls every decoding slot for its whole prefill (see
benchmarks/chunked_prefill.py); 0 keeps whole-prompt prefill.
Engine tiers serve through continuous-batching step loops
(``serving.scheduler.EngineLoop``): router workers submit into a shared
per-engine loop and block on per-request futures, so concurrent requests
interleave inside one decode batch instead of serializing whole generations
on the engine lock (``--serialized`` restores the lock-holding ``generate``
path as a baseline). ``--prewarm`` compiles every prefill bucket at startup
so the first request of each shape pays a warm dispatch instead of an XLA
compile — and, because the placer reads warm-up state (compile_events /
total_buckets, weighted by the measured compile-cost EMA) through each
backend's ``stats_fn``, a prewarmed tier attracts traffic while a cold one
is still compiling.

``serve(args)`` raises ``ServeFailed`` — and the launcher exits non-zero —
when any request failed, any tier raised (the router would otherwise hide
the error by spilling the request to the elastic tier), or any request
returned fewer tokens than asked for.

Observability: ``--trace-out trace.json`` records every request's lifecycle
(placement inputs, queue wait, execution, hedges, per-token decode stamps)
and writes Chrome trace-event JSON — load it in Perfetto / chrome://tracing;
one process per request, one thread per lane. ``--metrics-interval S``
starts a ``MonitorSampler`` sweeping every tier's ``capacity_now`` probe
into per-tier time series at that period; ``--metrics-out metrics.prom``
dumps the run's metrics registry (request counters, queue-wait / TTFT /
inter-token histograms, sampled tier gauges) in Prometheus text format at
exit. All of it is off (and costs nothing) unless the flags are given.
"""
from __future__ import annotations

import argparse
import threading
import time
from dataclasses import dataclass
from typing import Dict, List

SEQ_LEN = 1024                 # engine row width in tokens
PAGE = 16                      # KV page size in tokens
PROMPT_TOKENS = (16, 512)      # seeded prompt lengths, inclusive
TIER_SLOTS = {"interactive": 1, "batch": 8, "elastic": 4}
REQUEST_TIMEOUT_S = 600.0      # covers the elastic tier's cold compile


class ServeFailed(RuntimeError):
    """A served request failed, a tier raised, or an output fell short."""


@dataclass
class ServeResult:
    metrics: object                   # core.telemetry.Metrics of the router
    prompts: List[List[int]]          # by request id
    outputs: Dict[int, List[int]]     # generated tokens of each completed request
    engines: Dict[str, object]        # tier name -> PagedInferenceEngine


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true",
                    help="serve the tiny smoke configuration instead of the published widths")
    ap.add_argument("--use-pallas", action="store_true",
                    help="run the Pallas kernels (compiled on a TPU, interpreted on the CPU)")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--F", type=float, default=10.0, help="frequency threshold")
    ap.add_argument("--D", type=float, default=4096.0, help="data-size threshold (bytes)")
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--weights-int8", action="store_true")
    ap.add_argument("--hedge-after", type=float, default=None)
    ap.add_argument("--workers", type=int, default=0,
                    help="worker threads per tier (0 = serial poll loop)")
    ap.add_argument("--prewarm", action="store_true",
                    help="compile all prefill buckets before accepting traffic")
    ap.add_argument("--serialized", action="store_true",
                    help="bypass the engine step loops (lock-holding generate baseline)")
    ap.add_argument("--chunk-tokens", type=int, default=32,
                    help="chunked prefill chunk size in tokens (0 = whole-prompt "
                         "prefill; MoE archs: expert capacity competes per CHUNK, "
                         "so greedy outputs can differ from whole-prompt prefill "
                         "when capacity binds — use 0 for exact parity there)")
    ap.add_argument("--step-budget", type=int, default=0,
                    help="per-step prefill+decode token budget (0 = auto)")
    ap.add_argument("--trace-out", default=None,
                    help="write per-request Chrome trace-event JSON here "
                         "(Perfetto-loadable); omit to disable tracing")
    ap.add_argument("--metrics-interval", type=float, default=0.0,
                    help="MonitorSampler period in seconds (0 = off): sweeps "
                         "every tier's capacity_now probe into time series")
    ap.add_argument("--metrics-out", default=None,
                    help="write the metrics registry as Prometheus text here")
    return ap


def serve(args: argparse.Namespace) -> ServeResult:
    """Build the tiers, send ``args.requests`` seeded requests through the
    StraightLine router, and return the router metrics with every request's
    prompt and output. Raises ``ServeFailed`` after a failed request, a tier
    error or a short output."""
    import numpy as np

    from repro.configs.registry import get_config
    from repro.core import (
        CapacityGauge,
        MonitorSampler,
        Request,
        StraightLinePolicy,
        Thresholds,
        Tier,
        Tracer,
    )
    from repro.core.router import Backend, RequestFailed, StraightLineRouter
    from repro.core.telemetry import MetricsRegistry
    from repro.models.quant import quantize_params
    from repro.serving.engine import PagedEngineConfig, PagedInferenceEngine
    from repro.serving.scheduler import EngineLoop

    cfg = get_config(args.arch, smoke=args.smoke).replace(use_pallas=args.use_pallas)
    if args.smoke:
        cfg = cfg.replace(attn_chunk=64)
    if PROMPT_TOKENS[1] + args.max_new_tokens > SEQ_LEN:
        raise ValueError(f"--max-new-tokens {args.max_new_tokens} does not fit a "
                         f"{PROMPT_TOKENS[1]}-token prompt in {SEQ_LEN}-token rows")

    def make_engine(tier: str, params=None):
        slots = TIER_SLOTS[tier]
        return PagedInferenceEngine(cfg, PagedEngineConfig(
            page_size=PAGE, num_pages=1 + slots * SEQ_LEN // PAGE, max_slots=slots,
            max_seq_len=SEQ_LEN, max_new_tokens=args.max_new_tokens,
            chunk_tokens=args.chunk_tokens, step_token_budget=args.step_budget,
        ), params=params)

    t0 = time.time()
    interactive = make_engine("interactive")
    params = interactive.params
    if args.weights_int8:
        cfg = cfg.replace(weights_int8=True)
        params = quantize_params(params)
        interactive = make_engine("interactive", params)
    batch_tier = make_engine("batch", params)
    engines = {"interactive": interactive, "batch": batch_tier}
    print(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads}/{cfg.n_kv_heads} heads, vocab {cfg.vocab_size}, "
          f"use_pallas={cfg.use_pallas}, weights_int8={cfg.weights_int8}; "
          f"tiers ready in {time.time()-t0:.1f}s")

    if args.prewarm:
        t = time.time()
        for name, eng in engines.items():
            warmed = eng.prewarm()
            snap = eng.capacity_now()
            print(
                f"  prewarmed {name}: buckets {warmed} "
                f"({snap['compile_events']}/{snap['total_buckets']} shapes warm)"
            )
        print(f"  prewarm took {time.time()-t:.1f}s")

    registry = MetricsRegistry()
    tracer = Tracer() if args.trace_out else None
    gauge = CapacityGauge()
    sampler = None
    if args.metrics_interval > 0:
        sampler = MonitorSampler(gauge, interval_s=args.metrics_interval, registry=registry)

    rng = np.random.default_rng(0)
    lengths = rng.integers(PROMPT_TOKENS[0], PROMPT_TOKENS[1] + 1, args.requests)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist() for n in lengths]
    sizes = rng.choice([512.0, 16384.0], p=[0.8, 0.2], size=args.requests)

    tier_errors: List[str] = []

    def recorded(fn):
        """Note every exception a tier raises before the router sees it."""
        def call(*a):
            try:
                return fn(*a)
            except Exception as e:
                tier_errors.append(f"{type(e).__name__}: {e}")
                raise
        return call

    elastic: list = []
    elastic_lock = threading.Lock()

    def run_on(engine):
        def run(req):
            return engine.generate([prompts[req.rid]])[0].out
        return run

    def elastic_run(req):
        with elastic_lock:             # one cold start even under concurrency
            if not elastic:
                t = time.time()
                eng = make_engine("elastic", params)
                engines["elastic"] = eng
                elastic.append(
                    eng if args.serialized
                    else EngineLoop(eng, name="elastic", registry=registry).start()
                )
                gauge.register_stats("elastic", elastic[0].capacity_now)
                print(f"  [elastic cold start {time.time()-t:.1f}s]")
        if args.serialized:
            return run_on(elastic[0])(req)
        loop = elastic[0]
        return loop.wait(loop.submit(prompts[req.rid], trace=req.trace), req.timeout_s).out

    loops: list = []

    def engine_backend(tier, engine, queue_cap):
        """Continuous-batching backend: workers submit into the engine's
        shared step loop and block on futures (capacity = max_slots so the
        pool keeps the decode batch fed); --serialized keeps the
        lock-holding generate path."""
        name = tier.name.lower()
        capacity = engine.pcfg.max_slots
        if args.serialized:
            gauge.register_stats(name, engine.capacity_now)
            return Backend(tier, recorded(run_on(engine)), capacity=capacity,
                           queue_cap=queue_cap, stats_fn=engine.capacity_now)
        loop = EngineLoop(engine, name=name, registry=registry).start()
        loops.append(loop)
        gauge.register_stats(name, loop.capacity_now)
        return Backend(
            tier, recorded(run_on(engine)), capacity=capacity, queue_cap=queue_cap,
            stats_fn=loop.capacity_now,
            submit_fn=lambda req: loop.submit(prompts[req.rid], trace=req.trace),
            wait_fn=recorded(lambda sid, timeout: loop.wait(sid, timeout).out),
        )

    router = StraightLineRouter(
        {
            Tier.FLASK: engine_backend(Tier.FLASK, interactive, 8),
            Tier.DOCKER: engine_backend(Tier.DOCKER, batch_tier, 64),
            Tier.SERVERLESS: Backend(Tier.SERVERLESS, recorded(elastic_run), capacity=16),
        },
        policy=StraightLinePolicy(Thresholds(F=args.F, D=args.D)),
        window_s=10.0,
        hedge_after_s=args.hedge_after,
        tracer=tracer,
        registry=registry,
    )
    t0 = time.time()
    try:
        if sampler is not None:
            sampler.start()
        if args.workers > 0:
            router.start(args.workers)
        for i in range(args.requests):
            router.submit(Request(rid=i, arrival_t=0.0, data_size=float(sizes[i]),
                                  timeout_s=REQUEST_TIMEOUT_S))
        router.drain()
    finally:
        wall = time.time() - t0
        router.stop()
        for lp in loops + [e for e in elastic if isinstance(e, EngineLoop)]:
            lp.stop()
        if sampler is not None:
            sampler.stop()
    if sampler is not None:
        covered = {t: len(sampler.series(t)) for t in sampler.tiers()}
        print(f"monitor: {sampler.samples_taken} samples across tiers {covered}")
    if tracer is not None:
        tracer.export_chrome(args.trace_out)
        print(f"wrote {len(tracer)} traces to {args.trace_out} "
              f"(open in Perfetto / chrome://tracing)")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            f.write(registry.prometheus_text())
        print(f"wrote metrics registry to {args.metrics_out}")

    m = router.metrics
    outputs: Dict[int, List[int]] = {}
    failures: Dict[int, str] = {}
    for rid in range(args.requests):
        try:
            outputs[rid] = list(router.result(rid, timeout=0))
        except RequestFailed as e:
            failures[rid] = e.reason
    by_tier = {t.name: sum(1 for r in m.completed if r.tier == t) for t in Tier}
    mode = f"{args.workers} workers/tier" if args.workers > 0 else "serial poll loop"
    batching = "serialized generate" if args.serialized else "continuous-batching loops"
    prefill = f"chunked prefill ({args.chunk_tokens} tok)" if args.chunk_tokens else "whole-prompt prefill"
    print(f"{args.requests} requests in {wall:.1f}s ({mode}, {batching}, {prefill}): {m.summary()}")
    print(f"placement: {by_tier}")

    short = {rid: len(out) for rid, out in outputs.items() if len(out) < args.max_new_tokens}
    if failures or tier_errors or short:
        raise ServeFailed(
            f"{len(failures)} failed {failures}, {len(tier_errors)} tier errors "
            f"{tier_errors[:3]}, {len(short)} short outputs {short}"
        )
    return ServeResult(metrics=m, prompts=prompts, outputs=outputs, engines=engines)


def main(argv=None) -> None:
    from repro.jax_cache import enable_compile_cache

    args = build_parser().parse_args(argv)
    enable_compile_cache()
    serve(args)


if __name__ == "__main__":
    main()
