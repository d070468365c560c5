"""End-to-end driver (the paper's kind: serving): batched requests through
the StraightLine router onto three REAL JAX inference backends — with the
placer consuming LIVE capacity from the paged serving engines, every
engine tier fronted by a continuous-batching step loop, and the full
observability stack on: per-request lifecycle traces, the process metrics
registry, and a MonitorSampler time series per tier.

Tiers (DESIGN.md §2):
  interactive — 1-slot paged engine, lowest latency, tiny page pool
  batch       — 8-slot paged engine over a shared KV page pool
  elastic     — engines spun up on demand (cold start = init + weight load)

Each engine is owned by a ``serving.scheduler.EngineLoop``: router workers
submit into the shared step loop and block on per-request futures, so
concurrent requests on one engine interleave inside a single decode batch
(instead of serializing whole generations on the engine lock). Prefill is
CHUNKED (``chunk_tokens=CHUNK``): a long prompt is absorbed a page-multiple
chunk per step under the engines' token budget, so it cannot stall the
interactive tier's decode batch for a whole prefill. Algorithm 1's
S_F/S_D availability checks pull through a CapacityGauge fed by each
engine's ``admission_capacity()`` (free slots bounded by free KV pages), and
the loop's ``capacity_now()`` additionally exports batch occupancy + queue
depth so telemetry sees true interleaved utilization.

Observability (this run asserts all three outputs):
  * every request carries a Trace from submit to settle — hedged copies
    (``hedge_after_s``) share one trace and race on separate lanes; the
    Chrome trace-event export lands at ``$TRACE_OUT`` (Perfetto-loadable);
  * the metrics registry (placement counters, queue-wait / TTFT /
    inter-token histograms) dumps Prometheus text at ``$METRICS_OUT``;
  * a MonitorSampler sweeps each tier's ``capacity_now`` probe into
    per-tier time series while the burst runs.

    PYTHONPATH=src python examples/serve_hybrid.py
"""
import json
import os
import threading
import time

import numpy as np

from repro.core import (
    CapacityGauge,
    MonitorSampler,
    Request,
    StraightLinePolicy,
    Thresholds,
    Tier,
    Tracer,
    default_registry,
)
from repro.configs.registry import get_config
from repro.core.router import Backend, StraightLineRouter
from repro.jax_cache import enable_compile_cache
from repro.serving.engine import PagedEngineConfig, PagedInferenceEngine
from repro.serving.scheduler import EngineLoop

enable_compile_cache()
CFG = get_config("smollm-360m", smoke=True).replace(attn_chunk=64)
MAXLEN, NEW, PROMPT = 96, 8, 8
PS = 16
CHUNK = 32                    # chunked prefill: tokens absorbed per step
TRACE_OUT = os.environ.get("TRACE_OUT", "/tmp/serve_hybrid_trace.json")
METRICS_OUT = os.environ.get("METRICS_OUT", "/tmp/serve_hybrid_metrics.prom")

t0 = time.time()
interactive = PagedInferenceEngine(
    CFG, PagedEngineConfig(page_size=PS, num_pages=1 + MAXLEN // PS, max_slots=1,
                           max_seq_len=MAXLEN, max_new_tokens=NEW, chunk_tokens=CHUNK)
)
batch_tier = PagedInferenceEngine(
    CFG, PagedEngineConfig(page_size=PS, num_pages=1 + 4 * MAXLEN // PS, max_slots=8,
                           max_seq_len=MAXLEN, max_new_tokens=NEW, chunk_tokens=CHUNK),
    params=interactive.params,
)
print(f"tiers ready in {time.time()-t0:.1f}s")

# pre-warm: compile every prefill bucket before traffic arrives, so no
# request pays an XLA compile and the placer sees fully-warm tiers
for eng in (interactive, batch_tier):
    eng.prewarm()
print(f"batch tier: {batch_tier.capacity_now()}")

# one continuous-batching step loop per engine: all device stepping happens
# on the loop thread; submitters (router workers) only enqueue + wait
registry = default_registry()
interactive_loop = EngineLoop(interactive, name="flask").start()
batch_loop = EngineLoop(batch_tier, name="docker").start()

# live capacity feedback: the placer sees each engine's measured admission
# capacity (slots bounded by free pages), not a hardcoded constant — plus
# warm-up state and batch occupancy through the loops' stats probes
gauge = CapacityGauge()
gauge.register("flask", lambda: interactive.admission_capacity(PROMPT + NEW))
gauge.register("docker", lambda: batch_tier.admission_capacity(PROMPT + NEW))
gauge.register_stats("flask", interactive_loop.capacity_now)
gauge.register_stats("docker", batch_loop.capacity_now)

tracer = Tracer()
sampler = MonitorSampler(gauge, interval_s=0.02, registry=registry).start()

elastic_pool = []
elastic_lock = threading.Lock()


def prompt_for(req: Request):
    return list(np.random.default_rng(req.rid).integers(1, CFG.vocab_size, PROMPT))


def elastic_run(req: Request):
    # cold start: spin up a fresh engine + step loop (weights init = load
    # analogue); concurrent elastic requests then batch on it too
    with elastic_lock:               # one cold start even under concurrency
        if not elastic_pool:
            t = time.time()
            eng = PagedInferenceEngine(
                CFG, PagedEngineConfig(page_size=PS, num_pages=1 + 2 * MAXLEN // PS,
                                       max_slots=4, max_seq_len=MAXLEN, max_new_tokens=NEW,
                                       chunk_tokens=CHUNK),
                params=interactive.params,
            )
            elastic_pool.append(EngineLoop(eng, name="elastic").start())
            gauge.register_stats("elastic", elastic_pool[0].capacity_now)
            print(f"  [elastic cold start: {time.time()-t:.1f}s]")
    loop = elastic_pool[0]
    return loop.wait(loop.submit(prompt_for(req), trace=req.trace), req.timeout_s).out


def loop_backend(tier, loop, capacity, queue_cap):
    return Backend(
        tier,
        run=lambda req: loop.wait(loop.submit(prompt_for(req)), req.timeout_s).out,
        capacity=capacity, queue_cap=queue_cap,
        capacity_fn=lambda: gauge.free("flask" if tier == Tier.FLASK else "docker"),
        stats_fn=lambda: gauge.stats("flask" if tier == Tier.FLASK else "docker"),
        submit_fn=lambda req: loop.submit(prompt_for(req), trace=req.trace),
        wait_fn=lambda sid, timeout: loop.wait(sid, timeout).out,
    )


router = StraightLineRouter(
    {
        Tier.FLASK: loop_backend(Tier.FLASK, interactive_loop, 1, 8),
        Tier.DOCKER: loop_backend(Tier.DOCKER, batch_loop, 8, 64),
        Tier.SERVERLESS: Backend(Tier.SERVERLESS, elastic_run, capacity=16),
    },
    policy=StraightLinePolicy(Thresholds(F=10, D=4096)),   # scaled-down thresholds
    window_s=10.0,
    hedge_after_s=0.25,              # straggler mitigation: slow copies race a
    tracer=tracer,                   # duplicate on the elastic tier
    registry=registry,
)

# worker pools keep the decode batches fed; 16 serverless workers leave
# headroom for hedge clones to race while their primaries still run
router.start(16)
rng = np.random.default_rng(0)
N = 24
# a burst: submit everything at once -> f_t crosses F -> elastic absorbs it
for i in range(N):
    size = float(rng.choice([512.0, 16384.0], p=[0.8, 0.2]))   # bimodal payloads
    router.submit(Request(rid=i, arrival_t=0.0, data_size=size, timeout_s=120.0))
router.drain()
router.stop()

m = router.metrics
print(f"\n{N} requests: {m.summary()}")
by_tier = {t.name: sum(1 for r in m.completed if r.tier == t) for t in Tier}
print("placement:", by_tier)
print("live capacity after drain:", gauge.snapshot())
print("batch tier occupancy gauge:", gauge.occupancy("docker"),
      "steps:", batch_loop.steps,
      "prefill backlog:", gauge.prefill_backlog("docker"))
for loop in [interactive_loop, batch_loop] + elastic_pool:
    loop.stop()
sampler.stop()
assert m.total == N and m.failure_rate == 0.0

# --- observability outputs (the three artifacts this example certifies) ----

# (a) lifecycle traces: every request settled exactly one trace; each shows
# Algorithm 1's placement inputs; hedged requests race on parallel lanes
traces = tracer.traces()
assert len(traces) == N, (len(traces), N)
for t in traces:
    names = [s["name"] for s in t["spans"]]
    assert "serve.router.place" in names, t["rid"]
    placement = next(s for s in t["spans"] if s["name"] == "serve.router.place")
    assert {"f_t", "flask_free", "docker_free", "tier"} <= set(placement["attrs"])
hedged = [t for t in traces if any(e["name"] == "hedge_fired" for e in t["events"])]
dual = [t for t in hedged
        if sum(1 for s in t["spans"] if s["name"] == "execute") >= 2
        and any(s["name"] == "queue_wait" for s in t["spans"])
        and any(ts for ts in t["tokens"].values())]
print(f"traces: {len(traces)} total, {len(hedged)} hedged, {len(dual)} dual-execution")
assert hedged, "burst produced no hedged request"
assert dual, "no hedged trace shows both racing executions"
tracer.export_chrome(TRACE_OUT)
with open(TRACE_OUT) as f:
    chrome = json.load(f)                      # round-trips: Perfetto-loadable
assert chrome["traceEvents"], "empty Chrome trace"
print(f"wrote {TRACE_OUT} ({len(chrome['traceEvents'])} events)")

# (b) Prometheus text: latency histograms from the engine loops + router
prom = registry.prometheus_text()
with open(METRICS_OUT, "w") as f:
    f.write(prom)
assert "ttft_seconds_bucket" in prom and "itl_seconds_bucket" in prom, prom[:400]
assert "router_requests_total" in prom and "router_queue_wait_seconds_bucket" in prom
print(f"wrote {METRICS_OUT} ({len(prom.splitlines())} lines)")

# (c) MonitorSampler: a time series exists for every tier that served traffic
live_tiers = {"elastic" if name == "SERVERLESS" else name.lower()
              for name, n in by_tier.items() if n > 0}
live_tiers |= {"flask", "docker"}            # stats probes registered up front
assert live_tiers <= set(sampler.tiers()), (live_tiers, sampler.tiers())
for tier in sorted(sampler.tiers()):
    series = sampler.series(tier)
    occ = [s["occupancy"] for s in series if s["occupancy"] is not None]
    print(f"monitor[{tier}]: {len(series)} samples, peak occupancy "
          f"{max(occ) if occ else 0.0:.2f}")

print("OK — all requests served by real JAX paged engines through Algorithm 1,")
print("     batched by shared step loops with chunked (budgeted) prefill,")
print("     with S_F/S_D read live from page pools — and the whole run is")
print("     observable: traces (Perfetto), Prometheus metrics, tier time series")
