"""Observability subsystem tests: lifecycle traces for every request kind,
metrics registry correctness, MonitorSampler consistency under concurrency,
Chrome-trace export, and the read-side thread-safety regressions in
``Metrics`` / ``FrequencyEstimator``.

Router-level trace tests use fake backends (fast, deterministic — the
hedge race reuses the event-controlled idiom from
test_router_concurrency.py); engine-level trace tests drive a real tiny
paged JAX engine so chunk spans / preemption events / token stamps come
from the actual serving path.
"""
import json
import threading
import time

import pytest

from repro.core import Request, StraightLinePolicy, Thresholds, Tier
from repro.core.router import Backend, StraightLineRouter
from repro.core.telemetry import (
    CapacityGauge,
    FrequencyEstimator,
    Histogram,
    Metrics,
    MetricsRegistry,
    MonitorSampler,
    log_buckets,
)
from repro.core.tracing import NULL_TRACER, Trace, Tracer


def _policy():
    # F huge: no burst path; D = 1e6: moderate payloads fall through to S_F/S_D
    return StraightLinePolicy(Thresholds(F=1e9, D=1e6))


def _req(rid=0, size=100.0, timeout=60.0):
    return Request(rid=rid, arrival_t=0.0, data_size=size, timeout_s=timeout)


def _router(backends, tracer, **kw):
    return StraightLineRouter(
        backends, policy=_policy(), tracer=tracer, registry=MetricsRegistry(), **kw
    )


def _tiers(flask=None, docker=None, sls=None, **caps):
    return {
        Tier.FLASK: Backend(Tier.FLASK, flask or (lambda r: "f"),
                            capacity=caps.get("flask_cap", 1),
                            queue_cap=caps.get("flask_q", 8)),
        Tier.DOCKER: Backend(Tier.DOCKER, docker or (lambda r: "d"), capacity=2),
        Tier.SERVERLESS: Backend(Tier.SERVERLESS, sls or (lambda r: "s"), capacity=4),
    }


def assert_well_formed(t: dict) -> None:
    """Every span is a real interval inside the trace, and per lane the
    record order is monotone in start time."""
    assert t["spans"], f"trace {t['rid']} has no spans"
    by_lane = {}
    for s in t["spans"]:
        assert s["t1"] >= s["t0"] >= t["t0"] - 1e-9, (t["rid"], s)
        by_lane.setdefault(s["lane"], []).append(s["t0"])
    for lane, starts in by_lane.items():
        assert starts == sorted(starts), f"lane {lane} spans out of order"
    for e in t["events"]:
        assert e["t"] >= t["t0"] - 1e-9, (t["rid"], e)
    names = [s["name"] for s in t["spans"]]
    assert "serve.router.place" in names
    p = next(s for s in t["spans"] if s["name"] == "serve.router.place")
    assert {"f_t", "flask_free", "docker_free", "tier", "reason"} <= set(p["attrs"])


# ---------------------------------------------------------------------------
# Router lifecycle traces: one test per request kind
# ---------------------------------------------------------------------------


def test_completed_request_trace():
    tracer = Tracer()
    router = _router(_tiers(), tracer)
    with router:
        router.submit(_req(1))
        router.drain(timeout=10)
    [t] = tracer.traces()
    assert_well_formed(t)
    assert t["rid"] == 1 and not t["attrs"]["failed"]
    assert t["attrs"]["tier"] == "FLASK" and t["attrs"]["response_s"] > 0
    names = [s["name"] for s in t["spans"]]
    assert names.count("queue_wait") == 1 and names.count("execute") == 1
    ex = next(s for s in t["spans"] if s["name"] == "execute")
    assert ex["lane"] == "flask" and ex["attrs"]["outcome"] == "ok"
    assert any(e["name"] == "enqueued" for e in t["events"])


def test_failed_request_trace():
    def boom(req):
        raise RuntimeError("down")

    tracer = Tracer()
    # no retry tier to spill to: the error is terminal
    router = _router(_tiers(flask=boom), tracer, retry_on_failure=False)
    with router:
        router.submit(_req(2))
        router.drain(timeout=10)
    [t] = tracer.traces()
    assert_well_formed(t)
    assert t["attrs"]["failed"] and t["attrs"]["fail_reason"] == "error:RuntimeError"
    ex = next(s for s in t["spans"] if s["name"] == "execute")
    assert ex["attrs"]["outcome"] == "error:RuntimeError"
    assert any(e["name"] == "failed" for e in t["events"])


def test_retry_spill_trace_records_both_lanes():
    def flaky(req):
        raise RuntimeError("flake")

    tracer = Tracer()
    router = _router(_tiers(flask=flaky), tracer)
    with router:
        router.submit(_req(3))
        router.drain(timeout=10)
    [t] = tracer.traces()
    assert_well_formed(t)
    assert not t["attrs"]["failed"] and t["attrs"]["tier"] == "SERVERLESS"
    assert any(e["name"] == "retry_spill" for e in t["events"])
    lanes = {s["lane"] for s in t["spans"] if s["name"] == "execute"}
    assert lanes == {"flask", "serverless-retry"}


def test_deflected_request_trace():
    tracer = Tracer()
    tiers = _tiers(flask_q=0)            # flask chosen but cannot even queue
    router = _router(tiers, tracer)
    with router:
        assert router.submit(_req(4)) == Tier.SERVERLESS
        router.drain(timeout=10)
    [t] = tracer.traces()
    assert_well_formed(t)
    d = next(e for e in t["events"] if e["name"] == "deflected")
    assert d["attrs"] == {"from_tier": "FLASK", "to_tier": "SERVERLESS"}
    assert next(s for s in t["spans"]
                if s["name"] == "serve.router.place")["attrs"]["tier"] == "FLASK"
    assert t["attrs"]["tier"] == "SERVERLESS" and not t["attrs"]["failed"]


def test_timed_out_request_trace():
    release = threading.Event()

    def slow(req):
        assert release.wait(30)
        return "f"

    tracer = Tracer()
    router = _router(_tiers(flask=slow), tracer, retry_on_failure=False)
    with router:
        router.submit(_req(5, timeout=5.0))      # occupies the 1 flask worker
        router.submit(_req(6, timeout=0.01))     # queued behind it, expires there
        time.sleep(0.1)
        release.set()
        router.drain(timeout=10)
    t = next(t for t in tracer.traces() if t["rid"] == 6)
    assert_well_formed(t)
    assert t["attrs"]["failed"] and t["attrs"]["fail_reason"] == "timeout-in-queue"
    assert [s["name"] for s in t["spans"] if s["lane"] == "flask"] == ["queue_wait"]


@pytest.mark.parametrize("winner", ["original", "hedge"])
def test_hedged_request_trace_parallel_lanes(winner):
    """Both racing copies record spans on their own lanes in ONE trace, the
    trace finishes exactly once, and the summary reflects the winner."""
    release_flask, release_sls, sls_started = (threading.Event() for _ in range(3))

    def flask_run(req):
        assert release_flask.wait(30)
        return "flask-result"

    def sls_run(req):
        sls_started.set()
        assert release_sls.wait(30)
        return "sls-result"

    tracer = Tracer()
    router = _router(_tiers(flask=flask_run, sls=sls_run), tracer, hedge_after_s=0.01)
    with router:
        router.submit(_req(7))
        assert sls_started.wait(10)
        first, second = (
            (release_flask, release_sls) if winner == "original"
            else (release_sls, release_flask)
        )
        first.set()
        router.result(7, timeout=10)
        second.set()
        router.drain(timeout=10)
        time.sleep(0.1)                  # let the loser's worker record its span
    assert len(tracer) == 1, "hedged request must finish its trace exactly once"
    [t] = tracer.traces()
    assert_well_formed(t)
    assert any(e["name"] == "hedge_fired" for e in t["events"])
    lanes = {s["lane"] for s in t["spans"] if s["name"] == "execute"}
    assert lanes == {"flask", "serverless-hedge"}, "copies must race on parallel lanes"
    expect = "FLASK" if winner == "original" else "SERVERLESS"
    assert t["attrs"]["tier"] == expect and t["attrs"]["hedged"]


def test_tracer_disabled_is_zero_cost_and_ring_bounded():
    assert NULL_TRACER.begin(1) is None
    assert Tracer(enabled=False).begin(1, a=2) is None
    NULL_TRACER.finish(None)             # no-op, no error
    tracer = Tracer(capacity=3)
    for i in range(7):
        tracer.finish(tracer.begin(i))
    assert len(tracer) == 3
    assert [t["rid"] for t in tracer.traces()] == [4, 5, 6]   # oldest evicted
    # finish is exactly-once even when called twice with the same trace
    t = tracer.begin(99)
    tracer.finish(t)
    tracer.finish(t)
    assert [x["rid"] for x in tracer.traces()].count(99) == 1


def test_untraced_router_records_no_trace_but_metrics_still_flow():
    reg = MetricsRegistry()
    router = StraightLineRouter(_tiers(), policy=_policy(), registry=reg)
    with router:
        router.submit(_req(8))
        router.drain(timeout=10)
    assert router.metrics.total == 1
    assert reg.counter("router_requests_total", {"tier": "flask"}).value == 1
    h = reg.histogram("router_queue_wait_seconds", {"tier": "flask"})
    assert h.total == 1                  # metrics are independent of tracing


# ---------------------------------------------------------------------------
# Engine-side traces: chunk spans, preemption, per-token stamps (real JAX)
# ---------------------------------------------------------------------------

MAXLEN, PS, CHUNK, NEW = 48, 8, 16, 4


@pytest.fixture(scope="module")
def smoke_cfg():
    from repro.configs.registry import get_config

    return get_config("smollm-360m", smoke=True).replace(attn_chunk=32)


def test_engine_loop_trace_chunks_tokens_and_latency_histograms(smoke_cfg):
    from repro.serving.engine import PagedEngineConfig, PagedInferenceEngine
    from repro.serving.scheduler import EngineLoop

    eng = PagedInferenceEngine(
        smoke_cfg,
        PagedEngineConfig(page_size=PS, num_pages=1 + 2 * MAXLEN // PS, max_slots=2,
                          max_seq_len=MAXLEN, max_new_tokens=NEW, chunk_tokens=CHUNK),
    )
    reg = MetricsRegistry()
    tracer = Tracer()
    trace = tracer.begin(0, model="smollm")
    prompt = list(range(1, 2 * CHUNK + 2))           # 33 tokens -> 3 chunks
    with EngineLoop(eng, name="t0", registry=reg) as loop:
        seq = loop.wait(loop.submit(prompt, trace=trace), timeout=120)
    tracer.finish(trace)
    [t] = tracer.traces()
    lane = f"engine-sid{seq.sid}"
    chunks = [s for s in t["spans"] if s["name"] == "serve.engine.prefill"]
    assert len(chunks) == 3 and all(s["lane"] == lane for s in chunks)
    assert [c["attrs"]["offset"] for c in chunks] == [0, CHUNK, 2 * CHUNK]
    ev = {e["name"] for e in t["events"]}
    assert {"engine_submit", "admitted", "resolved"} <= ev
    # one stamp per emitted token, strictly after the submit stamp, ordered
    times = t["tokens"][lane]
    assert len(times) == len(seq.out) == NEW
    assert times == sorted(times) and times[0] >= seq.submit_t
    # the loop fed the latency histograms, traced or not
    assert reg.histogram("ttft_seconds", {"engine": "t0"}).total == 1
    assert reg.histogram("itl_seconds", {"engine": "t0"}).total == NEW - 1
    assert t["attrs"]["model"] == "smollm"


def test_preemption_resume_trace_events(smoke_cfg):
    from repro.serving.engine import PagedEngineConfig, PagedInferenceEngine

    # 4 usable pages, two sequences that each grow to 3 pages: the newest
    # gets preempted, resumes (recompute) after the first finishes
    eng = PagedInferenceEngine(
        smoke_cfg,
        PagedEngineConfig(page_size=16, num_pages=5, max_slots=2,
                          max_seq_len=64, max_new_tokens=32),
    )
    tracer = Tracer()
    traces = [tracer.begin(i) for i in range(2)]
    for i, tr in enumerate(traces):
        eng.submit([1 + i] * 5, trace=tr)
    for _ in range(200):
        eng.step()
        if all(s is None for s in eng.slot_seq) and not eng.waiting:
            break
    assert eng.preemptions >= 1
    for tr in traces:
        tracer.finish(tr)
    dicts = tracer.traces()
    preempted = [t for t in dicts
                 if any(e["name"] == "preempted" for e in t["events"])]
    assert preempted, "tight page pool produced no preemption event"
    t = preempted[0]
    resumes = [s for s in t["spans"]
               if s["name"] == "serve.engine.prefill" and s["attrs"].get("resume", 0) >= 1]
    assert resumes, "no resume re-prefill span after preemption"
    ev = next(e for e in t["events"] if e["name"] == "preempted")
    assert ev["attrs"]["preemptions"] >= 1 and ev["attrs"]["n_out"] >= 1


# ---------------------------------------------------------------------------
# Metrics registry: histogram merge, exposition, snapshot
# ---------------------------------------------------------------------------


def test_histogram_merge_correctness():
    a, b = Histogram(), Histogram()
    xs_a = [1e-4, 3e-3, 0.5, 7.0]
    xs_b = [2e-3, 2e-3, 1e9]            # 1e9 overflows into +Inf
    for x in xs_a:
        a.observe(x)
    for x in xs_b:
        b.observe(x)
    merged = Histogram().merge(a).merge(b)
    assert merged.total == len(xs_a) + len(xs_b)
    assert merged.sum == pytest.approx(sum(xs_a) + sum(xs_b))
    assert merged.counts == [x + y for x, y in zip(a.counts, b.counts)]
    assert merged.counts[-1] == 1       # the 1e9 overflow
    assert a.total == len(xs_a)         # merge does not mutate sources
    with pytest.raises(ValueError):
        a.merge(Histogram(bounds=(1.0, 2.0)))


def test_histogram_percentile_and_bounds_semantics():
    h = Histogram(bounds=(0.01, 0.1, 1.0))
    for x in (0.005, 0.05, 0.05, 0.5):
        h.observe(x)
    assert h.percentile(25) == 0.01
    assert h.percentile(75) == 0.1
    assert h.percentile(100) == 1.0
    assert Histogram().percentile(50) != Histogram().percentile(50)   # NaN


def test_registry_prometheus_text_and_merged_view():
    reg = MetricsRegistry()
    reg.counter("reqs_total", {"tier": "flask"}).inc(3)
    reg.gauge("occ", {"tier": "flask"}).set(0.5)
    for tier, v in (("flask", 0.001), ("docker", 0.03), ("docker", 0.3)):
        reg.histogram("lat_seconds", {"tier": tier}).observe(v)
    text = reg.prometheus_text()
    assert '# TYPE reqs_total counter' in text
    assert 'reqs_total{tier="flask"} 3' in text
    assert 'occ{tier="flask"} 0.5' in text
    # cumulative buckets: counts along le= must be non-decreasing, and the
    # +Inf bucket equals _count
    rows = [l for l in text.splitlines() if l.startswith('lat_seconds_bucket{tier="docker"')]
    counts = [int(l.rsplit(" ", 1)[1]) for l in rows]
    assert counts == sorted(counts) and counts[-1] == 2
    assert 'lat_seconds_count{tier="docker"} 2' in text
    merged = reg.merged_histogram("lat_seconds")
    assert merged.total == 3 and reg.merged_histogram("nope") is None
    # same instance comes back for the same (name, labels)
    assert reg.counter("reqs_total", {"tier": "flask"}).value == 3


# ---------------------------------------------------------------------------
# MonitorSampler: time series + windows under concurrent sampling
# ---------------------------------------------------------------------------


def _stats_probe(state):
    def probe():
        return {
            "free_slots": state["free"], "num_slots": 4, "free_pages": state["free"] * 2,
            "waiting": state["q"], "prefill_backlog_tokens": 7,
            "compile_events": 1, "total_buckets": 2,
        }
    return probe


def test_sampler_series_and_prometheus_gauges():
    gauge = CapacityGauge()
    state = {"free": 1, "q": 3}
    gauge.register_stats("docker", _stats_probe(state))
    reg = MetricsRegistry()
    clock_t = [0.0]
    s = MonitorSampler(gauge, interval_s=1.0, registry=reg, clock=lambda: clock_t[0])
    for i in range(5):
        clock_t[0] = float(i)
        s.sample_once()
    assert s.tiers() == ["docker"] and len(s.series("docker")) == 5
    latest = s.latest("docker")
    assert latest == {
        "t": 4.0, "occupancy": 0.75, "free_pages": 2, "free_slots": 1,
        "queue_depth": 3, "prefill_backlog": 7, "warmth": 0.5,
        # no prefix cache or quantized pool on this probe: keys sampled as
        # unknown, exported as no gauge at all (None values never reach the
        # registry)
        "cached_pages": None, "prefix_hit_rate": None,
        "kv_bytes_per_token": None, "kv_cache_dtype": None,
    }
    assert [x["t"] for x in s.window("docker", last_s=2.0)] == [2.0, 3.0, 4.0]
    assert reg.gauge("tier_occupancy", {"tier": "docker"}).value == 0.75
    assert reg.gauge("tier_queue_depth", {"tier": "docker"}).value == 3.0


def test_sampler_concurrent_reads_and_flapping_probe():
    gauge = CapacityGauge()
    state = {"free": 2, "q": 0}
    gauge.register_stats("flask", _stats_probe(state))
    calls = [0]

    def flapping():
        calls[0] += 1
        if calls[0] % 2:
            raise RuntimeError("probe down")
        return {"free_slots": 1, "num_slots": 2}

    gauge.register_stats("elastic", flapping)
    s = MonitorSampler(gauge, interval_s=0.001, capacity=256)
    errors = []

    def reader():
        try:
            for _ in range(300):
                for tier in s.tiers():
                    win = s.window(tier, last_s=0.05)
                    assert all(w["t"] <= s.clock() for w in win)
                    _ = s.series(tier), s.latest(tier)
                state["free"] = (state["free"] + 1) % 4     # mutate under sampling
        except Exception as e:                               # pragma: no cover
            errors.append(e)

    with s:                              # context manager starts/stops the thread
        threads = [threading.Thread(target=reader) for _ in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert not errors
    assert not s.running and s.samples_taken >= len(s.series("flask"))
    assert "flask" in s.tiers()          # flapping elastic never killed the sweep
    assert len(s.series("flask")) <= 256


# ---------------------------------------------------------------------------
# Chrome trace-event export
# ---------------------------------------------------------------------------


def test_chrome_trace_roundtrips_and_names_lanes(tmp_path):
    tracer = Tracer()
    t = tracer.begin(42, model="m")
    t.add_span("serve.router.place", 1.0, 1.1)
    t.add_span("execute", 1.2, 2.0, lane="flask", outcome="ok")
    t.add_span("execute", 1.5, 1.9, lane="serverless-hedge", outcome="ok")
    t.event("hedge_fired", t=1.45)
    t.add_tokens("engine-sid3", [1.3, 1.4, 1.6])
    tracer.finish(t, tier="FLASK")
    path = tmp_path / "trace.json"
    tracer.export_chrome(str(path))
    with open(path) as f:
        doc = json.load(f)               # json.loads round-trip
    evs = doc["traceEvents"]
    thread_names = {e["args"]["name"] for e in evs
                    if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"router", "flask", "serverless-hedge", "engine-sid3"} <= thread_names
    assert {e["args"]["name"] for e in evs if e["ph"] == "M" and e["name"] == "process_name"} \
        == {"request 42"}
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(xs) == 3 and all(e["dur"] >= 0 and e["pid"] == 42 for e in xs)
    toks = [e for e in evs if e["ph"] == "i" and e["name"] == "token"]
    assert len(toks) == 3 and toks[0]["ts"] == pytest.approx(1.3e6)
    # lanes map to distinct tids within the request's process
    assert len({e["tid"] for e in evs if e["ph"] != "M"}) == 4


# ---------------------------------------------------------------------------
# Read-side thread-safety regressions (satellites 1 and 2)
# ---------------------------------------------------------------------------


def _done_req(rid, failed=False):
    r = _req(rid)
    r.tier = Tier.FLASK
    r.finish_t = 0.5
    r.failed = failed
    return r


def test_metrics_reads_safe_under_concurrent_record():
    m = Metrics()
    stop = threading.Event()
    errors = []

    def writer():
        i = 0
        while not stop.is_set():
            m.record(_done_req(i, failed=(i % 5 == 0)))
            i += 1

    def reader():
        try:
            while not stop.is_set():
                m.response_times()
                m.summary()
                _ = m.total, m.failure_rate
        except Exception as e:
            errors.append(e)

    threads = [threading.Thread(target=writer) for _ in range(2)] + [
        threading.Thread(target=reader) for _ in range(4)
    ]
    for t in threads:
        t.start()
    time.sleep(0.3)
    stop.set()
    for t in threads:
        t.join()
    assert not errors, errors
    assert m.total == len(m.completed) + len(m.failed)
    s = m.summary()
    assert 0.0 <= s["failure_rate"] <= 1.0


def test_frequency_estimator_safe_under_concurrent_observe_and_read():
    est = FrequencyEstimator(window_s=0.05)
    stop = threading.Event()
    errors = []

    def observer():
        while not stop.is_set():
            est.observe(time.monotonic())

    def reader():
        try:
            while not stop.is_set():
                f = est.frequency(time.monotonic())
                assert f >= 0.0
        except Exception as e:
            errors.append(e)

    threads = [threading.Thread(target=observer) for _ in range(2)] + [
        threading.Thread(target=reader) for _ in range(4)
    ]
    for t in threads:
        t.start()
    time.sleep(0.3)
    stop.set()
    for t in threads:
        t.join()
    assert not errors, errors
    assert est.frequency(time.monotonic()) >= 0.0
