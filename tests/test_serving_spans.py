"""The serving path's own spans (``repro.core.tracing.span``), read back from
the ``.xplane.pb`` of a real ``jax.profiler`` session at smoke size: the
engine step's span tree and its decode counts against the engine's slot
state, the admission stamp across preemption, the engine lock wait, the
loop, router and GC spans, and the request-trace side of the helper with
the profiler on and off."""
import gc
import glob
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core import Request, StraightLinePolicy, Thresholds, Tier
from repro.core.router import Backend, StraightLineRouter
from repro.core.telemetry import MetricsRegistry
from repro.core.tracing import Trace, install_gc_spans, span
from repro.serving.engine import PagedEngineConfig, PagedInferenceEngine
from repro.serving.scheduler import EngineLoop

PS, MAXLEN = 16, 64


@pytest.fixture(scope="module")
def cfg():
    return get_config("smollm-360m", smoke=True).replace(attn_chunk=64)


def _engine(cfg, slots=3, pages=None, new=6, chunk=16, chained=False):
    return PagedInferenceEngine(cfg, PagedEngineConfig(
        page_size=PS, num_pages=pages or 1 + slots * MAXLEN // PS, max_slots=slots,
        max_seq_len=MAXLEN, max_new_tokens=new, chunk_tokens=chunk, chained_tables=chained))


def _prompt(cfg, n, seed):
    return np.random.default_rng(seed).integers(1, cfg.vocab_size, n).tolist()


class Profiled:
    """A profiler session over the block; ``spans()`` reads its host
    plane's ``serve.*`` events (and any named in ``also``) as dicts with
    their parent, the innermost span of the same thread that contains it."""

    def __init__(self, tmp_path):
        self.dir = str(tmp_path / "trace")

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()

    def spans(self, also=()):
        from jax.profiler import ProfileData

        [path] = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)
        out = []
        for plane in ProfileData.from_file(path).planes:
            for k, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name.startswith("serve.") or ev.name in also:
                        out.append({"name": ev.name, "line": (plane.name, k),
                                    "t0": ev.start_ns, "t1": ev.start_ns + ev.duration_ns,
                                    "stats": dict(ev.stats), "parent": None})
        out.sort(key=lambda s: (s["line"], s["t0"], -s["t1"]))
        stack = []
        for s in out:
            while stack and (stack[-1]["line"] != s["line"] or stack[-1]["t1"] < s["t1"]):
                stack.pop()
            s["parent"] = stack[-1] if stack else None
            stack.append(s)
        return sorted(out, key=lambda s: s["t0"])


def _children(spans, parent):
    return [s for s in spans if s["parent"] is parent]


@pytest.mark.parametrize("chained,walked", [(False, MAXLEN // PS), (True, PS)])
def test_engine_step_span_tree_and_decode_counts(cfg, tmp_path, chained, walked):
    """Each ``step()`` is one ``serve.engine.step`` holding its phases; a
    step that decodes holds a ``serve.engine.decode`` whose counts are the
    engine's slot state at the dispatch, then a ``device_wait``. The kernel
    copies the pages below each row's length, every row's, at most the row
    width: the flat table's 4 pages, or one 16-entry page of a chained
    table."""
    eng = _engine(cfg, chained=chained)
    eng.name = "tier-a"
    for i, n in enumerate((5, 20, 40)):
        eng.submit(_prompt(cfg, n, i))
    eng.step()                                     # compiles outside the trace
    seen = []
    decode = eng._decode

    def recording(*args):
        live = [int(eng.slot_len[i]) for i, s in enumerate(eng.slot_seq)
                if s is not None and not eng._chunking[i]]
        seen.append((live, [int(n) for n in eng.slot_len]))
        return decode(*args)

    eng._decode = recording
    with Profiled(tmp_path) as prof:
        for _ in range(4):
            eng.step()
    spans = prof.spans()
    steps = [s for s in spans if s["name"] == "serve.engine.step"]
    assert len(steps) == 4
    assert all(s["stats"]["engine"] == "tier-a" and s["parent"] is None for s in steps)
    decodes = [s for s in spans if s["name"] == "serve.engine.decode"]
    assert len(decodes) == len(seen) >= 3
    for st in steps:
        names = [c["name"] for c in _children(spans, st)]
        assert names[:2] == ["serve.engine.grow", "serve.engine.admit"]
        if "serve.engine.decode" in names:
            i = names.index("serve.engine.decode")
            assert names[i + 1] == "serve.engine.device_wait"
    for d, (live, every) in zip(decodes, seen):
        c = d["stats"]
        assert c["rows"] == 3
        assert c["pages_walked"] == sum(min(-(-(n + 1) // PS), walked) for n in every)
        assert c["pages_walked"] < 3 * walked
        assert c["live"] == len(live) and c["live"] <= c["rows"]
        assert c["pages_live"] == sum(-(-(n + 1) // PS) for n in live)
        assert c["pages_live"] <= c["pages_walked"]
    chunks = [s for s in spans if s["name"] == "serve.engine.prefill"]
    assert chunks and all(c["parent"]["name"] == "serve.engine.step" for c in chunks)
    assert all(c["stats"]["padded"] >= c["stats"]["tokens"] for c in chunks)
    # the final chunk reads its token: a device wait inside the prefill span
    assert any(_children(spans, c) for c in chunks)


@pytest.mark.parametrize("chained", [False, True])
def test_decode_counts_pages_walked_is_the_kernels_walk(cfg, chained):
    """``pages_walked`` counts what the decode kernel copies: the pages
    below each row's length (cache plus the token being written), by the
    kernel's own ``live_pages``, every row's. On rows of different lengths
    that stays below rows x row width, which the kernel no longer walks."""
    from repro.kernels.paged_attention.kernel import live_pages

    eng = _engine(cfg, slots=4, new=20, chained=chained)
    for i, n in enumerate((3, 17, 40)):
        eng.submit(_prompt(cfg, n, i))
    for _ in range(6):
        eng.step()
    active = [i for i, s in enumerate(eng.slot_seq) if s is not None and not eng._chunking[i]]
    assert len(active) == 3 and len(set(eng.slot_len[active] // PS)) == 3
    c = eng._decode_counts(active)
    width = eng._row_width
    assert c["pages_walked"] == int(live_pages(eng.slot_len + 1, PS, width).sum())
    assert c["pages_live"] == sum(-(-(int(n) + 1) // PS) for n in eng.slot_len[active])
    assert c["pages_live"] < c["pages_walked"] < c["rows"] * width


def test_admit_stamp_is_set_once_and_kept_across_preemption(cfg):
    """Two sequences that each grow to 3 pages in a 4-page pool: the newest
    is preempted and re-admitted, and keeps its first admission stamp."""
    eng = PagedInferenceEngine(cfg, PagedEngineConfig(
        page_size=PS, num_pages=5, max_slots=2, max_seq_len=MAXLEN, max_new_tokens=32))
    for i in range(2):
        eng.submit([1 + i] * 5)
    seqs = list(eng.waiting)
    assert all(s.admit_t is None for s in seqs)
    first = {}
    for _ in range(200):
        eng.step()
        for s in seqs:
            if s.admit_t is not None:
                first.setdefault(s.sid, s.admit_t)
        if all(s is None for s in eng.slot_seq) and not eng.waiting:
            break
    assert eng.preemptions >= 1
    victim = next(s for s in seqs if s.preemptions)
    assert victim.done and victim.admit_t == first[victim.sid]
    for s in seqs:
        assert s.submit_t <= s.admit_t <= s.token_times[0]


def test_submit_stamps_before_the_engine_lock(cfg, tmp_path):
    """``submit`` stamps ``submit_t`` before it waits for the lock a step
    holds, and the wait is a ``serve.engine.lock_wait`` span."""
    eng = _engine(cfg)
    held, released = threading.Event(), []

    def hold():
        with eng.lock:
            held.set()
            time.sleep(0.2)
            released.append(time.monotonic())

    t = threading.Thread(target=hold)
    with Profiled(tmp_path) as prof:
        t.start()
        assert held.wait(30)
        eng.submit(_prompt(cfg, 5, 0))
        t.join(30)
    assert not t.is_alive()
    [seq] = eng.waiting
    assert seq.submit_t < released[0]
    [wait] = [s for s in prof.spans() if s["name"] == "serve.engine.lock_wait"]
    assert wait["t1"] - wait["t0"] >= 0.1e9


def test_loop_labels_engine_spans_and_resolves(cfg, tmp_path):
    eng = _engine(cfg, new=3)
    with EngineLoop(eng, name="tier-b", registry=MetricsRegistry()) as loop:
        loop.wait(loop.submit(_prompt(cfg, 5, 1)), timeout=120)     # warm
        with Profiled(tmp_path) as prof:
            loop.wait(loop.submit(_prompt(cfg, 5, 2)), timeout=120)
            time.sleep(0.1)
    spans = prof.spans()
    steps = [s for s in spans if s["name"] == "serve.engine.step"]
    assert steps and {s["stats"]["engine"] for s in steps} == {"tier-b"}
    resolve = [s for s in spans if s["name"] == "serve.loop.resolve"]
    assert [r["stats"]["finished"] for r in resolve] == [1]
    assert any(s["name"] == "serve.loop.idle" for s in spans)


def test_router_place_span_carries_the_tier(tmp_path):
    backends = {t: Backend(t, lambda r: "ok", capacity=1) for t in Tier}
    router = StraightLineRouter(backends, policy=StraightLinePolicy(Thresholds(F=1e9, D=1e6)),
                                registry=MetricsRegistry())
    with Profiled(tmp_path) as prof:
        tier = router.submit(Request(rid=1, arrival_t=0.0, data_size=100.0, timeout_s=10.0))
    [place] = [s for s in prof.spans() if s["name"] == "serve.router.place"]
    assert place["stats"]["tier"] == tier.name


def test_gc_spans(tmp_path):
    install_gc_spans()
    install_gc_spans()
    from repro.core import tracing

    assert gc.callbacks.count(tracing._gc_span) == 1
    with Profiled(tmp_path) as prof:
        gc.collect()
    spans = [s for s in prof.spans() if s["name"] == "serve.gc"]
    assert any(s["stats"]["generation"] == 2 and "collected" in s["stats"] for s in spans)


def test_span_records_into_a_request_trace_with_the_profiler_on(tmp_path):
    tr = Trace(7)
    with Profiled(tmp_path) as prof:
        with span("serve.test.on", tr, lane="lane-a", tokens=3) as sp:
            sp.set(padded=4)
    [(name, lane, t0, t1, attrs)] = tr.spans
    assert (name, lane, attrs) == ("serve.test.on", "lane-a", {"tokens": 3, "padded": 4})
    assert tr.t0 <= t0 <= t1
    [ev] = [s for s in prof.spans() if s["name"] == "serve.test.on"]
    assert ev["stats"] == {"tokens": 3, "padded": 4}


def test_without_a_profiler_nothing_is_recorded_but_the_trace(tmp_path):
    tr = Trace(8)
    with span("serve.test.off", tr, rows=2) as sp:
        sp.set(live=1)
    assert [s[0] for s in tr.spans] == ["serve.test.off"] and tr.spans[0][4] == {
        "rows": 2, "live": 1}
    with Profiled(tmp_path) as prof:
        with span("serve.test.marker"):
            pass
    names = {s["name"] for s in prof.spans()}
    assert "serve.test.marker" in names and "serve.test.off" not in names
