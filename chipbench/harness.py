"""One run of one cell: set-up, lead-in, the measured window, the drain, the
metrics and the check of what the window served.

Timeline (all on ``time.monotonic``, the clock the program stamps tokens
and requests with):

1. set-up: weights from the seed (one jitted call), the deployment, the
   program's ``prewarm()`` of every prefill bucket on every tier, and one
   warm-up request per tier, which compiles the decode step;
2. lead-in: the mix's own traffic for ``lead_in_s`` seconds, so Algorithm
   1's frequency window and the queues are in their steady state; set-up
   time ends where the window opens;
3. the window: requests due in ``[0, seconds)``, each sent when due by this
   (main) thread and timed from when it was due;
4. the drain: up to ``drain_s`` for the window's requests to finish.

With ``trace`` on, a profiler trace of ``trace_s`` seconds is taken from the
middle of the window by a thread of its own, and the step log records what
every engine step processed during it.
"""
from __future__ import annotations

import gc
import os
import shutil
import statistics
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

import check
import deploy
import traffic as traffic_mod
import xtrace

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
WARMUP_PROMPT_TOKENS = 40        # one 32-token chunk and one 16-token bucket
TRACE_S = 3.0


@dataclass
class Compiles:
    """Backend compiles counted by phase through ``jax.monitoring``."""
    phase: str = "setup"
    counts: Dict[str, int] = field(default_factory=dict)

    def install(self):
        import jax

        def on(event, secs, **_):
            if event == COMPILE_EVENT:
                self.counts[self.phase] = self.counts.get(self.phase, 0) + 1

        jax.monitoring.register_event_duration_secs_listener(on)
        return self


def tier_name(req) -> Optional[str]:
    """The tier that served a router request, by the deployment's tier names."""
    return None if req.tier is None else deploy.TIERS[int(req.tier)]


def _warm_up(dep, vocab: int, rng_seed: int) -> None:
    """Prewarm every prefill bucket, then send one request to each tier's
    loop; returns once each tier has emitted two tokens (its prefill token
    and one decode step), so every program the window runs is compiled.
    The requests finish during the lead-in."""
    for eng in dep.engines.values():
        eng.prewarm()
    rng = np.random.default_rng([int(rng_seed), 7])
    for loop in dep.loops.values():
        loop.submit(rng.integers(1, vocab, WARMUP_PROMPT_TOKENS).tolist())
    while any(eng.tokens_emitted < 2 for eng in dep.engines.values()):
        time.sleep(0.01)


class Tracer(threading.Thread):
    """Takes the profiler trace of [at, at + span) on the monotonic clock."""

    def __init__(self, at: float, span: float, log):
        super().__init__(name="chipbench-trace", daemon=True)
        self.at, self.span, self.log = at, span, log
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self.window = None
        self.error = None

    def run(self):
        import jax

        try:
            time.sleep(max(0.0, self.at - time.monotonic()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(xtrace.WINDOW_SPAN):
                self.log.on = True
                t0 = time.monotonic()
                time.sleep(max(0.0, t0 + self.span - time.monotonic()))
                t1 = time.monotonic()
                self.log.on = False
            jax.profiler.stop_trace()
            self.window = (t0, t1)
        except Exception as e:                  # reported by the run
            self.error = e

    def xplane(self) -> Optional[str]:
        for root, _, files in os.walk(self.dir):
            for f in files:
                if f.endswith(".xplane.pb"):
                    return os.path.join(root, f)
        return None

    def cleanup(self):
        shutil.rmtree(self.dir, ignore_errors=True)


class Session:
    """A deployment of one cell's configuration, built and warmed once; it
    serves one schedule per call of ``serve``."""

    def __init__(self, parts, cell: str, seed: int):
        import jax

        import weights
        from repro.models.api import get_model

        self.parts, self.cell = parts, cell
        wl = parts.workload(cell)
        self.cfg = parts.config(wl["config"])
        self.mix = parts.traffic(wl["traffic"])
        self.m = self.cfg["model"]
        parts.reference(self.cfg["reference"]).check(self.m)     # before any work
        self.compiles = Compiles().install()
        self.prompts: Dict[int, List[int]] = {}
        self.next_rid = 0
        shapes = get_model(deploy.model_config(self.cfg, use_pallas=False)).param_shapes()
        self.make_params = lambda s: weights.make_params(
            shapes, self.cfg["layout"]["leaves"], self.cfg["weights"]["rules"],
            self.cfg["layout"]["stacked"], s, jax.numpy.dtype(self.cfg["weights"]["dtype"]))
        self.dep = deploy.build(self.cfg, self.make_params(seed), self.mix["output_tokens"],
                                self.prompts)
        _warm_up(self.dep, self.m["vocab_size"], seed)
        self.compiles.phase = "lead-in"

    def reseed(self, seed: int) -> None:
        """New weights from ``seed`` in every engine (same shapes, no compile).
        The old weights are dropped first: two sets need not fit together."""
        for eng in self.dep.engines.values():
            eng.params = None
        gc.collect()
        params = self.make_params(seed)
        for eng in self.dep.engines.values():
            eng.params = params

    def schedule(self, seed: int, seconds: float, mix: Optional[dict] = None):
        sched = traffic_mod.schedule(self.parts, mix or self.mix, seed, seconds,
                                     self.m["vocab_size"])
        for r in sched.requests:
            r.rid += self.next_rid
            self.prompts[r.rid] = r.prompt
        self.next_rid += len(sched.requests)
        return sched

    def serve(self, sched, trace: bool = False, drain_s: Optional[float] = None) -> dict:
        """Lead-in, window and drain of ``sched``; returns what was served."""
        import jax

        from repro.core import Request
        from repro.launch.serve import REQUEST_TIMEOUT_S

        dep, seconds = self.dep, sched.window_s
        requests: Dict[int, Request] = {}
        self.compiles.phase = "lead-in"
        t0 = time.monotonic() + sched.lead_in_s
        close = t0 + seconds
        tracer = None
        if trace:
            span = min(TRACE_S, seconds / 2)
            tracer = Tracer(t0 + (seconds - span) / 2, span, dep.log)
            tracer.start()
        late: List[float] = []
        for r in sched.requests:
            due = t0 + r.due_s
            if r.in_window and self.compiles.phase != "window":
                time.sleep(max(0.0, t0 - time.monotonic()))
                self.compiles.phase = "window"
            time.sleep(max(0.0, due - time.monotonic()))
            req = Request(rid=r.rid, arrival_t=0.0, data_size=r.payload_bytes,
                          timeout_s=REQUEST_TIMEOUT_S)
            requests[r.rid] = req
            with jax.profiler.TraceAnnotation("router.submit"):
                dep.router.submit(req)
            if r.in_window:
                late.append(time.monotonic() - due)
        time.sleep(max(0.0, close - time.monotonic()))
        self.compiles.phase = "drain"
        window = sched.window
        drain_s = float(self.mix.get("drain_s", 0.0)) if drain_s is None else drain_s
        if drain_s > 0:
            deadline = close + drain_s
            for r in window:
                while requests[r.rid].finish_t is None and time.monotonic() < deadline:
                    time.sleep(0.005)
        t_end = time.monotonic() if drain_s > 0 else close
        if tracer is not None:
            tracer.join()
        seqs = {rid: dep.results[rid] for rid in requests if rid in dep.results}
        done = {rid for rid in seqs if requests[rid].finish_t is not None
                and not requests[rid].failed}
        failed = sum(1 for r in window if requests[r.rid].failed)
        if drain_s > 0:
            failed += sum(1 for r in window if r.rid not in done and not requests[r.rid].failed)
        return {"requests": requests, "seqs": seqs, "done": done, "window": window,
                "failed": failed, "late": late, "t0": t0, "close": close, "t_end": t_end,
                "tracer": tracer, "compiles": dict(self.compiles.counts)}

    def live_stamps(self) -> List[List[float]]:
        """Token stamps of sequences still inside the engines."""
        return [list(s.token_times) for eng in self.dep.engines.values()
                for s in list(eng.slot_seq) + list(eng.waiting) if s is not None]

    def finished(self, served: dict) -> List[dict]:
        req, seqs = served["requests"], served["seqs"]
        return [{"rid": rid, "prompt": self.prompts[rid], "out": list(seqs[rid].out),
                 "tier": tier_name(req[rid])} for rid in sorted(served["done"])]

    def stop(self) -> None:
        for lp in self.dep.loops.values():
            lp.stop()
        self.dep.router.stop()


def observations(s: Session, served: dict, stamps: List[List[float]], device_kind: str) -> dict:
    """What the metric readers read (see ``metrics/``)."""
    req, seqs, done = served["requests"], served["seqs"], served["done"]
    obs = {
        "model": s.m, "peaks": s.parts.peaks(device_kind),
        "page": s.cfg["page_tokens"],
        "requests": [
            {"rid": r.rid, "due": served["t0"] + r.due_s, "done": r.rid in done,
             "tier": tier_name(req[r.rid]),
             "arrival_t": req[r.rid].arrival_t, "start_t": req[r.rid].start_t,
             "finish_t": req[r.rid].finish_t,
             "token_times": list(seqs[r.rid].token_times) if r.rid in seqs else []}
            for r in served["window"]],
        "stamps": [list(q.token_times) for q in seqs.values()] + stamps,
        "t0": served["t0"], "close": served["close"], "t_end": served["t_end"],
        "steps": None, "trace": None,
    }
    tracer = served["tracer"]
    if tracer is not None:
        if tracer.error is not None or tracer.window is None:
            raise RuntimeError(f"the profiler trace failed: {tracer.error!r}")
        w0, w1 = tracer.window
        obs["steps"] = [rec for rec in s.dep.log.records if rec[1] >= w0 and rec[2] <= w1]
        obs["trace"] = xtrace.reduce(tracer.xplane())
        tracer.cleanup()
    return obs


def verdict(s: Session, served: dict, seed: int, control: bool = False):
    """The numbers ``correct`` compares, each beside its limit, and
    ``correct``: the widest logit gap of a seeded sample of what the window
    served against the reference (with ``control``, of the tokens that the
    reference in the configuration's lower control precision puts first, in
    the program's place), short outputs and ids outside the vocabulary.
    Returns (checks, correct, the sample's numbers, the tiers it covers)."""
    finished = s.finished(served)
    sample = check.sample(finished, int(s.mix["check_requests"]), seed)
    numbers = check.compare(s.parts, s.cfg, seed, sample, control) if sample else {}
    out_tokens, vocab = int(s.mix["output_tokens"]), s.m["vocab_size"]
    limits = s.parts.limits(s.cell)
    checks = {
        "max_logit_gap": {"value": numbers.get("max_logit_gap"), "limit": limits["max_logit_gap"]},
        "short_outputs": {"value": sum(1 for f in finished if len(f["out"]) != out_tokens),
                          "limit": 0},
        "out_of_vocab": {"value": sum(1 for f in finished for t in f["out"] if not 0 <= t < vocab),
                         "limit": 0},
    }
    correct = bool(sample) and all(
        c["value"] is not None and c["value"] <= c["limit"] for c in checks.values())
    return checks, correct, numbers, sorted({x["tier"] for x in sample})


def run(parts, cell: str, seed: int, seconds: float, trace: bool, t_start: float,
        control: bool = False) -> dict:
    """Run ``cell`` once: the contract's result object, less the device's
    name, plus ``info`` (earlier lines) and ``checks`` (compared numbers).
    ``control`` judges the control's tokens in the program's place."""
    import jax

    s = Session(parts, cell, seed)
    sched = s.schedule(seed, seconds)
    served = s.serve(sched, trace)
    setup_s = served["t0"] - t_start
    devices = jax.local_devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    s.stop()
    stamps = s.live_stamps()
    obs = observations(s, served, stamps, devices[0].device_kind)
    finished = s.finished(served)
    info = {
        "setup_s": setup_s, "window_s": seconds, "lead_in_s": sched.lead_in_s,
        "compiles": served["compiles"],
        "generator_late_ms": {"median": 1e3 * statistics.median(served["late"]),
                              "max": 1e3 * max(served["late"])} if served["late"] else {},
        "served_by_tier": {t: sum(1 for f in finished if f["tier"] == t) for t in deploy.TIERS},
        "window_done": sum(1 for r in served["window"] if r.rid in served["done"]),
        "preemptions": sum(e.preemptions for e in s.dep.engines.values()),
    }
    s.dep.engines.clear()
    s.dep.loops.clear()
    s.dep = None
    gc.collect()

    metrics = {}
    entries = parts.per_layer_for(cell) if trace else parts.end_to_end_for(cell)
    for e in entries:
        v = setup_s if e["name"] == "setup_s" else parts.metric(e["name"]).read(obs)
        if v is not None:
            metrics[e["name"]] = {"value": v, "unit": e["unit"]}
    result = {"attempted": len(served["window"]), "failed": served["failed"],
              "metrics": metrics, "memory_peak_bytes": peak}
    if trace:
        red = obs["trace"]
        result["device_trace"] = {"busy_s": red["busy_s"], "window_s": red["window_s"]}
        result["breakdown"] = xtrace.breakdown(red)
        info["idle_s_by_span"] = red["idle_by_span"]
        info["device_s_by_program"] = red["modules"]

    checks, result["correct"], info["check"], info["check_tiers"] = verdict(s, served, seed, control)
    result["info"] = info
    result["checks"] = checks
    return result
