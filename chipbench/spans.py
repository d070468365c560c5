"""Reduce the program's own serving spans and the device ops' scope paths in
a profiler trace (``.xplane.pb``) to what later per-layer metrics read.

The program puts a ``serve.*`` span (``repro.core.tracing.span``) on every
serving layer boundary; they are ``TraceAnnotation`` events on the host
plane's thread lines, on the clock of the device ops, with their counts as
event stats. The model's device programs carry ``jax.named_scope`` names,
which each operation's scope path (its ``op_name``) holds. The window is
the harness span ``chipbench.window``, as in ``xtrace.py``.

- steps, per engine (the ``engine`` count of ``serve.engine.step``): mean
  step time, the part of it spent in ``serve.engine.device_wait``
  descendants, the rest (host time), and the device time of the engine's
  own decode program (the ``jit_decode_all`` run that ended last before the
  decode's device wait ended);
- decode counts, per engine: ``rows``, ``live``, ``pages_walked`` and
  ``pages_live`` summed over its ``serve.engine.decode`` spans;
- device time per run of ``jit_decode_all`` by model scope (the innermost
  of ``SCOPES`` on each operation's scope path, else ``unscoped``), leaving
  out ``while``/``conditional``/``call``, which contain others;
- idle gaps on chip 0, each put down to the working ``serve.*`` span,
  ``serve.gc`` or JAX compile event that overlaps it most (the innermost of
  equal overlaps); wait spans are not working spans; ``none`` where nothing
  overlaps, ``unobserved`` where that gap opens the window: the profiler
  records no span or operation that began before its session started.

Only spans and runs wholly inside the window count; gaps are clipped to it.
A trace without the program's spans reduces to empty tables, never an error.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import numpy as np

import xplane
import xtrace

PREFIX = "serve."
STEP, DECODE, DEVICE_WAIT = "serve.engine.step", "serve.engine.decode", "serve.engine.device_wait"
WAIT_SPANS = (DEVICE_WAIT, "serve.engine.lock_wait", "serve.loop.idle")
COMPILE_EVENTS = ("backend_compile_and_load", "lower_sharding_computation")
COMPILE = "jax.compile"
DECODE_MODULE = "jit_decode_all"
SCOPES = ("embed", "attn", "kv_write", "paged_attn", "mlp", "norm", "head", "sample")
UNSCOPED = "unscoped"
UNOBSERVED = "unobserved"
SCOPE_STAT = "tf_op"          # an operation's scope path, on its metadata in a TPU trace
DECODE_COUNTS = ("rows", "live", "pages_walked", "pages_live")


class Span:
    __slots__ = ("name", "t0", "t1", "stats", "parent", "line")

    def __init__(self, name, t0, t1, stats, line):
        self.name, self.t0, self.t1, self.stats, self.line = name, t0, t1, stats, line
        self.parent: Optional[Span] = None

    def within(self, w0: float, w1: float) -> bool:
        return self.t0 >= w0 and self.t1 <= w1

    def ancestor(self, name: str) -> Optional["Span"]:
        p = self.parent
        while p is not None and p.name != name:
            p = p.parent
        return p


def _nest(spans: List[Span]) -> None:
    """Parent of each span: the innermost span of the same thread line that
    contains it."""
    spans.sort(key=lambda s: (s.line, s.t0, -s.t1))
    stack: List[Span] = []
    for s in spans:
        while stack and (stack[-1].line != s.line or stack[-1].t1 < s.t1):
            stack.pop()
        s.parent = stack[-1] if stack else None
        stack.append(s)


def scope_of(path: str) -> str:
    """The innermost model scope on an operation's scope path (a TPU trace
    writes it as ``<op_name>:<op type>``)."""
    for part in reversed(path.split("/")):
        part = part.split(":", 1)[0]
        if part in SCOPES:
            return part
    return UNSCOPED


def _decode_scopes(devices, w0, w1) -> Tuple[Dict[str, float], int]:
    """Device seconds by scope over every run of the decode program wholly
    inside the window, summed over chips, and the number of runs."""
    by_scope: Dict[str, float] = defaultdict(float)
    runs = 0
    for evs in devices.values():
        mods = sorted((s, s + d) for _, line, name, s, d, _ in evs
                      if line == "XLA Modules" and xtrace.module_name(name) == DECODE_MODULE
                      and s >= w0 and s + d <= w1)
        runs += len(mods)
        starts = [m[0] for m in mods]
        for _, line, name, s, d, stats in evs:
            if line != "XLA Ops" or xtrace.op_name(name) in xtrace.CONTAINERS:
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < mods[i][1]:
                by_scope[scope_of(str(stats.get(SCOPE_STAT, "")))] += d * 1e-9
    return dict(by_scope), runs


def _own_decode(spans: List[Span], chip0_decodes: List[Tuple[float, float]]) -> Dict[str, list]:
    """Device seconds of each engine's own decode runs: for a decode span,
    the decode program run on chip 0 that started after its dispatch began
    and ended last before the device wait that follows it ended."""
    ends = [e for _, e in chip0_decodes]
    out: Dict[str, list] = defaultdict(list)
    by_line: Dict[object, List[Span]] = defaultdict(list)
    for s in spans:
        if s.name in (DECODE, DEVICE_WAIT):
            by_line[s.line].append(s)
    for seq in by_line.values():
        seq.sort(key=lambda s: s.t0)
        for d, w in zip(seq, seq[1:]):
            if d.name != DECODE or w.name != DEVICE_WAIT:
                continue
            step = d.ancestor(STEP)
            i = bisect.bisect_right(ends, w.t1) - 1
            if step is None or i < 0 or chip0_decodes[i][0] < d.t0:
                continue
            out[str(step.stats.get("engine", "engine"))].append(
                (chip0_decodes[i][1] - chip0_decodes[i][0]) * 1e-9)
    return out


def _idle_by_span(spans: List[Span], compiles: List[Span], gaps, w0: float) -> Dict[str, float]:
    cands = [s for s in spans if s.name not in WAIT_SPANS] + compiles
    cands.sort(key=lambda s: s.t0)
    out: Dict[str, float] = defaultdict(float)
    nxt, active = 0, []
    for g0, g1 in gaps:
        while nxt < len(cands) and cands[nxt].t0 < g1:
            active.append(cands[nxt])
            nxt += 1
        active = [s for s in active if s.t1 > g0]
        best, key = "none", (0.0, 0.0)
        for s in active:
            ov = min(s.t1, g1) - max(s.t0, g0)
            if ov > 0 and (ov, -(s.t1 - s.t0)) > key:
                best, key = s.name, (ov, -(s.t1 - s.t0))
        if best == "none" and g0 == w0:
            best = UNOBSERVED
        out[best] += (g1 - g0) * 1e-9
    return dict(out)


def reduce(path: str, window: Optional[Tuple[float, float]] = None) -> Dict:
    """Reduce the trace at ``path``. ``window`` (start, end) in the trace's
    nanoseconds overrides the ``chipbench.window`` span."""
    spans: List[Span] = []
    compiles: List[Span] = []
    devices: Dict[int, list] = {}
    for plane in xplane.load(path).planes:
        if plane.name.startswith("/device:TPU:"):
            devices[int(plane.name.rsplit(":", 1)[1])] = list(xplane.events(plane))
            continue
        if not plane.name.startswith("/host:"):
            continue
        for k, _, name, t0, dur, stats in xplane.events(plane):
            if name == xtrace.WINDOW_SPAN and window is None:
                window = (t0, t0 + dur)
            elif name.startswith(PREFIX):
                spans.append(Span(name, t0, t0 + dur, stats, (plane.name, k)))
            elif name in COMPILE_EVENTS:
                compiles.append(Span(COMPILE, t0, t0 + dur, {}, (plane.name, k)))
    if window is None:
        raise ValueError(f"{path}: no {xtrace.WINDOW_SPAN!r} span and no window given")
    w0, w1 = window
    _nest(spans)

    gaps, chip0_decodes = [], []
    if devices:
        chip0 = devices[min(devices)]
        ops = [(max(s, w0), min(s + d, w1)) for _, line, _, s, d, _ in chip0
               if line == "XLA Ops" and s < w1 and s + d > w0]
        chip0_decodes = sorted(((s, s + d) for _, line, name, s, d, _ in chip0
                                if line == "XLA Modules"
                                and xtrace.module_name(name) == DECODE_MODULE),
                               key=lambda m: m[1])
        prev = w0
        for s, e in xtrace.union(ops) + [(w1, w1)]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)

    inside = [s for s in spans if s.within(w0, w1)]
    steps: Dict[str, dict] = {}
    for s in inside:
        if s.name != STEP:
            continue
        rec = steps.setdefault(str(s.stats.get("engine", "engine")),
                               {"n": 0, "step_s": 0.0, "device_wait_s": 0.0})
        rec["n"] += 1
        rec["step_s"] += (s.t1 - s.t0) * 1e-9
    for s in inside:
        step = s.ancestor(STEP) if s.name == DEVICE_WAIT else None
        if step is not None and step.within(w0, w1):
            steps[str(step.stats.get("engine", "engine"))]["device_wait_s"] += (s.t1 - s.t0) * 1e-9
    own = _own_decode(inside, chip0_decodes)
    for eng, rec in steps.items():
        n = rec.pop("n")
        step_s, wait_s = rec.pop("step_s"), rec.pop("device_wait_s")
        rec.update(steps=n, step_ms=1e3 * step_s / n, device_wait_ms=1e3 * wait_s / n,
                   host_ms=1e3 * (step_s - wait_s) / n,
                   own_decode_dev_ms=1e3 * float(np.mean(own[eng])) if own.get(eng) else None)

    decode: Dict[str, Dict[str, int]] = {}
    for s in inside:
        step = s.ancestor(STEP) if s.name == DECODE else None
        if step is None:
            continue
        rec = decode.setdefault(str(step.stats.get("engine", "engine")),
                                dict.fromkeys(DECODE_COUNTS + ("decodes",), 0))
        rec["decodes"] += 1
        for k in DECODE_COUNTS:
            rec[k] += int(s.stats.get(k, 0))

    by_scope, runs = _decode_scopes(devices, w0, w1)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "steps": steps,
        "decode": decode,
        "decode_runs": runs,
        "decode_dev_ms_by_scope": {k: 1e3 * v / runs for k, v in by_scope.items()} if runs else {},
        "idle_s_by_program_span": _idle_by_span(spans, compiles, gaps, w0),
    }


def busiest(red: Dict) -> Optional[str]:
    """The engine whose decodes served the most live rows."""
    dec = red.get("decode") or {}
    return max(dec, key=lambda e: dec[e]["live"]) if dec else None


def step_host_ms(red: Dict) -> Optional[float]:
    """Mean host time of the busiest engine's steps: each step less its
    device waits (ms)."""
    eng = busiest(red)
    rec = red["steps"].get(eng) if eng is not None else None
    return rec["host_ms"] if rec else None


def decode_rows_live_share(red: Dict) -> Optional[float]:
    """Rows decoding over rows swept, every decode in the window (%)."""
    dec = red.get("decode") or {}
    rows = sum(r["rows"] for r in dec.values())
    return 100.0 * sum(r["live"] for r in dec.values()) / rows if rows else None


def kv_pages_live_share(red: Dict) -> Optional[float]:
    """Pages of the live rows over pages the decode kernel walked, every
    decode in the window (%)."""
    dec = red.get("decode") or {}
    walked = sum(r["pages_walked"] for r in dec.values())
    return 100.0 * sum(r["pages_live"] for r in dec.values()) / walked if walked else None


def decode_unscoped_share(red: Dict) -> Optional[float]:
    """Share of the decode program's device time in operations that carry
    no model scope (%); nothing where no operation carries one (a program
    without the scopes)."""
    by = red.get("decode_dev_ms_by_scope") or {}
    if not any(k != UNSCOPED and v > 0 for k, v in by.items()):
        return None
    return 100.0 * by.get(UNSCOPED, 0.0) / sum(by.values())


def engine_queue_p95_ms(requests: List[dict]) -> Optional[float]:
    """Time from the router's hand-off (``start_t``) to the first admission
    into an engine slot (``admit_t``), 95th percentile over finished
    requests (ms)."""
    v = [r["admit_t"] - r["start_t"] for r in requests
         if r.get("done") and r.get("admit_t") is not None and r.get("start_t") is not None]
    return 1e3 * float(np.percentile(v, 95)) if v else None


METRICS = {"step_host_ms": step_host_ms, "decode_rows_live_share": decode_rows_live_share,
           "kv_pages_live_share": kv_pages_live_share,
           "decode_unscoped_share": decode_unscoped_share}
