"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics read.

Device planes are ``/device:TPU:<n>``. Their ``XLA Ops`` line holds one event
per operation (its name is the HLO instruction: ``%<op>.<n> = ...``), their
``XLA Modules`` line one event per program run (``jit_<fn>(<hash>)``). The
host plane's thread lines hold the harness's ``TraceAnnotation`` spans, on
the same clock. The window is the harness span ``chipbench.window``.

- busy: the union of operation intervals inside the window, per chip,
  averaged over chips; idle share is 1 - busy / window;
- ops: device seconds per operation name (``<op>`` with its ``.n`` dropped),
  leaving out ``while``/``conditional``/``call``, which contain others;
- modules: seconds and runs per program name (``jit_<fn>``);
- idle gaps: each stretch of the window with no operation on chip 0, named
  by the harness span that overlaps it most (``no_span`` where none does),
  and their total by name.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "chipbench.window"
CONTAINERS = ("while", "conditional", "call")
_OP = re.compile(r"^%?([^ =]+?)(?:\.\d+)?(?: =|$)")
_MODULE = re.compile(r"^(.*?)(?:\(\d+\))?$")


def op_name(event_name: str) -> str:
    m = _OP.match(event_name)
    return m.group(1) if m else event_name


def module_name(event_name: str) -> str:
    return _MODULE.match(event_name).group(1)


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s, e, w0, w1):
    return max(s, w0), min(e, w1)


def reduce(path: str, window: Optional[Tuple[float, float]] = None,
           host_prefixes: Tuple[str, ...] = ("router.", "engine.", "generator.")) -> Dict:
    """Reduce the trace at ``path``. ``window`` (start, end) in the trace's
    nanoseconds overrides the ``chipbench.window`` span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans: List[Tuple[float, float, str]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN and window is None:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name.startswith(host_prefixes):
                        spans.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} span and no window given")
    if not devices:
        raise ValueError(f"{path}: no TPU device plane")
    w0, w1 = window
    devices.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    busy_ns, ops, mods, chip0 = [], defaultdict(float), defaultdict(lambda: [0.0, 0]), None
    for plane in devices:
        ivs = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, w0, w1)
                    if e <= s:
                        continue
                    ivs.append((s, e))
                    name = op_name(ev.name)
                    if name not in CONTAINERS:
                        ops[name] += (e - s) * 1e-9
            elif line.name == "XLA Modules":
                for ev in line.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if s >= w0 and e <= w1:
                        rec = mods[module_name(ev.name)]
                        rec[0] += (e - s) * 1e-9
                        rec[1] += 1
        merged = union(ivs)
        busy_ns.append(sum(e - s for s, e in merged))
        if chip0 is None:
            chip0 = merged
    gaps = []
    prev = w0
    for s, e in chip0 + [(w1, w1)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    named: List[Tuple[str, float]] = []
    spans.sort()
    active: List[Tuple[float, float, str]] = []
    nxt = 0
    for g0, g1 in gaps:
        while nxt < len(spans) and spans[nxt][0] < g1:
            active.append(spans[nxt])
            nxt += 1
        active = [sp for sp in active if sp[1] > g0]
        best, best_ov = "no_span", 0.0
        for s, e, name in active:
            ov = min(e, g1) - max(s, g0)
            if ov > best_ov:
                best, best_ov = name, ov
        named.append((best, (g1 - g0) * 1e-9))
    by_span: Dict[str, float] = defaultdict(float)
    for name, secs in named:
        by_span[name] += secs
    n_dev = len(devices)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy_ns) / n_dev * 1e-9,
        "chips": n_dev,
        "ops": {k: v / n_dev for k, v in ops.items()},
        "modules": {k: (v[0], v[1]) for k, v in mods.items()},
        "idle_gaps": named,
        "idle_by_span": dict(by_span),
    }


def breakdown(red: Dict, n: int = 10) -> Dict[str, list]:
    """The contract's ``breakdown``: the device operations that took most
    time and the longest idle gaps by what the host was doing, 10 of each
    at most."""
    top = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:n]
    gaps = sorted(red["idle_gaps"], key=lambda g: -g[1])[:n]
    return {"device_ops": [[k, v] for k, v in top], "idle_gaps": [[k, v] for k, v in gaps]}
