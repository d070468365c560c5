"""The trace reduction (``xtrace.py``) on two small traces in ``testdata/``.

``synthetic.xplane.pb`` is built by hand (every interval in whole
microseconds, listed in ``SYNTHETIC`` and checked against the file first),
so its busy union, idle share, kernel time and gap names are known exactly.
``decode_step.xplane.pb`` is recorded: one warm run of SmolLM-360M's decode
program on a TPU v5e chip (8 slots), device plane only; its busy union is
checked against an independent count at nanosecond resolution.
"""
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import xtrace  # noqa: E402

DATA = BENCH / "testdata"
US = 1000.0          # ns

# (plane, line, event name, start us, duration us)
SYNTHETIC = [
    (f"/device:TPU:{c}", "XLA Ops", name, s + (5 if c == 1 and name.startswith("%copy") else 0), d)
    for c in (0, 1)
    for name, s, d in [("%fusion.1 = bf16[8]", 1090, 20),
                       ("%paged_attention_grouped.3 = bf16[8]", 1105, 15),
                       ("%while.2 = (s32[])", 1100, 30), ("%copy.7 = bf16[8]", 1150, 10),
                       ("%fusion.1 = bf16[8]", 1190, 20)]
] + [
    (f"/device:TPU:{c}", "XLA Modules", name, s, d)
    for c in (0, 1)
    for name, s, d in [("jit_decode_all(123)", 1100, 30), ("jit_decode_all(123)", 1150, 10),
                       ("jit_prefill_chunk_paged(456)", 1190, 20)]
] + [
    ("/host:CPU", "python3", "chipbench.window", 1100, 100),
    ("/host:CPU", "python3", "engine.step.batch", 1125, 30),
    ("/host:CPU", "python3", "router.submit", 1160, 25),
    ("/host:CPU", "python3", "other.thing", 1140, 10),
]


def _events(path):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    return [(p.name, ln.name, e.name, e.start_ns, e.duration_ns)
            for p in pd.planes for ln in p.lines for e in ln.events]


def test_synthetic_trace_holds_what_the_table_says():
    got = sorted((p, ln, n, round(s / US), round(d / US)) for p, ln, n, s, d in
                 _events(DATA / "synthetic.xplane.pb"))
    assert got == sorted(SYNTHETIC)


def test_synthetic_reduction():
    red = xtrace.reduce(str(DATA / "synthetic.xplane.pb"))
    assert red["chips"] == 2
    assert red["window_s"] == pytest.approx(100e-6)
    # chip 0: 1100-1130 (fusion clipped at the window's start, attention, while),
    # 1150-1160, 1190-1200 (clipped at its end) = 50 us; chip 1 the same with
    # its copy 5 us later
    assert red["busy_s"] == pytest.approx(50e-6)
    # containers (while) count towards busy but not as an operation
    assert red["ops"] == pytest.approx({"fusion": 20e-6, "paged_attention_grouped": 15e-6,
                                        "copy": 10e-6})
    # runs wholly inside the window, summed over both chips
    assert red["modules"]["jit_decode_all"] == pytest.approx((80e-6, 4))
    assert "jit_prefill_chunk_paged" not in red["modules"]
    # chip 0's gaps 1130-1150 and 1160-1190, named by the harness span
    # overlapping each most; "other.thing" is no harness span
    assert [n for n, _ in red["idle_gaps"]] == ["engine.step.batch", "router.submit"]
    assert [s for _, s in red["idle_gaps"]] == pytest.approx([20e-6, 30e-6])
    assert red["idle_by_span"] == pytest.approx({"engine.step.batch": 20e-6, "router.submit": 30e-6})
    bd = xtrace.breakdown(red)
    assert [k for k, _ in bd["device_ops"]] == ["fusion", "paged_attention_grouped", "copy"]
    assert [k for k, _ in bd["idle_gaps"]] == ["router.submit", "engine.step.batch"]


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        xtrace.reduce(str(DATA / "decode_step.xplane.pb"))


def test_recorded_decode_step():
    path = DATA / "decode_step.xplane.pb"
    events = _events(path)
    mod = next(e for e in events if e[1] == "XLA Modules" and e[2].startswith("jit_decode_all"))
    w0, w1 = mod[3] - 0.5e6, mod[3] + mod[4] + 0.5e6
    red = xtrace.reduce(str(path), window=(w0, w1))
    ops = [(s, s + d, n) for p, ln, n, s, d in events if ln == "XLA Ops"]
    # busy union, counted independently on a nanosecond grid
    grid = np.zeros(int(w1 - w0) + 2, bool)
    for s, e, _ in ops:
        a, b = max(s, w0), min(e, w1)
        if b > a:
            grid[int(round(a - w0)):int(round(b - w0))] = True
    assert red["busy_s"] * 1e9 == pytest.approx(grid.sum(), abs=2 * len(ops))
    assert 0 < red["busy_s"] < red["window_s"]
    idle = 1 - red["busy_s"] / red["window_s"]
    assert idle == pytest.approx(1 - grid.sum() / (w1 - w0), abs=1e-4)
    # kernel time: the paged attention kernel's own events
    pa = sum(min(e, w1) - max(s, w0) for s, e, n in ops
             if n.startswith("%paged_attention_grouped") and e > w0 and s < w1)
    assert red["ops"]["paged_attention_grouped"] == pytest.approx(pa * 1e-9)
    assert red["modules"]["jit_decode_all"] == pytest.approx((mod[4] * 1e-9, 1))
    # the kernel ran once per layer: 32 of SmolLM-360M
    assert sum(1 for *_, n in ops if n.startswith("%paged_attention_grouped")) == 32
