"""The reduction of the program's spans and scopes (``spans.py``) and the
full-stat trace reader (``xplane.py``), on ``testdata/spans.xplane.pb``.

That trace is built by hand from ``SPANS`` below (run this file to write
it again), every interval in whole microseconds, so every number the
reduction gives is known: two engines' nested ``serve.*`` spans with their
counts on two thread lines, a GC span and a compile event, and a device
plane whose decode runs hold operations with scope paths, some on the
operation's metadata (as a TPU trace keeps them), one on the event itself,
one with none.

``chat_spans.xplane.pb`` is cut from a recorded trace (the traced 3-s
window of ``smollm-360m.chat``, seed 2900001301, one TPU v5e chip): every
``serve.*`` span, chip 0's program runs, and the operations of one
interactive and one batch decode run, names cut at `` =`` and ``tf_op``
the one stat kept (``record`` below).
"""
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import spans  # noqa: E402
import xplane  # noqa: E402

DATA = BENCH / "testdata"
PATH = DATA / "spans.xplane.pb"
RECORDED = DATA / "chat_spans.xplane.pb"
HOST, TPU = "/host:CPU", "/device:TPU:0"
DEC = "jit(decode_all)/decode_all/while/body"

# (plane, line, event name, start us, duration us, event stats, metadata stats)
SPANS = [
    (HOST, "python3", "chipbench.window", 1000, 200, {}, {}),
    (HOST, "python3", "serve.engine.lock_wait", 1150, 8, {}, {}),
    (HOST, "python3", "backend_compile_and_load", 1175, 17, {}, {}),
    (HOST, "python3", "PjitFunction(decode_all)", 1041, 3, {}, {}),
    (HOST, "engine-a", "serve.engine.step", 990, 15, {"engine": "batch"}, {}),
    (HOST, "engine-a", "serve.engine.step", 1010, 80, {"engine": "batch"}, {}),
    (HOST, "engine-a", "serve.engine.grow", 1010, 2, {"preempted": 0}, {}),
    (HOST, "engine-a", "serve.engine.admit", 1012, 8, {"admitted": 1}, {}),
    (HOST, "engine-a", "serve.engine.prefill", 1020, 20,
     {"tokens": 30, "padded": 32, "offset": 0}, {}),
    (HOST, "engine-a", "serve.engine.device_wait", 1030, 10, {}, {}),
    (HOST, "engine-a", "serve.engine.decode", 1040, 10,
     {"rows": 8, "live": 3, "pages_walked": 64, "pages_live": 5}, {}),
    (HOST, "engine-a", "serve.engine.device_wait", 1050, 35, {}, {}),
    (HOST, "engine-a", "serve.loop.resolve", 1090, 5, {"finished": 1}, {}),
    (HOST, "engine-a", "serve.engine.step", 1100, 60, {"engine": "batch"}, {}),
    (HOST, "engine-a", "serve.engine.decode", 1100, 12,
     {"rows": 8, "live": 4, "pages_walked": 64, "pages_live": 7}, {}),
    (HOST, "engine-a", "serve.engine.device_wait", 1112, 38, {}, {}),
    (HOST, "engine-a", "serve.loop.idle", 1160, 40, {}, {}),
    (HOST, "engine-b", "serve.engine.step", 1020, 50, {"engine": "interactive"}, {}),
    (HOST, "engine-b", "serve.engine.decode", 1020, 5,
     {"rows": 1, "live": 1, "pages_walked": 8, "pages_live": 2}, {}),
    (HOST, "engine-b", "serve.engine.device_wait", 1025, 43, {}, {}),
    (HOST, "engine-b", "serve.gc", 1160, 15, {"generation": 2, "collected": 10}, {}),
    (HOST, "engine-b", "serve.engine.step", 1190, 20, {"engine": "interactive"}, {}),
    (TPU, "XLA Modules", "jit_prefill_chunk_paged(333)", 995, 10, {}, {}),
    (TPU, "XLA Modules", "jit_prefill_chunk_paged(333)", 1008, 2, {}, {}),
    (TPU, "XLA Modules", "jit_prefill_chunk_paged(333)", 1021, 17, {}, {}),
    (TPU, "XLA Modules", "jit_decode_all(222)", 1038, 28, {}, {}),
    (TPU, "XLA Modules", "jit_decode_all(111)", 1066, 18, {}, {}),
    (TPU, "XLA Modules", "jit_decode_all(111)", 1112, 36, {}, {}),
    (TPU, "XLA Modules", "jit_prefill_chunk_paged(333)", 1176, 2, {}, {}),
    (TPU, "XLA Ops", "%fusion.10 = bf16[8]", 995, 10, {},
     {"tf_op": "jit(prefill_chunk_paged)/prefill_chunk/mlp/dot_general"}),
    (TPU, "XLA Ops", "%fusion.11 = bf16[8]", 1008, 2, {}, {}),
    (TPU, "XLA Ops", "%fusion.1 = bf16[8]", 1021, 17, {},
     {"tf_op": "jit(prefill_chunk_paged)/prefill_chunk/attn/paged_attn/dot_general"}),
    (TPU, "XLA Ops", "%while.2 = (s32[])", 1038, 28, {},
     {"tf_op": "jit(decode_all)/decode_all/while"}),
    (TPU, "XLA Ops", "%copy.3 = bf16[8]", 1038, 6, {}, {"tf_op": f"{DEC}/copy"}),
    (TPU, "XLA Ops", "%paged_attention_grouped.4 = bf16[8]", 1044, 16, {},
     {"tf_op": f"{DEC}/attn/paged_attn/jit(paged_attention_grouped)"
               "/paged_attention_grouped/pallas_call"}),
    (TPU, "XLA Ops", "%fusion.5 = bf16[8]", 1060, 6, {},
     {"tf_op": "jit(decode_all)/decode_all/head/dot_general"}),
    (TPU, "XLA Ops", "%rmsnorm_2d.6 = bf16[8]", 1066, 4, {},
     {"tf_op": f"{DEC}/norm/jit(rmsnorm_2d)/rmsnorm_2d/pallas_call"}),
    (TPU, "XLA Ops", "%fusion.7 = bf16[8]", 1070, 10,
     {"tf_op": f"{DEC}/attn/kv_write/scatter"}, {}),
    (TPU, "XLA Ops", "%copy.8 = bf16[8]", 1080, 4, {}, {}),
    (TPU, "XLA Ops", "%fusion.9 = bf16[8]", 1112, 18, {}, {"tf_op": f"{DEC}/mlp/dot_general"}),
    (TPU, "XLA Ops", "%copy.12 = bf16[8]", 1130, 18, {}, {"tf_op": f"{DEC}/dynamic-slice"}),
    (TPU, "XLA Ops", "%fusion.13 = bf16[8]", 1176, 2, {}, {}),
]
US = 1e-6


def test_trace_holds_what_the_table_says():
    got = sorted((p.name, ln, n, round(s / 1e3), round(d / 1e3), st)
                 for p in xplane.load(str(PATH)).planes
                 for _, ln, n, s, d, st in xplane.events(p))
    want = sorted((p, ln, n, s, d, {**m, **st}) for p, ln, n, s, d, st, m in SPANS)
    assert got == want


def test_the_reader_agrees_with_the_profiler_data_reader():
    """Names and intervals as ``jax.profiler.ProfileData`` reads them, on a
    built trace and on the two recorded chip traces."""
    from jax.profiler import ProfileData

    for path in (PATH, RECORDED, DATA / "decode_step.xplane.pb"):
        mine = sorted((p.name, ln, n, round(s), round(d))
                      for p in xplane.load(str(path)).planes
                      for _, ln, n, s, d, _ in xplane.events(p))
        theirs = sorted((p.name, ln.name, e.name, round(e.start_ns), round(e.duration_ns))
                        for p in ProfileData.from_file(str(path)).planes
                        for ln in p.lines for e in ln.events)
        assert [r[:3] for r in mine] == [r[:3] for r in theirs]
        assert np.allclose([r[3:] for r in mine], [r[3:] for r in theirs], atol=1)


def test_ref_values_resolve_to_their_names():
    space = xplane.XSpace()
    plane = space.planes.add(id=1, name=TPU)
    for sid, name in ((1, "tf_op"), (2, "jit(decode_all)/attn/dot")):
        plane.stat_metadata[sid].id = sid
        plane.stat_metadata[sid].name = name
    plane.event_metadata[1].id = 1
    plane.event_metadata[1].name = "%fusion.1"
    plane.event_metadata[1].stats.add(metadata_id=1, ref_value=2)
    plane.lines.add(id=1, name="XLA Ops").events.add(metadata_id=1, offset_ps=5000,
                                                      duration_ps=2000)
    [ev] = list(xplane.events(plane))
    assert ev == (0, "XLA Ops", "%fusion.1", 5.0, 2.0, {"tf_op": "jit(decode_all)/attn/dot"})


@pytest.mark.parametrize("path,scope", [
    ("jit(decode_all)/decode_all/while/body/attn/kv_write/scatter", "kv_write"),
    ("jit(decode_all)/decode_all/while/body/attn/paged_attn/jit(paged_attention_grouped)"
     "/paged_attention_grouped/pallas_call", "paged_attn"),
    ("jit(decode_all)/decode_all/while/body/norm/jit(rmsnorm_2d)/rmsnorm_2d", "norm"),
    ("jit(decode_all)/decode_all/sample/argmax", "sample"),
    ("jit(decode_all)/decode_all/while/body/closed_call/mlp/dot_general:", "mlp"),
    ("jit(decode_all)/decode_all/sample:", "sample"),
    ("jit(decode_all)/decode_all/while/body/dynamic_update_slice:", "unscoped"),
    ("jit(decode_all)/decode_all/while/body/dynamic-slice", "unscoped"),
    ("jit(attention)/attn_chunk/add", "unscoped"),
    ("", "unscoped"),
])
def test_scope_is_the_innermost_model_scope(path, scope):
    assert spans.scope_of(path) == scope


@pytest.fixture(scope="module")
def red():
    return spans.reduce(str(PATH))


def test_steps_per_engine(red):
    assert red["window_s"] == pytest.approx(200 * US)
    # batch: steps 1010-1090 and 1100-1160 (990-1005 is before the window),
    # waits 10 (inside the prefill) + 35 and 38; its decodes ran 1066-1084
    # and 1112-1148 on the device
    assert red["steps"]["batch"] == pytest.approx({
        "steps": 2, "step_ms": 70e-3, "device_wait_ms": 41.5e-3, "host_ms": 28.5e-3,
        "own_decode_dev_ms": 27e-3})
    # interactive: 1190-1210 crosses the window's end and does not count
    assert red["steps"]["interactive"] == pytest.approx({
        "steps": 1, "step_ms": 50e-3, "device_wait_ms": 43e-3, "host_ms": 7e-3,
        "own_decode_dev_ms": 28e-3})


def test_decode_counts_per_engine(red):
    assert red["decode"] == {
        "batch": {"rows": 16, "live": 7, "pages_walked": 128, "pages_live": 12, "decodes": 2},
        "interactive": {"rows": 1, "live": 1, "pages_walked": 8, "pages_live": 2,
                        "decodes": 1}}


def test_decode_device_time_by_scope(red):
    """Three decode runs; the while loop contains the others and is left
    out; the prefill's operations are not the decode's."""
    assert red["decode_runs"] == 3
    assert red["decode_dev_ms_by_scope"] == pytest.approx({
        "unscoped": 28e-3 / 3, "paged_attn": 16e-3 / 3, "head": 6e-3 / 3, "norm": 4e-3 / 3,
        "kv_write": 10e-3 / 3, "mlp": 18e-3 / 3})


def test_idle_gaps_by_program_span(red):
    """Chip 0's gaps: 1005-1008 under no span; 1010-1021 mostly under the
    step; 1084-1112 as much under the decode as under its step, so the
    decode (the inner); 1148-1176 under the GC more than the step (the
    loop's idle wait overlaps it more, but waits are not work); 1178-1200
    under the compile more than the late step."""
    assert red["idle_s_by_program_span"] == pytest.approx({
        "none": 3 * US, "serve.engine.step": 11 * US, "serve.engine.decode": 28 * US,
        "serve.gc": 28 * US, "jax.compile": 22 * US})


def test_a_gap_that_opens_the_window_is_unobserved():
    """With the window opened at 1005 us, the first gap (1005-1008) has no
    span over it because nothing that began before a profiler session is
    recorded: it is unobserved, not idle under no span."""
    red = spans.reduce(str(PATH), window=(1005e3, 1200e3))
    assert red["idle_s_by_program_span"] == pytest.approx({
        "unobserved": 3 * US, "serve.engine.step": 11 * US, "serve.engine.decode": 28 * US,
        "serve.gc": 28 * US, "jax.compile": 22 * US})


def test_metrics(red):
    assert spans.busiest(red) == "batch"
    assert spans.step_host_ms(red) == pytest.approx(28.5e-3)
    assert spans.decode_rows_live_share(red) == pytest.approx(100 * 8 / 17)
    assert spans.kv_pages_live_share(red) == pytest.approx(100 * 14 / 136)
    assert spans.decode_unscoped_share(red) == pytest.approx(100 * 28 / 82)


def test_engine_queue_p95():
    reqs = [{"done": True, "start_t": 10.0, "admit_t": 10.0 + 0.01 * i} for i in range(21)]
    reqs += [{"done": False, "start_t": 0.0, "admit_t": 99.0},
             {"done": True, "start_t": 1.0, "admit_t": None}]
    assert spans.engine_queue_p95_ms(reqs) == pytest.approx(190.0)
    assert spans.engine_queue_p95_ms([]) is None


def _recorded_decodes():
    """The decode runs the cut keeps the operations of, and those
    operations."""
    [dev] = [p for p in xplane.load(str(RECORDED)).planes if p.name == TPU]
    evs = list(xplane.events(dev))
    ops = [e for e in evs if e[1] == "XLA Ops"]
    end = max(s + d for _, _, _, s, d, _ in ops)
    runs = [(s, s + d) for _, ln, n, s, d, _ in evs
            if ln == "XLA Modules" and n.startswith("jit_decode_all") and s + d <= end
            and any(s <= o[3] < s + d for o in ops)]
    return runs, [o for o in ops if any(a <= o[3] < b for a, b in runs)]


def test_recorded_chat_window_decode_counts_and_steps():
    """The recorded cut holds every ``serve.*`` span of a traced 3-s chat
    window: each tier decodes its whole slot count (1, 8 and 48 rows) and
    the kernel walks 128 pages of each 2048-token row; every step is mostly
    device wait, and an engine's own decode is part of that wait."""
    red = spans.reduce(str(RECORDED))
    assert set(red["decode"]) == {"interactive", "batch", "elastic"}
    for eng, rows in {"interactive": 1, "batch": 8, "elastic": 48}.items():
        d = red["decode"][eng]
        assert d["rows"] == rows * d["decodes"]
        assert d["pages_walked"] == 128 * d["rows"]
        assert 0 < d["live"] <= d["rows"] and 0 < d["pages_live"] <= d["pages_walked"]
    for st in red["steps"].values():
        assert 0 < st["host_ms"] < st["device_wait_ms"] < st["step_ms"]
    assert spans.busiest(red) == "elastic"


def test_recorded_decode_runs_by_scope():
    """Chip 0's interactive and batch decode runs in the cut: their
    operations' time by scope adds up to every operation's time but the
    loop's, the kernels sit in their scopes, and the scan's slices and
    copies are the unscoped part."""
    runs, ops = _recorded_decodes()
    red = spans.reduce(str(RECORDED), window=(runs[0][0], runs[-1][1]))
    assert red["decode_runs"] == 2
    total = sum(d for _, _, n, _, d, _ in ops if not n.startswith("%while"))
    by = red["decode_dev_ms_by_scope"]
    assert 2 * sum(by.values()) == pytest.approx(total * 1e-6, rel=1e-9)
    for _, _, n, _, _, st in ops:
        if n.startswith("%paged_attention_grouped"):
            assert spans.scope_of(st["tf_op"]) == "paged_attn"
        elif n.startswith("%rmsnorm_2d"):
            assert spans.scope_of(st["tf_op"]) == "norm"
    assert by["paged_attn"] > 0 and by["mlp"] > 0 and by["kv_write"] > 0
    assert 10 < spans.decode_unscoped_share(red) < 90


def test_a_trace_without_the_programs_spans_reads_nothing():
    """The harness's own synthetic trace: no ``serve.*`` span and no scope
    path, so every metric of this reduction is absent and nothing raises."""
    red = spans.reduce(str(DATA / "synthetic.xplane.pb"))
    assert red["steps"] == {} and red["decode"] == {}
    assert set(red["idle_s_by_program_span"]) == {"none"}
    assert all(fn(red) is None for fn in spans.METRICS.values())


def record(src: str, t0_ms: float, t1_ms: float) -> bytes:
    """Cut ``RECORDED`` from a whole traced window: every ``serve.*`` span
    and the window span of the host plane with their stats, chip 0's
    program runs, and its operations that start in [t0_ms, t1_ms) with
    their names cut at `` =`` and ``tf_op`` the one stat kept."""
    out = xplane.XSpace()
    for plane in xplane.load(src).planes:
        dev = plane.name == "/device:TPU:0"
        if not dev and plane.name != "/host:CPU":
            continue
        new = out.planes.add(id=plane.id, name=plane.name)
        names = {k: m.name for k, m in plane.stat_metadata.items()}
        for line in plane.lines:
            keep = []
            for ev in line.events:
                t = (line.timestamp_ns * 1000 + ev.offset_ps) / 1e9
                name = plane.event_metadata[ev.metadata_id].name
                if (dev and (line.name == "XLA Modules" or (
                        line.name == "XLA Ops" and t0_ms <= t < t1_ms))) or (
                        not dev and (name.startswith("serve.") or name == "chipbench.window")):
                    keep.append(ev)
            if not keep:
                continue
            ln = new.lines.add(id=line.id, name=line.name, timestamp_ns=line.timestamp_ns)
            for ev in keep:
                ln.events.add().CopyFrom(ev)
                if dev:
                    del ln.events[-1].stats[:]
                m = plane.event_metadata[ev.metadata_id]
                if ev.metadata_id not in new.event_metadata:
                    nm = new.event_metadata[ev.metadata_id]
                    nm.id, nm.name = m.id, m.name.split(" =")[0] if dev else m.name
                    for st in m.stats:
                        if not dev or names.get(st.metadata_id) == "tf_op":
                            nm.stats.add().CopyFrom(st)
            for ev in ln.events:
                for st in list(ev.stats) + list(new.event_metadata[ev.metadata_id].stats):
                    for sid in (st.metadata_id, st.ref_value):     # a ref names a string
                        if sid in plane.stat_metadata:
                            new.stat_metadata[sid].CopyFrom(plane.stat_metadata[sid])
    return out.SerializeToString()


if __name__ == "__main__":
    if sys.argv[1:2] == ["--record"]:      # --record <whole.xplane.pb> <t0 ms> <t1 ms>
        RECORDED.write_bytes(record(sys.argv[2], float(sys.argv[3]), float(sys.argv[4])))
    else:
        PATH.write_bytes(xplane.build(SPANS))
