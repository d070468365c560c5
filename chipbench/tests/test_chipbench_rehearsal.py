"""A CPU rehearsal of a whole run at smoke size (``chipbench_smoke.py``): the
pipeline's counts, its control, and the command's refusal of the CPU."""
import os
import subprocess
import sys
import time

import pytest

import chipbench_smoke as smoke
import harness  # on the path through chipbench_smoke


@pytest.fixture(scope="module")
def parts(tmp_path_factory):
    return smoke.parts(tmp_path_factory.mktemp("rehearsal"))


def test_run_counts(parts):
    t = time.monotonic()
    res = harness.run(parts, smoke.CELL, smoke.BIG_SEED, 4.0, False, t)
    mix = parts.traffic("tiny")
    assert res["attempted"] == round(mix["arrivals"]["rate_per_s"] * 4.0)
    assert res["failed"] == 0
    assert res["correct"] is True
    info = res["info"]
    assert "window" not in info["compiles"]            # nothing compiled in the window
    assert info["compiles"]["setup"] > 0
    assert info["window_done"] == res["attempted"]
    assert sum(info["served_by_tier"].values()) >= res["attempted"]
    assert info["check"]["tokens"] == mix["check_requests"] * mix["output_tokens"]
    assert set(info["check_tiers"]) == {t for t, n in info["served_by_tier"].items() if n}
    assert res["checks"]["short_outputs"]["value"] == 0
    e2e = {m["name"] for m in parts.end_to_end_for(smoke.CELL)}
    assert set(res["metrics"]) == e2e
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["metrics"]["setup_s"]["value"] == pytest.approx(info["setup_s"])


def test_control_is_not_correct(parts):
    """The reference computed in the configuration's control precision
    (bfloat16 for this float32 cell), judged in the program's place through
    the run's own checks, is not correct where the program is."""
    for seed in (1, smoke.BIG_SEED):
        res = harness.run(parts, smoke.CELL, seed, 4.0, False, time.monotonic(), control=True)
        assert res["correct"] is False
        gap = res["checks"]["max_logit_gap"]
        assert gap["value"] > gap["limit"]
        assert res["info"]["check"]["tokens"] >= 300


def test_command_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(smoke.BENCH / "run.py"), "--workload", "smollm-360m.chat",
         "--seed", str(smoke.BIG_SEED), "--seconds", "1", "--trace", "0"],
        cwd=smoke.BENCH.parent, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "needs 1 TPU chip" in p.stderr


def test_traced_run(parts, monkeypatch):
    """The traced path end to end: the profiler runs (on the CPU), the step
    log fills, and every per-layer metric of the cell is read. The CPU's
    trace has no TPU plane, so the recorded synthetic trace stands in for
    the device's."""
    import xtrace

    reduce = xtrace.reduce
    monkeypatch.setattr(xtrace, "reduce", lambda path: reduce(
        str(smoke.BENCH / "testdata" / "synthetic.xplane.pb")))
    res = harness.run(parts, smoke.CELL, smoke.BIG_SEED, 4.0, True, time.monotonic())
    assert res["correct"] is True
    per_layer = {m["name"] for m in parts.per_layer_for(smoke.CELL)}
    assert set(res["metrics"]) == per_layer
    assert res["device_trace"]["busy_s"] == pytest.approx(50e-6)
    assert [k for k, _ in res["breakdown"]["idle_gaps"]] == ["router.submit", "engine.step.batch"]
    assert res["metrics"]["decode_batch_mean"]["value"] >= 1
    assert res["info"]["idle_s_by_span"]["router.submit"] == pytest.approx(30e-6)
