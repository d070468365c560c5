"""A whole run at smoke size with the timed path broken underneath (each
fault of ``faults.py``): ``correct`` comes out false."""
import time

import pytest

import chipbench_smoke as smoke
import deploy
import faults
import harness


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_is_not_correct(fault, monkeypatch, tmp_path):
    parts = smoke.parts(tmp_path)
    build = deploy.build

    def broken(*args, **kwargs):
        dep = build(*args, **kwargs)
        for eng in dep.engines.values():
            faults.FAULTS[fault](eng)
        return dep

    monkeypatch.setattr(deploy, "build", broken)
    res = harness.run(parts, smoke.CELL, smoke.BIG_SEED, 4.0, False, time.monotonic())
    assert res["correct"] is False
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
    assert res["checks"]["short_outputs"]["value"] == 0
