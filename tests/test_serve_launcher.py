"""The serving launcher end to end at smoke size: ``serve()`` returns every
request's full-length output, and raises — so the launcher exits non-zero —
when a tier fails, even though the router hides the error by spilling the
request to the elastic tier."""
import pytest

from repro.launch import serve as launcher
from repro.serving import scheduler


def _args(*extra):
    return launcher.build_parser().parse_args(
        ["--smoke", "--requests", "4", "--max-new-tokens", "4", *extra]
    )


def test_serve_returns_full_length_outputs():
    res = launcher.serve(_args("--workers", "2"))
    assert not res.metrics.failed and len(res.metrics.completed) == 4
    assert sorted(res.outputs) == [0, 1, 2, 3]
    assert all(len(out) == 4 for out in res.outputs.values())
    assert all(launcher.PROMPT_TOKENS[0] <= len(p) <= launcher.PROMPT_TOKENS[1]
               for p in res.prompts)
    assert {"interactive", "batch"} <= set(res.engines)


def test_serve_raises_when_a_tier_fails(monkeypatch):
    def lost(self, sid, timeout=None):
        raise RuntimeError("device lost")

    monkeypatch.setattr(scheduler.EngineLoop, "wait", lost)
    with pytest.raises(launcher.ServeFailed, match="tier errors"):
        launcher.serve(_args())
