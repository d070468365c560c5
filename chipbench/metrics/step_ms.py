"""Host time per ``engine.step()`` call in the traced window, on the tier
that decoded the most: total time over calls (ms)."""
import spec

_steps = spec.load_module(spec.HERE / "metrics" / "_steps.py")


def read(obs):
    steps = _steps.busiest(obs)
    if not steps:
        return None
    return 1e3 * sum(r[2] - r[1] for r in steps) / len(steps)
