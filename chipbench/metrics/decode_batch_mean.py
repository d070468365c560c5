"""Tokens decoded per step that decoded, in the traced window, on the tier
that decoded the most: counted from the engine's slot state around each
``step()`` call."""
import spec

_steps = spec.load_module(spec.HERE / "metrics" / "_steps.py")


def read(obs):
    steps = _steps.busiest(obs)
    if not steps:
        return None
    dec = [len(r[4]) for r in steps if r[4]]
    return sum(dec) / len(dec) if dec else None
