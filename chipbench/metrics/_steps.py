"""Shared by the step-loop readers: the traced steps of the tier that decoded
the most tokens. Each step record is (tier, t0, t1, prefill spans, decoded
positions)."""


def busiest(obs):
    steps = obs.get("steps")
    if not steps:
        return None
    by = {}
    for rec in steps:
        by.setdefault(rec[0], []).append(rec)
    tier = max(by, key=lambda t: sum(len(r[4]) for r in by[t]))
    return by[tier]
