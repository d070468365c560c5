"""The whole step's share of the chip's peak: model FLOPs of every prompt and
output token the engines processed in the traced window (``flops.py``, from
shapes), over the union of the ``step()`` intervals times the peak bf16
FLOP/s (%)."""
import flops


def read(obs):
    steps = obs.get("steps")
    if not steps:
        return None
    m = obs["model"]
    work = 0.0
    for _, _, _, prefill, decoded in steps:
        work += sum(flops.span_flops(m, a, b, emits) for a, b, emits in prefill)
        work += sum(flops.token_flops(m, p, True) for p in decoded)
    spans = sorted((r[1], r[2]) for r in steps)
    busy, end = 0.0, None
    for s, e in spans:
        if end is None or s > end:
            busy += e - s
            end = e
        elif e > end:
            busy += e - end
            end = e
    if work <= 0 or busy <= 0:
        return None
    return 100.0 * work / (busy * obs["peaks"]["bf16_flops"])
