"""Paged decode attention against its roofline: the least time the chip
could take for the work the decoding sequences needed in the traced window
(the larger of bytes over HBM bandwidth and FLOPs over peak, from
``flops.py``), over the device time of the kernel
(``paged_attention_grouped``) in the trace (%)."""
import flops


def read(obs):
    red, steps = obs.get("trace"), obs.get("steps")
    if not red or not steps:
        return None
    t_kernel = red["ops"].get("paged_attention_grouped", 0.0)
    if t_kernel <= 0:
        return None
    m, pk, page = obs["model"], obs["peaks"], obs["page"]
    pos = [p for r in steps for p in r[4]]
    nbytes = sum(flops.paged_decode_bytes(m, p, page, m["kv_bytes"], m["act_bytes"]) for p in pos)
    work = sum(flops.paged_decode_flops(m, p) for p in pos)
    t_min = max(nbytes / pk["hbm_bytes_per_s"], work / pk["bf16_flops"])
    return 100.0 * t_min / t_kernel if t_min > 0 else None
